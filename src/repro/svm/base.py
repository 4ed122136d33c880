"""Shared plumbing for the sklearn-style facades (SVC / SVR / OneClassSVM).

One copy of the solver-knob wiring, the ``gamma="scale"`` resolution, the
fused-engine eligibility rule, and the batched query-Gram helper — the
estimators differ only in which :class:`repro.core.qp.DualQP` instance
they build and how they post-process the dual.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solver import SolverConfig
from repro.kernels import ops
from repro.telemetry import phase_scope, span


class SVMEstimatorBase:
    """Mixin holding the facade knobs shared by every estimator.

    Subclasses set ``_fit_attr`` to the attribute whose presence marks a
    fitted model and call :meth:`_init_common` from their ``__init__``.
    """

    _fit_attr = "alpha_"

    def _init_common(self, *, algorithm: str, eps: float, max_iter: int,
                     plan_candidates: int, impl: str, engine: str,
                     precompute: bool, dtype, step: str = "plain",
                     mesh=None, devices=None, diagnostics=None) -> None:
        if engine not in ("auto", "fused", "batched", "sharded"):
            raise ValueError(f"engine must be auto|fused|batched|sharded, "
                             f"got {engine!r}")
        if engine in ("fused", "batched") and (mesh is not None
                                               or devices is not None):
            raise ValueError("mesh/devices belong to the sharded engine — "
                             f"drop them or use engine='sharded'/'auto', "
                             f"got engine={engine!r}")
        self.algorithm = algorithm
        self.step = step
        self.eps = eps
        self.max_iter = max_iter
        self.plan_candidates = plan_candidates
        self.impl = impl
        self.engine = engine
        self.precompute = precompute
        self.mesh = mesh
        self.devices = devices
        self.diagnostics = diagnostics
        # f64 when x64 is on (the paper-accuracy setting), else a clean f32
        # fallback instead of per-call truncation warnings
        self.dtype = dtype if dtype is not None else (
            jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)

    def _ring_config(self):
        """Device-tier telemetry geometry, when the flight recorder is on.

        The static :class:`~repro.telemetry.ring.RingConfig` of the
        attached :class:`~repro.telemetry.Diagnostics` handle, or ``None``
        — the engines then trace their telemetry-free jaxpr.  Only the
        fused/sharded engines carry rings; on the classic batched engine a
        ``diagnostics=`` handle still records host-tier fit phases.
        """
        if self.diagnostics is None:
            return None
        return self.diagnostics.ring_config

    def _fit_scope(self, name: str):
        """The fit's root span (:mod:`repro.telemetry.spans`), always on;
        with a ``diagnostics`` handle it also emits the ``phase`` event."""
        return phase_scope(name, None if self.diagnostics is None
                           else self.diagnostics.sink)

    @staticmethod
    def _hold_counters(sp, res) -> None:
        """Keep the engine's counters on the fit's root span, unread:
        ``n_unshrink`` (reactivations, exit-check reopenings among them)
        where the engine reports it."""
        extra = ({} if getattr(res, "n_unshrink", None) is None
                 else {"n_unshrink": res.n_unshrink})
        sp.hold(iterations=res.iterations, n_planning=res.n_planning,
                converged=res.converged, **extra)

    def _config(self) -> SolverConfig:
        return SolverConfig(algorithm=self.algorithm, step=self.step,
                            eps=self.eps, max_iter=self.max_iter,
                            plan_candidates=self.plan_candidates)

    @span("fit.gamma")
    def _resolve_gamma(self, X) -> float:
        if self.gamma == "scale":
            var = float(np.asarray(X).var())
            return 1.0 / (X.shape[1] * var) if var > 0 else 1.0
        return float(self.gamma)

    def _resolve_engine(self, n_lanes: int = 1) -> str:
        """Pick the fit engine; ``n_lanes`` is the QP lane count of the
        upcoming fit (class heads for SVC, 1 for SVR/one-class) — ``auto``
        only shards when there is more than one lane to spread."""
        fusable = (self.algorithm in ("smo", "pasmo")
                   and self.plan_candidates == 1)
        if self.engine == "sharded":
            if not fusable:
                raise ValueError(
                    "engine='sharded' runs on the fused engine, which needs "
                    "algorithm in ('smo', 'pasmo') and plan_candidates == 1")
            return "sharded"
        if self.engine != "auto":
            return self.engine
        if not fusable:
            return "batched"
        if (self.mesh is not None or self.devices is not None
                or (n_lanes > 1 and len(jax.devices()) > 1)):
            return "sharded"
        return "fused"

    def _check_fitted(self):
        if not hasattr(self, self._fit_attr):
            raise RuntimeError(
                f"{type(self).__name__} instance is not fitted yet")

    def _query_gram(self, Xq):
        """Query cross-Gram against the training set -> (Kq, squeeze)."""
        Xq = jnp.asarray(Xq, self.dtype)
        squeeze = Xq.ndim == 1
        if squeeze:
            Xq = Xq[None, :]
        Kq = ops.gram(Xq, self.X_, gamma=self.gamma_, impl=self.impl)
        return Kq.astype(self.dtype), squeeze
