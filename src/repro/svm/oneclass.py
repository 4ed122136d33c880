"""sklearn-style ``OneClassSVM`` facade: ν novelty detection on PA-SMO.

The fit is the one-class instance of the generalized dual
(:func:`repro.core.qp.oneclass_qp`): ``p = 0``, box ``[0, 1/(nu l)]``,
equality ``sum(a) = 1`` — started from the LIBSVM feasible point
(:func:`repro.core.qp.oneclass_alpha0`) since 0 is infeasible, with its
gradient ``G0 = -K alpha0`` paid as one matvec before the loop.  Engines
mirror :class:`repro.svm.svc.SVC` (one fused lane, or the standard solver
on a kernel oracle).  The decision function is

    f(x) = k(x, X) @ alpha - rho,   rho = -b

(the solver's universal bias estimate ``b = (max_up G + min_down G) / 2``
equals ``-rho`` here); ``predict`` returns +1 for inliers, -1 for
outliers, and the fraction of training outliers approaches ``nu``.
"""

from __future__ import annotations

from functools import partial
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qp as qp_mod
from repro.core.sharded_lanes import solve_fused_sharded_qp
from repro.core.solver import solve_qp
from repro.core.solver_fused import solve_fused_batched_qp
from repro.kernels import ops
from repro.kernels.ref import HIGHEST
from repro.svm.base import SVMEstimatorBase
from repro.telemetry import span


class OneClassSVM(SVMEstimatorBase):
    """RBF one-class SVM driven by the planning-ahead solver.

    ``nu`` in (0, 1) upper-bounds the training-outlier fraction and
    lower-bounds the support-vector fraction.  Remaining knobs as in
    :class:`repro.svm.svc.SVC`.
    """

    def __init__(self, nu: float = 0.5, gamma: Union[float, str] = "scale",
                 *, algorithm: str = "pasmo", step: str = "plain",
                 eps: float = 1e-3,
                 max_iter: int = 1_000_000, plan_candidates: int = 1,
                 impl: str = "auto", engine: str = "auto",
                 precompute: bool = True, dtype=None, mesh=None,
                 devices=None, diagnostics=None):
        if not 0.0 < nu <= 1.0:
            raise ValueError(f"nu must be in (0, 1], got {nu!r}")
        self.nu = nu
        self.gamma = gamma
        self._init_common(algorithm=algorithm, eps=eps, max_iter=max_iter,
                          plan_candidates=plan_candidates, impl=impl,
                          engine=engine, precompute=precompute, dtype=dtype,
                          step=step, mesh=mesh, devices=devices,
                          diagnostics=diagnostics)

    def fit(self, X, y=None) -> "OneClassSVM":
        with self._fit_scope("oneclass_fit") as sp:
            self._fit(X, sp)
        return self

    def _fit(self, X, sp) -> None:
        X = jnp.asarray(X, self.dtype)
        l = X.shape[0]
        self.gamma_ = self._resolve_gamma(X)
        self.X_ = X
        cfg = self._config()
        engine = self._resolve_engine()
        qp = qp_mod.oneclass_qp(l, self.nu, self.dtype)
        a0 = qp_mod.oneclass_alpha0(l, self.nu, self.dtype)
        sp.attrs.update(engine=engine, rows=int(X.shape[0]))

        tel = self._ring_config()
        ring = None
        with span("fit.solve"):
            if engine in ("fused", "sharded"):
                bank_kw = {}
                if self.precompute and ops.resolve_impl(self.impl) == "jnp":
                    K = ops.gram(X, gamma=self.gamma_,
                                 impl=self.impl).astype(self.dtype)
                    G0 = -(K @ a0)
                    bank_kw = dict(gram=K[None],
                                   gram_idx=jnp.zeros((1,), jnp.int32))
                else:
                    G0 = -qp_mod.make_rbf(X, self.gamma_).matvec(a0)
                if engine == "sharded":
                    solver = partial(solve_fused_sharded_qp, mesh=self.mesh,
                                     devices=self.devices)
                else:
                    solver = solve_fused_batched_qp
                out = solver(
                    X, qp.p[None], qp.bounds.lower[None],
                    qp.bounds.upper[None], self.gamma_, cfg, impl=self.impl,
                    alpha0=a0[None], G0=G0[None], telemetry=tel, **bank_kw)
                if tel is not None:
                    out, ring = out
                res = jax.tree.map(lambda leaf: leaf[0], out)
            else:
                if self.precompute:
                    K = ops.gram(X, gamma=self.gamma_, impl=self.impl)
                    kern = qp_mod.PrecomputedKernel(K.astype(self.dtype))
                else:
                    kern = qp_mod.make_rbf(X, self.gamma_)
                res = solve_qp(kern, qp, cfg, alpha0=a0)
        self._hold_counters(sp, res)
        if self.diagnostics is not None:
            jax.block_until_ready(res.alpha)
        if ring is not None:
            self.diagnostics.drain_ring(
                ring, [{"gamma": self.gamma_, "nu": float(self.nu)}], out)
        self.fit_result_ = res
        self.engine_ = engine
        self.alpha_ = res.alpha
        self.b_ = res.b
        self.rho_ = float(-res.b)

    def decision_function(self, Xq) -> jnp.ndarray:
        """Signed distance to the separating surface: >= 0 for inliers."""
        self._check_fitted()
        Kq, squeeze = self._query_gram(Xq)
        df = jnp.dot(Kq, self.alpha_, precision=HIGHEST) + self.b_
        return df[0] if squeeze else df

    def predict(self, Xq) -> np.ndarray:
        """+1 (inlier) / -1 (outlier), sklearn convention."""
        self._check_fitted()
        df = np.asarray(self.decision_function(Xq))
        return np.where(df >= 0, 1, -1).astype(np.int64)

    @property
    def n_support_(self) -> int:
        """Number of support vectors (nonzero duals)."""
        self._check_fitted()
        return int((np.asarray(self.alpha_) > 1e-12).sum())
