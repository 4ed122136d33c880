"""sklearn-style ``SVR`` facade: ε-insensitive regression on the PA-SMO core.

The fit is ONE generalized dual QP (:func:`repro.core.qp.svr_qp`): 2l
doubled variables sharing the base l x l Gram through the sign-folded
operator — rows are tiled base rows, so no 2l x 2l matrix is ever
materialized in either engine.  Engines mirror :class:`repro.svm.svc.SVC`:

* ``"fused"``   — one lane of the fused two-pass batched solver with
  ``doubled=True`` (:func:`repro.core.solver_fused.solve_fused_batched_qp`).
* ``"batched"`` — the standard solver over a
  :class:`~repro.core.qp.DoubledKernel` oracle (supports every
  algorithm/ablation knob).

Prediction reuses the SVC Gram machinery: ``f(x) = k(x, X) @ beta + b``
with ``beta = alpha[:l] + alpha[l:]`` (:func:`repro.core.qp.svr_fold`).

    >>> reg = SVR(C=10.0, epsilon=0.1, gamma=0.5).fit(X, y)
    >>> reg.predict(Xq)
"""

from __future__ import annotations

from functools import partial
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qp as qp_mod
from repro.core.solver import solve_qp
from repro.core.sharded_lanes import solve_fused_sharded_qp
from repro.core.solver_fused import solve_fused_batched_qp
from repro.kernels import ops
from repro.kernels.ref import HIGHEST
from repro.svm.base import SVMEstimatorBase
from repro.telemetry import span


class SVR(SVMEstimatorBase):
    """RBF ε-support-vector regression driven by the planning-ahead solver.

    ``C`` is the box budget, ``epsilon`` the insensitive-tube half-width,
    ``gamma`` a float or ``"scale"``; ``eps`` is the KKT stopping accuracy
    (solver tolerance, NOT the tube).  ``impl``/``engine``/``precompute``
    — and the ``algorithm``/``step`` solver knobs, including
    ``step="conjugate"`` — select backends exactly as in
    :class:`repro.svm.svc.SVC`.  The fit is
    a single QP lane, so ``engine="auto"`` never picks ``"sharded"`` here
    — an explicit ``engine="sharded"`` (with optional ``mesh``/``devices``)
    still routes the lane through the sharded engine, mainly so grid code
    can treat all three facades uniformly.
    """

    _fit_attr = "beta_"

    def __init__(self, C: float = 1.0, epsilon: float = 0.1,
                 gamma: Union[float, str] = "scale", *,
                 algorithm: str = "pasmo", step: str = "plain",
                 eps: float = 1e-3,
                 max_iter: int = 1_000_000, plan_candidates: int = 1,
                 impl: str = "auto", engine: str = "auto",
                 precompute: bool = True, dtype=None, mesh=None,
                 devices=None, diagnostics=None):
        self.C = C
        self.epsilon = epsilon
        self.gamma = gamma
        self._init_common(algorithm=algorithm, eps=eps, max_iter=max_iter,
                          plan_candidates=plan_candidates, impl=impl,
                          engine=engine, precompute=precompute, dtype=dtype,
                          step=step, mesh=mesh, devices=devices,
                          diagnostics=diagnostics)

    def fit(self, X, y) -> "SVR":
        with self._fit_scope("svr_fit") as sp:
            self._fit(X, y, sp)
        return self

    def _fit(self, X, y, sp) -> None:
        X = jnp.asarray(X, self.dtype)
        y = jnp.asarray(y, self.dtype)
        self.gamma_ = self._resolve_gamma(X)
        self.X_ = X
        cfg = self._config()
        engine = self._resolve_engine()
        sp.attrs.update(engine=engine, rows=int(X.shape[0]))
        qp = qp_mod.svr_qp(y, float(self.C), float(self.epsilon))

        tel = self._ring_config()
        ring = None
        with span("fit.solve"):
            if engine in ("fused", "sharded"):
                bank_kw = {}
                if self.precompute and ops.resolve_impl(self.impl) == "jnp":
                    K = ops.gram(X, gamma=self.gamma_, impl=self.impl)
                    bank_kw = dict(gram=K[None].astype(self.dtype),
                                   gram_idx=jnp.zeros((1,), jnp.int32))
                if engine == "sharded":
                    solver = partial(solve_fused_sharded_qp, mesh=self.mesh,
                                     devices=self.devices)
                else:
                    solver = solve_fused_batched_qp
                out = solver(
                    X, qp.p[None], qp.bounds.lower[None],
                    qp.bounds.upper[None], self.gamma_, cfg, impl=self.impl,
                    doubled=True, telemetry=tel, **bank_kw)
                if tel is not None:
                    out, ring = out
                res = jax.tree.map(lambda leaf: leaf[0], out)
            else:
                if self.precompute:
                    K = ops.gram(X, gamma=self.gamma_, impl=self.impl)
                    base = qp_mod.PrecomputedKernel(K.astype(self.dtype))
                else:
                    base = qp_mod.make_rbf(X, self.gamma_)
                res = solve_qp(qp_mod.DoubledKernel(base), qp, cfg)
        self._hold_counters(sp, res)
        if self.diagnostics is not None:
            jax.block_until_ready(res.alpha)
        if ring is not None:
            self.diagnostics.drain_ring(
                ring, [{"gamma": self.gamma_, "C": float(self.C),
                        "epsilon": float(self.epsilon)}], out)
        self.fit_result_ = res
        self.engine_ = engine
        self.alpha_ = res.alpha                    # (2l,) doubled dual
        self.beta_ = qp_mod.svr_fold(res.alpha)    # (l,) coefficients
        self.b_ = res.b

    def predict(self, Xq) -> jnp.ndarray:
        self._check_fitted()
        Kq, squeeze = self._query_gram(Xq)
        f = jnp.dot(Kq, self.beta_, precision=HIGHEST) + self.b_
        return f[0] if squeeze else f

    def score(self, Xq, yq) -> float:
        """Coefficient of determination R^2 (sklearn convention)."""
        yq = np.asarray(yq, np.float64)
        pred = np.asarray(self.predict(Xq), np.float64)
        ss_res = float(np.sum((yq - pred) ** 2))
        ss_tot = float(np.sum((yq - yq.mean()) ** 2))
        return 1.0 - ss_res / ss_tot if ss_tot > 0 else 0.0

    @property
    def n_support_(self) -> int:
        """Number of support vectors (nonzero folded coefficients)."""
        self._check_fitted()
        return int((np.abs(np.asarray(self.beta_)) > 1e-9).sum())
