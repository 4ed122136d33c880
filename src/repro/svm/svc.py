"""sklearn-style ``SVC`` facade over the PA-SMO core.

Binary problems are one signed-dual QP; multiclass problems are reduced
one-vs-rest.  Two fit engines (selected by ``engine``):

* ``"fused"`` — the fused two-pass batched solver
  (:mod:`repro.core.solver_fused`): two kernel passes per iteration for
  the whole class stack, converged heads frozen in-kernel; ``precompute``
  picks the row source (Gram-bank gathers vs on-the-fly X rows).  The
  default whenever the solver config is compatible (``algorithm`` in
  smo/pasmo, ``plan_candidates == 1``).
* ``"batched"`` — the standard vmapped solver over a precomputed Gram
  matrix (or on-the-fly rows with ``precompute=False``); supports every
  algorithm/ablation knob.

Prediction is batched through :func:`repro.kernels.ops.gram`, so the query
cross-kernel is computed once for all class heads (and hits the Pallas
path on TPU).

    >>> clf = SVC(C=10.0, gamma=0.5).fit(X, y)
    >>> clf.predict(Xq)            # labels, any dtype y was given in
    >>> clf.decision_function(Xq)  # (m,) binary margin or (m, k) OVR scores
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import multiclass as mc
from repro.core import qp as qp_mod
from repro.core.solver import SolveResult, solve
from repro.core.solver_fused import FusedResult
from repro.kernels import ops
from repro.kernels.ref import HIGHEST
from repro.svm.base import SVMEstimatorBase
from repro.telemetry import span


class SVC(SVMEstimatorBase):
    """RBF support-vector classifier driven by the planning-ahead solver.

    Parameters mirror sklearn where they overlap: ``C`` (scalar, or a
    per-class vector for one-vs-rest), ``gamma`` (float or ``"scale"``),
    ``class_weight`` (``None``, ``"balanced"``, or a ``{label: weight}``
    dict — sample ``i`` of class ``c`` gets budget ``C * w_c``, i.e. a
    per-coordinate box of the generalized dual; requires scalar ``C``).
    Solver knobs (``algorithm``, ``step``, ``eps``, ``max_iter``,
    ``plan_candidates``) map onto
    :class:`repro.core.solver.SolverConfig` — ``step="conjugate"``
    (requires ``algorithm="smo"``) selects the Conjugate-SMO
    two-direction step; ``impl`` selects the
    kernel backend (``"auto"`` = Pallas on TPU, jnp elsewhere) for both the
    fused fit engine and the predict Gram work; ``engine`` picks the fit
    engine (``"auto"`` resolves to ``"sharded"`` on a multiclass fit with
    more than one device attached — or whenever ``mesh``/``devices`` is
    given — else ``"fused"`` when the config allows it, else
    ``"batched"``); ``precompute=False`` trades the O(l^2) Gram
    memory for on-the-fly kernel rows in either engine (in the fused
    engine ``precompute=True`` builds the shared Gram bank on the jnp
    backend — the CPU throughput mode).  ``engine="sharded"`` lane-shards
    the class heads over a device mesh
    (:mod:`repro.core.sharded_lanes`) — identical fit, one while_loop per
    device slab; ``mesh``/``devices`` pin the mesh (default: every
    attached device).  ``diagnostics`` (a
    :class:`~repro.telemetry.Diagnostics` handle) turns on the flight
    recorder: fit phases are timed on the host, and on the fused/sharded
    engines each class head drains a per-lane
    :class:`~repro.telemetry.ring.TelemetryRing` (KKT-gap trajectory,
    active-set size, planning mu/mu* ratios) into the handle's JSONL sink
    — render it with ``python -m repro.launch.telemetry_report``.
    """

    def __init__(self, C: Union[float, np.ndarray] = 1.0,
                 gamma: Union[float, str] = "scale", *,
                 class_weight: Union[dict, str, None] = None,
                 algorithm: str = "pasmo", step: str = "plain",
                 eps: float = 1e-3,
                 max_iter: int = 1_000_000, plan_candidates: int = 1,
                 impl: str = "auto", engine: str = "auto",
                 precompute: bool = True, dtype=None, mesh=None,
                 devices=None, diagnostics=None):
        if not (class_weight is None or class_weight == "balanced"
                or isinstance(class_weight, dict)):
            raise ValueError("class_weight must be None, 'balanced' or a "
                             f"{{label: weight}} dict, got {class_weight!r}")
        self.C = C
        self.class_weight = class_weight
        self.gamma = gamma
        self._init_common(algorithm=algorithm, eps=eps, max_iter=max_iter,
                          plan_candidates=plan_candidates, impl=impl,
                          engine=engine, precompute=precompute, dtype=dtype,
                          step=step, mesh=mesh, devices=devices,
                          diagnostics=diagnostics)

    # -- fitting ------------------------------------------------------------

    def _sample_weights(self, y_idx: np.ndarray, k: int) -> np.ndarray:
        """Per-sample class weights w_{y_i} (class_weight is not None)."""
        if self.class_weight == "balanced":
            counts = np.bincount(y_idx, minlength=k)
            w = len(y_idx) / (k * np.maximum(counts, 1))
        else:
            w = np.array([float(self.class_weight.get(c, 1.0))
                          for c in self.classes_])
        return w[y_idx]

    def fit(self, X, y) -> "SVC":
        with self._fit_scope("svc_fit") as sp:
            self._fit(X, y, sp)
        return self

    def _fit(self, X, y, sp) -> None:
        X = jnp.asarray(X, self.dtype)
        self.classes_, y_idx = mc.class_index(y)
        k = len(self.classes_)
        if k < 2:
            raise ValueError("fit needs at least two classes")
        self.gamma_ = self._resolve_gamma(X)
        self.X_ = X
        cfg = self._config()
        engine = self._resolve_engine(n_lanes=1 if k == 2 else k)
        sp.attrs.update(engine=engine, n_class=k, rows=int(X.shape[0]))

        if k == 2 and np.asarray(self.C).size != 1:
            raise ValueError("per-class C requires more than two "
                             "classes (binary problems are one QP)")
        if self.class_weight is not None:
            # per-sample budgets C_i = C * w_{y_i}: a per-coordinate box of
            # the generalized dual, shared by all one-vs-rest heads
            if np.asarray(self.C).size != 1:
                raise ValueError("class_weight requires a scalar C")
            Csamp = jnp.asarray(
                float(np.asarray(self.C).reshape(()))
                * self._sample_weights(y_idx, k), self.dtype)
            C_bin, C_ovr = Csamp, jnp.broadcast_to(Csamp, (k, len(y_idx)))
        else:
            C_bin = float(np.asarray(self.C).reshape(())) if k == 2 else None
            C_ovr = jnp.asarray(self.C, self.dtype)
        if k == 2:
            yb = jnp.where(jnp.asarray(y_idx) == 1, 1.0, -1.0) \
                    .astype(self.dtype)
        else:
            Y = mc.ovr_labels(y_idx, k, self.dtype)

        tel = self._ring_config()
        ring = None
        with span("fit.solve"):
            if engine in ("fused", "sharded"):
                shard_kw = {}
                if engine == "sharded":
                    shard_kw = dict(mesh=self.mesh, devices=self.devices)
                    if self.mesh is None and self.devices is None:
                        shard_kw["devices"] = tuple(jax.devices())
                if k == 2:
                    C_arg = (C_bin[None, :] if self.class_weight is not None
                             else C_bin)
                    out = mc.solve_ovr_fused(X, yb[None, :], C_arg,
                                             self.gamma_, cfg, impl=self.impl,
                                             precompute=self.precompute,
                                             telemetry=tel, **shard_kw)
                else:
                    out = mc.solve_ovr_fused(X, Y, C_ovr,
                                             self.gamma_, cfg, impl=self.impl,
                                             precompute=self.precompute,
                                             telemetry=tel, **shard_kw)
                if tel is not None:
                    out, ring = out
                res = (jax.tree.map(lambda leaf: leaf[0], out)
                       if k == 2 else out)
            else:
                if self.precompute:
                    K = ops.gram(X, gamma=self.gamma_, impl=self.impl)
                    kern = qp_mod.PrecomputedKernel(K.astype(self.dtype))
                else:
                    kern = qp_mod.make_rbf(X, self.gamma_)
                if k == 2:
                    res = solve(kern, yb, C_bin, cfg)
                else:
                    res = mc.solve_ovr(kern, Y, C_ovr, cfg)
        self._hold_counters(sp, res)
        if self.diagnostics is not None:
            jax.block_until_ready(res.alpha)
        if ring is not None:
            # one lane per class head (the lone head of a binary fit is the
            # "classes_[1] vs rest" problem, label index 1)
            Cv = np.asarray(self.C, float).reshape(-1)
            heads = [1] if k == 2 else range(k)
            meta = [{"gamma": self.gamma_, "label": int(c),
                     **({} if self.class_weight is not None else
                        {"C": float(Cv[c] if Cv.size > 1 else Cv[0])})}
                    for c in heads]
            self.diagnostics.drain_ring(ring, meta, out)
        self.fit_result_: Union[SolveResult, FusedResult] = res
        self.engine_ = engine
        self.alpha_ = res.alpha          # (l,) binary, (k, l) one-vs-rest
        self.b_ = res.b

    # -- inference ----------------------------------------------------------

    def decision_function(self, Xq) -> jnp.ndarray:
        """Binary: (m,) signed margin (positive -> ``classes_[1]``).
        Multiclass: (m, k) one-vs-rest scores."""
        self._check_fitted()
        Kq, squeeze = self._query_gram(Xq)
        if self.alpha_.ndim == 1:
            df = jnp.dot(Kq, self.alpha_, precision=HIGHEST) + self.b_
        else:
            df = mc.ovr_decision(Kq, self.alpha_, self.b_)
        return df[0] if squeeze else df

    def predict(self, Xq) -> np.ndarray:
        self._check_fitted()
        df = self.decision_function(Xq)
        if self.alpha_.ndim == 1:
            idx = (np.asarray(df) >= 0).astype(np.int64)
        else:
            idx = np.asarray(jnp.argmax(df, axis=-1))
        return self.classes_[idx]

    def score(self, Xq, yq) -> float:
        """Mean accuracy on (Xq, yq)."""
        return float(np.mean(self.predict(Xq) == np.asarray(yq)))

    # -- introspection --------------------------------------------------

    @property
    def n_support_(self) -> np.ndarray:
        """Support-vector count per head ((1,) binary, (k,) one-vs-rest)."""
        self._check_fitted()
        a = np.atleast_2d(np.asarray(self.alpha_))
        return (np.abs(a) > 1e-9).sum(axis=1)
