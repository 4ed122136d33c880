"""One-vs-rest multiclass layer: batched label vectors, shared-kernel solves.

A k-class SVM in the one-vs-rest (OVR) reduction is k *independent* binary
QPs (eq. 1) that differ only in the sign pattern of ``y`` (and hence in the
box bounds ``[min(0, y_i C), max(0, y_i C)]``) — the Gram matrix is shared.
Because the PA-SMO iteration is O(1) beyond the kernel row, the whole stack
of solves batches under ``vmap``: one ``lax.while_loop`` advances all class
heads together, and with a :class:`~repro.core.qp.PrecomputedKernel` mapped
with ``in_axes=None`` the Gram work is done once per row of K — a gather per
class, not a recompute per class.

Conventions:

* ``y_idx``  — integer class indices, shape (l,), values in [0, k).
* ``Y``      — stacked signed label vectors, shape (k, l), rows in {-1, +1}.
* Batched results carry a leading class axis on every ``SolveResult`` leaf.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qp as qp_mod
from repro.core.solver import SolveResult, SolverConfig, solve
from repro.kernels.ref import HIGHEST


def class_index(y) -> Tuple[np.ndarray, np.ndarray]:
    """Map arbitrary label values to dense indices.

    Returns ``(classes, y_idx)`` where ``classes`` is the sorted unique label
    array and ``y_idx[i]`` is the position of ``y[i]`` in it.  Host-side
    (numpy): label vocabularies are data-dependent shapes, not trace-time
    values.
    """
    classes, y_idx = np.unique(np.asarray(y), return_inverse=True)
    return classes, y_idx.astype(np.int32)


def ovr_labels(y_idx, n_classes: int, dtype=jnp.float64) -> jax.Array:
    """Stacked one-vs-rest signed label vectors, shape (k, l).

    Row ``c`` is ``+1`` where ``y_idx == c`` and ``-1`` elsewhere — the
    label vector of the binary "class c vs rest" problem.
    """
    y_idx = jnp.asarray(y_idx)
    onehot = y_idx[None, :] == jnp.arange(n_classes, dtype=y_idx.dtype)[:, None]
    return jnp.where(onehot, 1.0, -1.0).astype(dtype)


def ovr_bounds(Y: jax.Array, C) -> qp_mod.Bounds:
    """Per-class box bounds: ``Bounds`` with (k, l) leaves.

    ``C`` may be a scalar (shared) or a (k,) vector (per-class budgets, e.g.
    to rebalance rare classes in the OVR reduction).
    """
    C = jnp.broadcast_to(jnp.asarray(C, Y.dtype), (Y.shape[0],))
    return qp_mod.make_bounds(Y, C[:, None])


@partial(jax.jit, static_argnames=("cfg",))
def solve_ovr(kernel, Y: jax.Array, C,
              cfg: SolverConfig = SolverConfig(),
              alpha0: Optional[jax.Array] = None,
              G0: Optional[jax.Array] = None) -> SolveResult:
    """Solve all one-vs-rest heads in one vmapped ``while_loop``.

    ``kernel`` is a single (unbatched) oracle shared across classes — it is
    mapped with ``in_axes=None``, so a precomputed Gram matrix is gathered,
    never recomputed, per class.  ``Y`` is (k, l); ``C`` is scalar, (k,)
    per-class, or (k, l) per-sample budgets (class-weighted SVC); optional
    ``alpha0``/``G0`` are (k, l) warm starts.  Returns a
    :class:`SolveResult` whose leaves carry a leading class axis.
    """
    Y = jnp.asarray(Y)
    k = Y.shape[0]
    C = jnp.asarray(C, Y.dtype)
    if C.ndim < 2:
        C = jnp.broadcast_to(C, (k,))
    if alpha0 is None:
        return jax.vmap(
            lambda y, c: solve(kernel, y, c, cfg),
            in_axes=(0, 0))(Y, C)
    return jax.vmap(
        lambda y, c, a0, g0: solve(kernel, y, c, cfg, alpha0=a0, G0=g0),
        in_axes=(0, 0, 0, 0))(Y, C, alpha0, G0)


def solve_ovr_fused(X, Y: jax.Array, C, gamma,
                    cfg: SolverConfig = SolverConfig(), *,
                    impl: str = "auto", block_l: int = 1024,
                    precompute: bool = False, mesh=None, devices=None,
                    telemetry=None):
    """Solve all one-vs-rest heads through the fused two-pass batched engine.

    Unlike :func:`solve_ovr` this consumes the raw ``X`` (l, d); every
    iteration advances the whole class stack through two batched kernel
    passes (:func:`repro.core.solver_fused.solve_fused_batched`).  With
    ``precompute=True`` on the jnp backend the single shared Gram matrix
    is built once and rows become gathers (CPU throughput mode); otherwise
    rows are recomputed from ``X`` and no Gram is ever materialized.
    ``C`` is scalar, (k,) per-class, or (k, l) per-sample budgets
    (class-weighted SVC); ``gamma`` is the shared RBF width.  Returns a
    :class:`~repro.core.solver_fused.FusedResult` with a
    leading class axis on every leaf.  Requires
    ``cfg.algorithm in ("smo", "pasmo")`` and ``plan_candidates == 1``.
    ``mesh``/``devices`` shard the class-head lanes over a device mesh
    (:mod:`repro.core.sharded_lanes`) — identical results, one while_loop
    per device slab.  ``telemetry`` (a static
    :class:`~repro.telemetry.ring.RingConfig`) turns on the fused
    engine's flight recorder; the return value becomes the
    ``(FusedResult, TelemetryRing)`` pair with class-leading ring leaves.
    """
    from repro.core.solver_fused import solve_fused_batched
    from repro.kernels import ops as kernel_ops
    X = jnp.asarray(X)
    Y = jnp.asarray(Y)
    bank_kw = {}
    if precompute and kernel_ops.resolve_impl(impl) == "jnp":
        K = kernel_ops.gram(X, gamma=gamma, impl=impl)
        bank_kw = dict(gram=K[None].astype(Y.dtype),
                       gram_idx=jnp.zeros((Y.shape[0],), jnp.int32))
    if mesh is not None or devices is not None:
        from repro.core.sharded_lanes import solve_fused_sharded
        return solve_fused_sharded(X, Y, C, gamma, cfg, mesh=mesh,
                                   devices=devices, impl=impl,
                                   block_l=block_l, telemetry=telemetry,
                                   **bank_kw)
    return solve_fused_batched(X, Y, C, gamma, cfg,
                               impl=impl, block_l=block_l,
                               telemetry=telemetry, **bank_kw)


def ovr_decision(Kq: jax.Array, alpha: jax.Array, b: jax.Array) -> jax.Array:
    """OVR decision scores for query cross-kernel ``Kq`` (m, l).

    ``alpha`` (k, l) carries the label signs (signed dual), ``b`` is (k,).
    Returns (m, k): one binary decision value per class head.
    """
    return jnp.dot(Kq, alpha.T, precision=HIGHEST) + b[None, :]


def ovr_predict(Kq: jax.Array, alpha: jax.Array, b: jax.Array) -> jax.Array:
    """argmax-of-scores OVR prediction -> (m,) int32 class indices."""
    return jnp.argmax(ovr_decision(Kq, alpha, b), axis=-1).astype(jnp.int32)
