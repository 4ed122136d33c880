"""Lane-sharded fused engine: the flat lane batch ``shard_map``-ed over a mesh.

The fused batched engine
(:func:`repro.core.solver_fused.solve_fused_batched_qp`) advances the whole
(gamma, class, C) lane batch through ONE single-device ``lax.while_loop`` —
grid throughput is capped by one chip no matter how many are attached.  But
lanes are *embarrassingly parallel*: every per-iteration quantity of lane b
(selection, step, planning history, in-kernel freezing, the shrinking mask)
is a function of lane b's state alone, and the only shared operands — ``X``
and the optional Gram bank — are read-only.  So the lane axis shards with
ZERO collectives in the hot loop: each device runs its own independent
two-pass while_loop on its lane slab and terminates when ITS slab converges
(per-shard termination — a shard of easy lanes retires early instead of
idling on the global straggler barrier that the single-device loop pays).

Two scheduling details make the flat split balance:

* **cost-balanced round-robin** — lane iteration counts grow with the box
  budget (big-C lanes iterate longest; see ``BENCH_grid.json``), so slicing
  the flat batch contiguously would park one gamma's big-C stragglers on
  one shard.  Lanes are instead dealt round-robin in descending box-width
  order (descending C for classification/SVR lanes, descending ``1/(nu l)``
  for one-class lanes) so every shard sees the same cost spectrum; the
  inverse permutation restores the caller's lane order on gather-back
  (:func:`lane_schedule`).
* **pad lanes** — the batch pads to a multiple of the axis size with
  frozen ``L = U = 0`` lanes (:func:`pad_lanes`): the same degenerate-box
  convention the engine already handles — such a lane converges at t = 0,
  every kernel pass is a bitwise no-op on it, and its finalized
  ``kkt_gap``/``b`` are finite.  Pads are stripped from every returned
  leaf.

The per-shard body is byte-for-byte the batched engine, so every row
source (plain RBF recompute, in-kernel doubled ε-SVR halves, Gram-bank
gathers) and every backend (``jnp``/``interpret``/``pallas``) rides along
unchanged, as do warm starts and soft shrinking.  Per-lane trajectories
are independent of batch composition (all reductions run along the lane's
own row axis), so sharded results match the single-device engine lane for
lane — same objectives, same iteration counts.  One caveat, a property
of XLA codegen rather than of the sharding layer (it reproduces
*already on a single device* by just changing the batch size): the
compiled reduction/matmul order of the kernel passes can depend on the
lane-batch shape, and a small per-device slab may compile differently
than the same lanes inside the full batch (the doubled ε-SVR operator is
the most sensitive — solo vs in-batch lanes differ at ~1e-8 — but small
plain slabs reproduce it too).  When the slab codegen diverges, the two
engines take different float round-off trajectories and stop at
*different eps-optimal points*: iteration counts differ and objectives
agree to the solver tolerance, not bitwise.  For exact bitwise parity
keep the per-device slab comfortably sized (the tests pin a 2-device
mesh for their iteration-count parity case); for tight objective parity
across any slab shape, tighten ``cfg.eps`` — both engines' objectives
sit within O(eps^2)-ish of the shared optimum.

This is stage (1) of the ROADMAP's million-row plan ("shard the lanes,
then shard the rows"); stage (2) plugs a row-sharded
:class:`~repro.kernels.row_source.RowSource` with all-reduced pass A/B
partials into the same seam.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as Pspec

from repro.core.solver import SolverConfig
from repro.core.solver_fused import FusedResult, solve_fused_batched_qp
from repro.launch.mesh import make_lane_mesh
from repro.telemetry.ring import RingConfig, TelemetryRing


def resolve_lane_mesh(mesh: Optional[Mesh] = None, devices=None,
                      axis: str = "data") -> Mesh:
    """Resolve the lane mesh: an explicit mesh wins, else a 1-D mesh over
    ``devices`` (default: every attached device)."""
    if mesh is not None:
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh.shape}")
        if devices is not None:
            raise ValueError("pass either mesh or devices, not both")
        return mesh
    return make_lane_mesh(devices, axis=axis)


def lane_schedule(cost: jax.Array, n_shards: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Cost-balanced round-robin lane permutation for ``n_shards`` slabs.

    ``cost`` (B,) is a per-lane straggler proxy (box width == C); B must be
    divisible by ``n_shards``.  Returns ``(order, inv)``: ``lanes[order]``
    lays the batch out shard-major so contiguous slab p holds the lanes at
    descending-cost positions ``p, p + n_shards, p + 2 n_shards, ...`` —
    every shard gets the same cost spectrum instead of one shard inheriting
    a whole big-C straggler block.  ``inv`` is the inverse permutation
    (``result[order][inv] == result``) applied on gather-back so callers
    never see the scheduling order.
    """
    B = cost.shape[0]
    assert B % n_shards == 0, (B, n_shards)
    # lax.sort with an int32 iota payload == stable argsort on the int32
    # index channel (jnp.argsort would mint int64 indices under x64)
    iota = jnp.arange(B, dtype=jnp.int32)
    _, srt = jax.lax.sort((-cost, iota), num_keys=1)   # descending, stable
    order = srt.reshape(B // n_shards, n_shards).T.reshape(-1)
    _, inv = jax.lax.sort((order, iota), num_keys=1)
    return order, inv


def pad_lanes(A: jax.Array, pad: int, value=0.0) -> jax.Array:
    """Append ``pad`` inert lanes along axis 0 (``L = U = 0`` convention:
    every padded per-lane quantity is 0 except gamma, padded by value)."""
    if pad == 0:
        return A
    widths = [(0, pad)] + [(0, 0)] * (A.ndim - 1)
    return jnp.pad(A, widths, constant_values=value)


@partial(jax.jit, static_argnames=("cfg", "mesh", "axis", "impl", "block_l",
                                   "doubled", "shrinking", "telemetry"))
def _solve_sharded(X, P, L, U, gamma, cfg, mesh, axis, impl, block_l,
                   alpha0, G0, gram, gram_idx, doubled, shrinking,
                   telemetry=None):
    nsh = mesh.shape[axis]
    X = jnp.asarray(X)
    P = jnp.asarray(P)
    dtype = P.dtype
    B, n = P.shape
    L = jnp.broadcast_to(jnp.asarray(L, dtype), (B, n))
    U = jnp.broadcast_to(jnp.asarray(U, dtype), (B, n))
    gamma = jnp.broadcast_to(jnp.asarray(gamma, dtype), (B,))
    warm = alpha0 is not None
    bank = gram is not None

    # ---- pad to a multiple of the axis size (frozen L = U = 0 lanes) ----
    pad = (-B) % nsh
    Bp = B + pad
    Pp, Lp, Up = (pad_lanes(A, pad) for A in (P, L, U))
    gp = pad_lanes(gamma, pad, value=1.0)   # any positive width is inert

    # ---- cost-balanced round-robin schedule ----------------------------
    # box width == C for classification/SVR lanes, 1/(nu l) for one-class;
    # pad lanes have width 0 and sort last, landing one per shard
    cost = jnp.max(Up - Lp, axis=1)
    order, inv = lane_schedule(cost, nsh)

    lane1, lane2, rep = Pspec(axis), Pspec(axis, None), Pspec()
    operands = [jnp.take(Pp, order, axis=0), jnp.take(Lp, order, axis=0),
                jnp.take(Up, order, axis=0), jnp.take(gp, order)]
    in_specs = [rep, lane2, lane2, lane2, lane1]
    if warm:
        operands += [jnp.take(pad_lanes(jnp.asarray(alpha0, dtype), pad),
                              order, axis=0),
                     jnp.take(pad_lanes(jnp.asarray(G0, dtype), pad),
                              order, axis=0)]
        in_specs += [lane2, lane2]
    if bank:
        gidx = pad_lanes(jnp.asarray(gram_idx, jnp.int32), pad, value=0)
        operands += [jnp.asarray(gram), jnp.take(gidx, order)]
        in_specs += [rep, lane1]

    collect = telemetry is not None

    def local_solve(Xl, *slab):
        it = iter(slab)
        Pl, Ll, Ul, gl = next(it), next(it), next(it), next(it)
        kw = {}
        if warm:
            kw["alpha0"], kw["G0"] = next(it), next(it)
        if bank:
            kw["gram"], kw["gram_idx"] = next(it), next(it)
        # the per-shard body IS the batched engine: its own while_loop,
        # per-shard termination, no collective anywhere in the hot loop
        r = solve_fused_batched_qp(Xl, Pl, Ll, Ul, gl, cfg, impl=impl,
                                   block_l=block_l, doubled=doubled,
                                   shrinking=shrinking, telemetry=telemetry,
                                   **kw)
        ring_leaves = ()
        if collect:
            r, ring = r
            # every ring leaf is lane-leading, so per-shard rings ride
            # the same lane specs as the result leaves and gather back
            # in caller lane order below
            ring_leaves = tuple(jax.tree.leaves(ring))
        return (r.alpha, r.b, r.G, r.iterations, r.objective, r.kkt_gap,
                r.converged, r.n_planning, r.n_unshrink) + ring_leaves

    n_ring = len(dataclasses.fields(TelemetryRing)) if collect else 0
    out = jax.shard_map(local_solve, mesh=mesh,
                        in_specs=tuple(in_specs),
                        out_specs=(lane1,) * (9 + n_ring),
                        check_vma=False)(X, *operands)

    # gather-back: undo the schedule, strip the pad lanes
    out = tuple(jnp.take(leaf, inv[:B], axis=0) for leaf in out)
    res = FusedResult(*out[:9])
    if collect:
        return res, TelemetryRing(*out[9:])
    return res


def solve_fused_sharded_qp(X, P, L, U, gamma,
                           cfg: SolverConfig = SolverConfig(), *,
                           mesh: Optional[Mesh] = None, devices=None,
                           axis: str = "data", impl: str = "auto",
                           block_l: int = 1024, alpha0=None, G0=None,
                           gram=None, gram_idx=None, doubled: bool = False,
                           shrinking: bool = False,
                           telemetry: Optional[RingConfig] = None):
    """Lane-sharded :func:`~repro.core.solver_fused.solve_fused_batched_qp`.

    Same problem layout and result contract as the batched engine — B
    general dual QP lanes over shared ``X`` (``P``/``L``/``U`` per lane,
    per-lane ``gamma``, optional warm starts, optional Gram bank, the
    doubled ε-SVR operator, soft shrinking) — but the lane batch is
    ``shard_map``-ed over ``mesh[axis]``: each device runs its own
    two-pass while_loop on a cost-balanced slab of lanes and stops when
    that slab converges (see module docstring).  ``mesh`` must carry the
    named ``axis``; alternatively pass ``devices`` (or neither — every
    attached device) and a 1-D mesh is built.  Results come back in the
    caller's lane order with pad lanes stripped; per-lane objectives and
    iteration counts match the single-device engine exactly.

    ``telemetry`` (static :class:`~repro.telemetry.ring.RingConfig`)
    turns on the fused engine's flight recorder per shard; the per-shard
    rings gather back in caller lane order (pad lanes stripped) and the
    return value becomes ``(FusedResult, TelemetryRing)``.

    ``cfg.step == "conjugate"`` rides through unchanged (the config is
    static and the conjugate carry is per lane, so the per-shard body is
    still byte-for-byte the batched engine).
    """
    assert (alpha0 is None) == (G0 is None), \
        "warm starts need the (alpha0, G0) pair"
    assert (gram is None) == (gram_idx is None), \
        "the Gram bank needs the (gram, gram_idx) pair"
    mesh = resolve_lane_mesh(mesh, devices, axis)
    return _solve_sharded(X, P, L, U, gamma, cfg, mesh, axis, impl, block_l,
                          alpha0, G0, gram, gram_idx, doubled, shrinking,
                          telemetry=telemetry)


def solve_fused_sharded(X, Y, C, gamma, cfg: SolverConfig = SolverConfig(),
                        *, mesh: Optional[Mesh] = None, devices=None,
                        axis: str = "data", impl: str = "auto",
                        block_l: int = 1024, alpha0=None, G0=None,
                        gram=None, gram_idx=None,
                        shrinking: bool = False,
                        telemetry: Optional[RingConfig] = None):
    """Lane-sharded classification batch — the ``p = y`` instance of
    :func:`solve_fused_sharded_qp`, mirroring
    :func:`~repro.core.solver_fused.solve_fused_batched`.  ``C`` is a
    scalar, (B,) per-lane budgets, or (B, l) per-sample budgets."""
    Y = jnp.asarray(Y)
    dtype = Y.dtype
    B = Y.shape[0]
    C = jnp.asarray(C, dtype)
    if C.ndim < 2:
        C = jnp.broadcast_to(C, (B,))[:, None]
    YC = Y * C
    return solve_fused_sharded_qp(
        X, Y, jnp.minimum(0.0, YC), jnp.maximum(0.0, YC), gamma, cfg,
        mesh=mesh, devices=devices, axis=axis, impl=impl, block_l=block_l,
        alpha0=alpha0, G0=G0, gram=gram, gram_idx=gram_idx, doubled=False,
        shrinking=shrinking, telemetry=telemetry)
