"""C/gamma model-selection grids as one jit-compiled, vmapped solve.

A hyper-parameter grid over an RBF-SVM is ``n_gamma * n_class * n_C``
independent QPs that share one dataset.  Three structural facts make the
whole grid a single compiled call instead of a Python loop:

* The O(l^2 d) part of the Gram work — the squared-distance matrix — is
  *gamma-independent*: ``K_gamma = exp(-gamma * D2)`` is one elementwise
  exp per gamma on a shared ``D2``.
* (C, gamma, labels) are traced arguments of :func:`repro.core.solver.solve`
  (the config is static, the problem is data), so every grid point shares
  one compilation and batches under ``vmap``.
* The C-axis is solved by ``lax.scan`` in ascending order with *scaled
  warm starts*: ``alpha * (C_t/C_{t-1})`` is exactly feasible for the grown
  box (signs and the sum-to-zero constraint are scale-invariant, and bound
  support vectors land exactly on the new bound), and the matching gradient
  is closed-form — ``G' = (1-r) y + r G`` since ``G = y - K alpha`` — so
  the restart costs O(l), no kernel evaluations (cf. the paper's cold-start
  property in §2).

Two engines share this structure, selected by ``impl``:

* ``impl=None`` — the vmapped standard solver over per-gamma precomputed
  Gram matrices (the differential oracle; ~4 logical passes per iteration
  per lane).
* ``impl="auto"|"pallas"|"interpret"|"jnp"`` — the fused two-pass batched
  engine (:func:`repro.core.solver_fused.solve_fused_batched`): the whole
  lane batch advances through ONE while_loop with TWO batched kernel
  launches per iteration, no Gram materialization, converged lanes frozen
  in-kernel.  The direct answer to the ROADMAP's "vmapped while_loop body
  is op-dispatch bound" item.

Axis convention for all stacked results: ``(n_gamma, n_class, n_C, ...)``.
"""

from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qp as qp_mod
from repro.core.solver import (SolveResult, SolverConfig, resolve_shrink_cfg,
                               solve)
from repro.core.solver_fused import (FusedResult, solve_fused_batched,
                                     solve_fused_batched_qp,
                                     solve_fused_chunked_qp)
from repro.core.sharded_lanes import (resolve_lane_mesh, solve_fused_sharded,
                                      solve_fused_sharded_qp)
from repro.kernels.ref import HIGHEST
from repro.telemetry import span


def sqdist(X: jax.Array) -> jax.Array:
    """Pairwise squared distances (l, l) — the shared, gamma-free Gram work."""
    sq = jnp.sum(X * X, axis=-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.dot(X, X.T, precision=HIGHEST)
    return jnp.maximum(d2, 0.0)


@partial(jax.jit, static_argnames=("cfg", "warm_start"))
def _solve_grid(X, Y, Cs, gammas, cfg: SolverConfig,
                warm_start: bool) -> SolveResult:
    D2 = sqdist(X)

    def per_gamma(gamma):
        kern = qp_mod.PrecomputedKernel(jnp.exp(-gamma * D2))

        def per_class(y):
            def step(carry, C):
                alpha, G, C_prev = carry
                r = C / C_prev
                a0 = alpha * r                   # exactly feasible at C
                g0 = (1.0 - r) * y + r * G       # y - K(r alpha), O(l)
                res = solve(kern, y, C, cfg, alpha0=a0, G0=g0)
                nxt = (res.alpha, res.G, C) if warm_start else carry
                return nxt, res

            # alpha=0, G=y is the C-free cold start: the scaled carry maps
            # it to itself, so the first scan step is exact for any C_prev.
            cold = (jnp.zeros_like(y), y, Cs[0])
            _, out = jax.lax.scan(step, cold, Cs)
            return out

        return jax.vmap(per_class)(Y)

    return jax.vmap(per_gamma)(gammas)


# ---------------------------------------------------------------------------
# Fused-batched engine (two kernel launches per iteration, all lanes)
# ---------------------------------------------------------------------------
#
# The vmapped engine above runs the standard ~4-pass solver body per lane —
# correct everywhere, but op-dispatch bound on CPU (the ROADMAP open item).
# The fused engine flattens ALL grid axes — gamma, class, AND C — into
# B = n_gamma * k * n_C lanes over shared X and drives the whole batch
# through ``solve_fused_batched``: ONE while_loop total, TWO batched kernel
# launches per iteration, O(B) scalar algebra in between.  In-kernel lane
# freezing is what makes the flat batch viable: the wall clock is the
# SLOWEST single lane, not the sum of per-C maxima that the scanned
# warm-start chain pays (all-C-lanes-at-once replaces the C chain, so the
# scaled warm start does not apply here; lanes cold-start).
#
# The row source is orthogonal to the backend (``precompute``): with a
# Gram bank the per-gamma matrices are built once (same (n_gamma, l, l)
# memory as the vmapped engine) and rows become gathers — on the jnp
# backend as XLA-fused algebra, on pallas/interpret through the
# rows-variant kernels; without a bank the rows are recomputed from X
# tiles (the accelerator memory mode — no Gram at all).  The default
# (``precompute=None``) banks exactly on the jnp backend.
#
# The fused engine does not track the per-step counters n_free /
# n_clipped / n_reverted — they are GENUINELY UNTRACKED, so the fused
# drivers fill all three with the -1 sentinel (UNTRACKED) instead of
# zeros: a zero would read as "this never happened" to callers comparing
# engines.  The state counter every engine shares is n_free_sv — the
# number of *free support vectors* at the optimum, computed from the
# final alpha and the box bounds (see SolveResult).

UNTRACKED = -1  # sentinel for counters the fused iteration never materializes


def _free_sv_count(alpha, L, U) -> jax.Array:
    """Per-lane count of strictly-interior (free) support vectors."""
    return jnp.sum((alpha > L) & (alpha < U), axis=-1).astype(jnp.int32)


def _use_bank(impl: str, precompute) -> bool:
    """Resolve the row-source policy: ``None`` banks exactly on jnp."""
    from repro.kernels.ops import resolve_impl
    if precompute is None:
        return resolve_impl(impl) == "jnp"
    return bool(precompute)


def _trace_fields(dims, dtype, ring=None) -> dict:
    """The ``SolveResult`` trace/step-recording buffers for grid drivers.

    The fused engines never run the classic solver's in-loop
    ``record_trace``/``record_steps`` recorders, so historically every
    driver allocated its own placeholder buffers.  This is now the ONE
    place they come from: placeholders by default, and when the flight
    recorder ran (``ring`` is the grid-shaped
    :class:`~repro.telemetry.ring.TelemetryRing`) the Fig. 3 mu/mu*
    channel fills ``trace``/``n_trace`` with the classic semantics —
    one entry per *accepted* planning step, oldest-wins at the cap, the
    count free-running past it.
    """
    cap = dims + (1,)
    fields = dict(
        trace=jnp.zeros(cap, dtype), n_trace=jnp.zeros(dims, jnp.int32),
        steps_i=jnp.zeros(cap, jnp.int32), steps_j=jnp.zeros(cap, jnp.int32),
        steps_mu=jnp.zeros(cap, dtype))
    if ring is not None:
        fields["trace"] = jnp.asarray(ring.ratio, dtype)
        fields["n_trace"] = jnp.asarray(ring.n_ratio)
    return fields


def _drain_grid_ring(diagnostics, ring, meta, result):
    """Flatten a grid-shaped ring to lanes and hand it to ``diagnostics``."""
    ndim = result.iterations.ndim if hasattr(result, "iterations") else 3
    # numpy, not jnp: the drain is host-bound and a jnp reshape per leaf
    # costs a device dispatch each
    ring_flat = jax.tree.map(
        lambda leaf: np.asarray(leaf).reshape(
            (-1,) + np.shape(leaf)[ndim:]), ring)
    flat_res = SimpleNamespace(**{
        k: np.asarray(getattr(result, k)).reshape(-1)
        for k in ("iterations", "kkt_gap", "converged", "n_planning",
                  "n_unshrink")
        if getattr(result, k, None) is not None})
    return diagnostics.drain_ring(ring_flat, meta, flat_res)


@partial(jax.jit, static_argnames=("cfg", "impl", "block_l", "precompute",
                                   "shrinking", "mesh", "telemetry"))
def _solve_grid_fused(X, Y, Cs, gammas, cfg: SolverConfig,
                      impl: str, block_l: int, precompute,
                      shrinking: bool = False, mesh=None, telemetry=None):
    k, l = Y.shape
    nG = gammas.shape[0]
    nC = Cs.shape[0]
    # lane order (gamma, class, C) row-major, matching the result axes
    Yf = jnp.repeat(jnp.tile(Y, (nG, 1)), nC, axis=0)    # (B, l)
    gf = jnp.repeat(gammas, k * nC)                      # (B,)
    Cf = jnp.tile(Cs, nG * k)                            # (B,)
    solver = (solve_fused_batched if mesh is None
              else partial(solve_fused_sharded, mesh=mesh))
    if _use_bank(impl, precompute):
        bank = jnp.exp(-gammas[:, None, None] * sqdist(X))
        bidx = jnp.repeat(jnp.arange(nG, dtype=jnp.int32), k * nC)
        out = solver(X, Yf, Cf, gf, cfg, impl=impl,
                     block_l=block_l, gram=bank, gram_idx=bidx,
                     shrinking=shrinking, telemetry=telemetry)
    else:
        out = solver(X, Yf, Cf, gf, cfg, impl=impl,
                     block_l=block_l, shrinking=shrinking,
                     telemetry=telemetry)
    ring = None
    if telemetry is not None:
        out, ring = out

    def to_grid(leaf):                                   # (B, ...) leaves
        return leaf.reshape((nG, k, nC) + leaf.shape[1:])

    fr: FusedResult = jax.tree.map(to_grid, out)
    ring_g = None if ring is None else jax.tree.map(to_grid, ring)
    YC = Y[None, :, None, :] * Cs[None, None, :, None]
    n_free_sv = _free_sv_count(fr.alpha, jnp.minimum(0.0, YC),
                               jnp.maximum(0.0, YC))
    untracked = jnp.full((nG, k, Cs.shape[0]), UNTRACKED, jnp.int32)
    res = SolveResult(
        alpha=fr.alpha, b=fr.b, G=fr.G, iterations=fr.iterations,
        objective=fr.objective, kkt_gap=fr.kkt_gap, converged=fr.converged,
        n_planning=fr.n_planning, n_free=untracked,
        n_clipped=untracked, n_reverted=untracked, n_free_sv=n_free_sv,
        **_trace_fields((nG, k, nC), X.dtype, ring_g))
    return res if ring_g is None else (res, ring_g)


@span("solve_grid")
def solve_grid(X, Y, Cs, gammas, cfg: SolverConfig = SolverConfig(), *,
               warm_start: bool = True, impl: str | None = None,
               block_l: int = 1024, precompute: bool | None = None,
               shrinking: bool = False, mesh=None,
               devices=None, diagnostics=None) -> SolveResult:
    """Solve the full (gamma, class, C) grid in ONE compiled call.

    ``X``: (l, d) shared inputs; ``Y``: (k, l) signed label vectors (a 1-D
    ``y`` is promoted to one class head); ``Cs``: (n_C,); ``gammas``:
    (n_gamma,) (scalars are promoted).  Returns a :class:`SolveResult` whose
    leaves have leading axes ``(n_gamma, n_class, n_C)`` aligned with the
    *input* order of ``Cs``/``gammas``.

    ``impl`` selects the engine.  ``None`` (default) is the vmapped
    standard-solver path over per-gamma precomputed Gram matrices — the
    differential oracle.  Any kernel backend name
    (``"auto"``/``"pallas"``/``"interpret"``/``"jnp"``) routes the grid
    through the fused two-pass batched engine
    (:func:`repro.core.solver_fused.solve_fused_batched`): the WHOLE
    (gamma, class, C) grid becomes one flat lane batch advanced by a
    single while_loop with two kernel launches per iteration and
    in-kernel lane freezing.  ``precompute`` picks the row source:
    ``True`` builds the shared per-gamma Gram bank (rows become gathers on
    ANY backend — jnp algebra or the rows-variant Pallas kernels),
    ``False`` recomputes rows from X tiles (no Gram ever materialized),
    ``None`` (default) banks exactly on the jnp backend.  The fused
    engine requires ``cfg.algorithm in ("smo", "pasmo")``,
    ``plan_candidates == 1``, WSS2 selection and no trace/step recording
    (asserted), and fills the untracked step-type counters
    ``n_free``/``n_clipped``/``n_reverted`` with the ``UNTRACKED`` (-1)
    sentinel while reporting the free-SV count in ``n_free_sv`` (see
    module notes).

    ``cfg.step == "conjugate"`` (with ``cfg.algorithm == "smo"``) selects
    the Conjugate-SMO two-direction step in EITHER engine — the config is
    static, so the knob threads through unchanged; on the fused path the
    per-lane conjugate carry resets at chunk boundaries in
    :func:`solve_grid_compacted` (a fresh direction history, exactly like
    the planning history).

    With ``warm_start=True`` the vmapped engine solves the C-axis in
    ascending order (results are scattered back to input order), chaining
    each solve from the previous optimum; ``warm_start=False`` gives
    independent cold starts — same optima, more iterations (used by the
    parity tests).  The fused engine runs all C lanes concurrently from
    cold starts, so ``warm_start`` has no effect there.

    ``shrinking=True`` turns on active-set shrinking in either engine:
    the fused engine masks bound-pinned variables out of its scans
    in-loop (soft shrinking, see
    :func:`~repro.core.solver_fused.solve_fused_batched_qp`); the vmapped
    engine enables its periodic ``cfg.shrink_every`` shrink-and-verify
    cycle.  Optima are unchanged either way (full KKT re-check before any
    lane converges); for the physical row-compaction speedup use
    :func:`solve_grid_compacted`.

    ``mesh``/``devices`` (fused engine only) shard the flat lane batch
    over a device mesh (:mod:`repro.core.sharded_lanes`): pass a mesh
    with a ``data`` axis, or an explicit device list to build a 1-D mesh
    over.  Each device runs its own while_loop on a cost-balanced lane
    slab (zero collectives in the hot loop); results are identical to the
    single-device engine lane for lane.

    ``diagnostics`` (a :class:`repro.telemetry.Diagnostics`, fused engine
    only) turns on the flight recorder: the solve runs under a phase
    scope, the in-loop :class:`~repro.telemetry.ring.TelemetryRing`
    samples every lane (KKT-gap trajectory, active-set size, planning
    mu/mu* ratios), the drained per-lane events land in the diagnostics
    sink keyed by (gamma, class, C), and ``trace``/``n_trace`` on the
    returned result carry the Fig. 3 planning-ratio channel — the
    classic engine's ``record_trace``, generalized to the batched
    engine.
    """
    X = jnp.asarray(X)
    Y = jnp.asarray(Y)
    if Y.ndim == 1:
        Y = Y[None, :]
    Cs_np = np.asarray(Cs, dtype=np.float64).reshape(-1)
    gammas_np = np.asarray(gammas, dtype=np.float64).reshape(-1)
    order = np.argsort(Cs_np, kind="stable")
    Cs_j = jnp.asarray(Cs_np[order], X.dtype)
    gammas_j = jnp.asarray(gammas_np, X.dtype)
    if mesh is not None or devices is not None:
        if impl is None:
            raise ValueError("lane sharding runs on the fused engine — "
                             "set impl (e.g. impl='jnp') with mesh/devices")
        mesh = resolve_lane_mesh(mesh, devices)
    if diagnostics is not None and impl is None:
        raise ValueError("diagnostics rides the fused engine — set impl "
                         "(e.g. impl='jnp') with diagnostics")
    tel = None if diagnostics is None else diagnostics.ring_config
    ring = None
    if impl is None:
        with span("fit.solve"):
            res = _solve_grid(X, Y, Cs_j, gammas_j,
                              resolve_shrink_cfg(cfg, True) if shrinking
                              else cfg, warm_start)
    else:
        k = Y.shape[0]
        cm = (nullcontext() if diagnostics is None else diagnostics.scope(
            "solve_grid_fused", lanes=len(gammas_np) * k * len(Cs_np)))
        with span("fit.solve"), cm:
            res = _solve_grid_fused(X, Y, Cs_j, gammas_j, cfg, impl,
                                    block_l, precompute, shrinking, mesh,
                                    tel)
            if tel is not None:
                res, ring = res
            if diagnostics is not None:
                jax.block_until_ready(res.alpha)
    if np.any(order != np.arange(len(Cs_np))):
        inv = np.argsort(order, kind="stable")
        res = jax.tree.map(lambda leaf: jnp.take(leaf, inv, axis=2), res)
        if ring is not None:
            ring = jax.tree.map(lambda leaf: jnp.take(leaf, inv, axis=2),
                                ring)
    if ring is not None:
        meta = [{"gamma": float(g), "label": int(c), "C": float(Cv)}
                for g in gammas_np for c in range(Y.shape[0])
                for Cv in Cs_np]
        _drain_grid_ring(diagnostics, ring, meta, res)
    return res


# ---------------------------------------------------------------------------
# Chunked/compacted grid driver (CPU throughput mode)
# ---------------------------------------------------------------------------
#
# A vmapped while_loop runs until the SLOWEST lane converges, so a batch of
# heterogeneous QPs wastes (max - mean)/mean of its lane-iterations on
# already-converged lanes.  The classic fix: run the loop in fixed chunks of
# iterations, and between chunks compact the unconverged lanes into a
# smaller (power-of-two-bucketed, so compile count stays logarithmic) batch.
# Warm-starting makes chunking free — a resumed solve continues from
# (alpha, G) exactly, only the O(1) planning history is reset.


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


@partial(jax.jit, static_argnames=("cfg",))
def _chunk_solve(Ks, gidx, ys, C, a0, g0, cfg: SolverConfig) -> SolveResult:
    """One chunk of vmapped solves over lanes indexing the shared Gram bank.

    ``Ks`` is the un-mapped (n_gamma, l, l) stack; ``gidx`` maps each lane
    to its gamma — a :class:`~repro.core.qp.StackedKernel` gather per row
    access instead of a per-lane Gram copy (``jnp.repeat`` would cost
    k-fold memory on multiclass grids).
    """
    return jax.vmap(
        lambda g, y, a, gr: solve(qp_mod.StackedKernel(Ks, g), y, C, cfg,
                                  alpha0=a, G0=gr))(gidx, ys, a0, g0)


# step-type counters a chunked solve CAN resume across chunks (they are
# plain per-step sums, so summing the per-chunk values matches solve_grid)
_CHUNK_COUNTERS = ("iterations", "n_planning", "n_free", "n_clipped",
                   "n_reverted")


def _compacted_fused_flat(X, Y, Cs_np, gammas_np,
                          cfg: SolverConfig, chunk: int, impl: str,
                          block_l: int, precompute,
                          shrinking: bool, mesh=None,
                          diagnostics=None) -> SolveResult:
    """Chunked driver over the fused engine, FLAT lane layout.

    Like :func:`_solve_grid_fused` every (gamma, class, C) grid point is
    its own cold-started lane — there is no C chain to scan.  The whole
    lane/row compaction loop lives in
    :func:`~repro.core.solver_fused.solve_fused_chunked_qp`: between
    chunks the host drops converged lanes and (with ``shrinking=True``)
    physically gathers the surviving base rows, so later chunks launch
    their kernels over the live prefix only.  Compaction stacks with the
    in-kernel freeze: frozen lanes cost masked no-op work only until the
    next chunk boundary, after which they cost nothing.
    """
    k, l = Y.shape
    nG, nC = len(gammas_np), len(Cs_np)
    dtype = X.dtype
    Yf = np.repeat(np.tile(np.asarray(Y, np.float64), (nG, 1)), nC, axis=0)
    gam_lane = np.repeat(gammas_np, k * nC)
    C_lane = np.tile(Cs_np, nG * k)
    YC = Yf * C_lane[:, None]
    bank_kw = {}
    if _use_bank(impl, precompute):
        bank_kw = dict(
            gram=jnp.exp(-jnp.asarray(gammas_np, dtype)[:, None, None]
                         * sqdist(X)),
            gram_idx=np.repeat(np.arange(nG, dtype=np.int32), k * nC))
    fr = solve_fused_chunked_qp(
        X, Yf, np.minimum(0.0, YC), np.maximum(0.0, YC), gam_lane, cfg,
        impl=impl, block_l=block_l, chunk=chunk, shrinking=shrinking,
        mesh=mesh, diagnostics=diagnostics, **bank_kw)
    ring = None
    if diagnostics is not None and diagnostics.ring_config is not None:
        fr, ring = fr
    n_free_sv = _free_sv_count(fr.alpha,
                               jnp.asarray(np.minimum(0.0, YC), dtype),
                               jnp.asarray(np.maximum(0.0, YC), dtype))

    def shape(leaf):
        return leaf.reshape((nG, k, nC) + leaf.shape[1:])

    ring_g = None if ring is None else jax.tree.map(shape, ring)
    untracked = jnp.full((nG, k, nC), UNTRACKED, jnp.int32)
    res = SolveResult(
        alpha=shape(fr.alpha), b=shape(fr.b), G=shape(fr.G),
        iterations=shape(fr.iterations),
        objective=shape(fr.objective), kkt_gap=shape(fr.kkt_gap),
        converged=shape(fr.converged),
        n_planning=shape(fr.n_planning), n_free=untracked,
        n_clipped=untracked, n_reverted=untracked,
        n_free_sv=shape(n_free_sv),
        **_trace_fields((nG, k, nC), dtype, ring_g))
    if ring_g is not None:
        # flat lane order == caller axis order here (no C sort on the
        # fused path), so the meta enumerates the result axes directly
        meta = [{"gamma": float(g), "label": int(c), "C": float(Cv)}
                for g in gammas_np for c in range(k) for Cv in Cs_np]
        _drain_grid_ring(diagnostics, ring_g, meta, SimpleNamespace(
            iterations=res.iterations, kkt_gap=res.kkt_gap,
            converged=res.converged, n_planning=res.n_planning,
            n_unshrink=shape(fr.n_unshrink)))
    return res


@span("solve_grid_compacted")
def solve_grid_compacted(X, Y, Cs, gammas,
                         cfg: SolverConfig = SolverConfig(), *,
                         chunk: int = 96, impl: str | None = None,
                         block_l: int = 1024,
                         precompute: bool | None = None,
                         shrinking: bool = False, mesh=None,
                         devices=None, diagnostics=None) -> SolveResult:
    """Host-driven variant of :func:`solve_grid`: same (gamma, class, C)
    result axes, but the batch is re-compacted every ``chunk`` iterations so
    converged lanes stop consuming wall time.  This is the CPU throughput
    mode; the single fused call is the accelerator mode.

    ``impl`` selects the chunk engine exactly as in :func:`solve_grid`.
    ``None`` runs the vmapped standard solver over the shared per-gamma
    Gram bank (lanes *index* the (n_gamma, l, l) stack — no per-lane Gram
    copies), scanning the C axis with scaled warm starts; the per-step
    counters ``n_free``/``n_clipped``/``n_reverted`` are accumulated
    across chunks, matching :func:`solve_grid` semantics.  A kernel
    backend name routes chunks through
    :func:`~repro.core.solver_fused.solve_fused_batched` in the FLAT lane
    layout (every (gamma, class, C) point is a lane; compaction stacks
    with the in-kernel freeze; ``precompute`` picks the row source as in
    :func:`solve_grid`); there the per-step counters
    ``n_free``/``n_clipped``/``n_reverted`` carry the ``UNTRACKED`` (-1)
    sentinel — the fused iteration never materializes the step type, and
    a zero would be indistinguishable from "never happened".  Every mode
    reports the free-support-vector count from the final
    ``alpha``/bounds in ``n_free_sv``.  The trace/step recording buffers
    are placeholders in both modes (chunk resumes reset the O(1)
    recording state).

    ``shrinking=True`` adds active-set shrinking.  On the fused path the
    chunked driver (:func:`~repro.core.solver_fused.solve_fused_chunked_qp`)
    turns it into HARD row compaction: between chunks the bound-pinned
    base rows no live lane can still move are physically gathered out,
    so the kernels run at the shrunken width — real FLOP reduction, with
    LIBSVM-style gradient reconstruction + full-KKT re-check before any
    lane retires (unshrink events are counted per lane).  On the vmapped
    path it enables the classic engine's ``cfg.shrink_every`` cycle.

    ``mesh``/``devices`` (fused path only) lane-shard every chunk as in
    :func:`solve_grid`; host-side lane compaction between chunks stacks
    with the device split.

    ``diagnostics`` (fused path only) turns on the flight recorder: the
    chunked driver emits per-chunk ``chunk_solve`` phase events and EWMA
    ``straggler_warning`` events, the per-chunk device rings are merged
    into run-global per-lane trajectories, and ``trace``/``n_trace``
    carry the Fig. 3 planning-ratio channel as in :func:`solve_grid`.
    """
    X = jnp.asarray(X)
    Y = jnp.asarray(Y)
    if Y.ndim == 1:
        Y = Y[None, :]
    k, l = Y.shape
    Cs_np = np.asarray(Cs, np.float64).reshape(-1)
    gammas_np = np.asarray(gammas, np.float64).reshape(-1)
    if mesh is not None or devices is not None:
        if impl is None:
            raise ValueError("lane sharding runs on the fused engine — "
                             "set impl (e.g. impl='jnp') with mesh/devices")
        mesh = resolve_lane_mesh(mesh, devices)
    if impl is not None:
        with span("fit.solve"):
            return _compacted_fused_flat(X, Y, Cs_np, gammas_np, cfg, chunk,
                                         impl, block_l, precompute,
                                         shrinking, mesh, diagnostics)
    if diagnostics is not None:
        raise ValueError("diagnostics rides the fused engine — set impl "
                         "(e.g. impl='jnp') with diagnostics")
    if shrinking:
        cfg = resolve_shrink_cfg(cfg, True)
    order = np.argsort(Cs_np, kind="stable")
    nG, nC = len(gammas_np), len(Cs_np)
    B = nG * k

    Yf = jnp.tile(Y, (nG, 1))                           # (B, l)
    g_of_lane = np.repeat(np.arange(nG, dtype=np.int32), k)
    D2 = sqdist(X)
    Ks = jnp.exp(-jnp.asarray(gammas_np, X.dtype)[:, None, None] * D2)
    # never exceed the caller's budget: the last chunk may be partial
    ccfg = dataclasses.replace(cfg, max_iter=min(chunk, cfg.max_iter))

    alpha = np.zeros((B, l))
    G = np.asarray(Yf, np.float64).copy()
    C_prev = float(Cs_np[order][0])
    out = {f: np.zeros((B, nC) + s) for f, s in
           [("alpha", (l,)), ("G", (l,)), ("b", ()), ("objective", ()),
            ("kkt_gap", ()), ("converged", ()),
            *[(f, ()) for f in _CHUNK_COUNTERS]]}

    max_chunks = max(1, -(-cfg.max_iter // chunk))
    for ci in order:
        C = float(Cs_np[ci])
        r = C / C_prev
        a_c = alpha * r                                  # scaled warm start
        g_c = (1.0 - r) * np.asarray(Yf) + r * G
        active = np.arange(B)
        counts = {f: np.zeros(B) for f in _CHUNK_COUNTERS}
        for _ in range(max_chunks):
            bsz = _bucket(len(active))
            idx = np.concatenate([active, np.repeat(active[:1],
                                                    bsz - len(active))])
            res = _chunk_solve(Ks, jnp.asarray(g_of_lane[idx]),
                               jnp.take(Yf, idx, axis=0), C,
                               jnp.asarray(a_c[idx], X.dtype),
                               jnp.asarray(g_c[idx], X.dtype), ccfg)
            n = len(active)
            a_c[active] = np.asarray(res.alpha)[:n]
            g_c[active] = np.asarray(res.G)[:n]
            for f in _CHUNK_COUNTERS:
                counts[f][active] += np.asarray(getattr(res, f))[:n]
            done = np.asarray(res.converged)[:n]
            for f in ("b", "objective", "kkt_gap"):
                out[f][active, ci] = np.asarray(getattr(res, f))[:n]
            out["converged"][active, ci] = done
            active = active[~done]
            if len(active) == 0:
                break
        out["alpha"][:, ci] = a_c
        out["G"][:, ci] = g_c
        for f in _CHUNK_COUNTERS:
            out[f][:, ci] = counts[f]
        alpha, G, C_prev = a_c, g_c, C

    YC = np.asarray(Yf)[:, None, :] * Cs_np[None, :, None]   # (B, nC, l)
    out["n_free_sv"] = np.asarray(_free_sv_count(
        out["alpha"], np.minimum(0.0, YC), np.maximum(0.0, YC)))

    def shape(f, dtype=X.dtype):
        arr = out[f].reshape((nG, k, nC) + out[f].shape[2:])
        return jnp.asarray(arr, dtype)

    return SolveResult(
        alpha=shape("alpha"), b=shape("b"), G=shape("G"),
        iterations=shape("iterations", jnp.int32),
        objective=shape("objective"), kkt_gap=shape("kkt_gap"),
        converged=shape("converged", bool),
        n_planning=shape("n_planning", jnp.int32),
        n_free=shape("n_free", jnp.int32),
        n_clipped=shape("n_clipped", jnp.int32),
        n_reverted=shape("n_reverted", jnp.int32),
        n_free_sv=shape("n_free_sv", jnp.int32),
        **_trace_fields((nG, k, nC), X.dtype))


# ---------------------------------------------------------------------------
# Generalized-dual grids: ε-SVR and one-class lanes on the same fused engine
# ---------------------------------------------------------------------------
#
# The fused engine is dual-generic (per-lane P/L/U), so a regression or
# novelty-detection hyper-parameter grid flattens into the SAME flat
# cold-start lane batch as the SVC grid: one while_loop, two batched kernel
# passes per iteration, in-kernel lane freezing.  The ε-SVR lanes run the
# doubled 2l-variable operator over the base X (rows tiled — no 2l x 2l
# Gram anywhere); on the jnp backend both grids share the per-gamma *base*
# Gram bank exactly like the SVC grid.


@span("solve_grid_svr")
def solve_grid_svr(X, y, Cs, epsilons, gammas,
                   cfg: SolverConfig = SolverConfig(), *,
                   impl: str = "auto", block_l: int = 1024,
                   precompute: bool | None = None,
                   shrinking: bool = False, mesh=None,
                   devices=None, diagnostics=None) -> FusedResult:
    """Solve the full ε-SVR (gamma, epsilon, C) grid as one fused lane batch.

    ``X``: (l, d); ``y``: (l,) real targets; ``Cs``: (n_C,); ``epsilons``:
    (n_eps,) tube widths; ``gammas``: (n_gamma,) (scalars are promoted).
    Every lane runs the doubled 2l-variable operator over the *base* X —
    rows stay l-wide on every backend (in-kernel half reads on
    pallas/interpret, tiled base rows on jnp); ``precompute`` picks the
    per-gamma *base* Gram bank exactly as in :func:`solve_grid`.
    Returns a :class:`~repro.core.solver_fused.FusedResult` whose leaves
    have leading axes ``(n_gamma, n_eps, n_C)``; ``alpha`` is the doubled
    (..., 2l) dual — fold with :func:`repro.core.qp.svr_fold` to (..., l)
    coefficients, after which :func:`grid_decision` evaluates the whole
    grid (pass the eps axis in the class slot).  ``shrinking=True``
    enables in-loop soft shrinking over the doubled coordinates (the
    per-lane active mask rides through the ``dup`` kernels like any
    other lane state; see :func:`solve_fused_batched_qp`).
    ``mesh``/``devices`` shard the lane batch over devices exactly as in
    :func:`solve_grid` (doubled lanes promise objective parity vs the
    single-device engine, not bitwise iteration counts — see
    :mod:`repro.core.sharded_lanes`).  ``diagnostics`` turns on the
    flight recorder as in :func:`solve_grid`, with per-lane events keyed
    by (gamma, epsilon, C).
    """
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    dtype = X.dtype
    l = y.shape[0]
    Cs_j = jnp.asarray(np.asarray(Cs, np.float64).reshape(-1), dtype)
    eps_j = jnp.asarray(np.asarray(epsilons, np.float64).reshape(-1), dtype)
    gam_j = jnp.asarray(np.asarray(gammas, np.float64).reshape(-1), dtype)
    nG, nE, nC = gam_j.shape[0], eps_j.shape[0], Cs_j.shape[0]
    zl = jnp.zeros((nC, l), dtype)
    # lane order (gamma, eps, C) row-major; P varies along eps, box along C
    P_e = jnp.concatenate([y[None, :] - eps_j[:, None],
                           y[None, :] + eps_j[:, None]], axis=1)  # (nE, 2l)
    Pf = jnp.tile(jnp.repeat(P_e, nC, axis=0), (nG, 1))           # (B, 2l)
    L_c = jnp.concatenate([zl, -Cs_j[:, None] + zl], axis=1)      # (nC, 2l)
    U_c = jnp.concatenate([Cs_j[:, None] + zl, zl], axis=1)
    Lf = jnp.tile(L_c, (nG * nE, 1))
    Uf = jnp.tile(U_c, (nG * nE, 1))
    gf = jnp.repeat(gam_j, nE * nC)
    bank_kw = {}
    if _use_bank(impl, precompute):
        bank_kw = dict(
            gram=jnp.exp(-gam_j[:, None, None] * sqdist(X)),
            gram_idx=jnp.repeat(jnp.arange(nG, dtype=jnp.int32), nE * nC))
    tel = None if diagnostics is None else diagnostics.ring_config
    cm = (nullcontext() if diagnostics is None
          else diagnostics.scope("solve_grid_svr", lanes=nG * nE * nC))
    with span("fit.solve"), cm:
        if mesh is not None or devices is not None:
            out = solve_fused_sharded_qp(
                X, Pf, Lf, Uf, gf, cfg, mesh=mesh, devices=devices,
                impl=impl, block_l=block_l, doubled=True,
                shrinking=shrinking, telemetry=tel, **bank_kw)
        else:
            out = solve_fused_batched_qp(X, Pf, Lf, Uf, gf, cfg, impl=impl,
                                         block_l=block_l, doubled=True,
                                         shrinking=shrinking, telemetry=tel,
                                         **bank_kw)
        ring = None
        if tel is not None:
            out, ring = out
        if diagnostics is not None:
            jax.block_until_ready(out.alpha)
    if ring is not None:
        # flat lane order (gamma, eps, C) row-major == the result axes
        meta = [{"gamma": float(g), "epsilon": float(e), "C": float(Cv)}
                for g in np.asarray(gam_j) for e in np.asarray(eps_j)
                for Cv in np.asarray(Cs_j)]
        diagnostics.drain_ring(ring, meta, out)
    return jax.tree.map(
        lambda leaf: leaf.reshape((nG, nE, nC) + leaf.shape[1:]), out)


@span("solve_grid_oneclass")
def solve_grid_oneclass(X, nus, gammas, cfg: SolverConfig = SolverConfig(),
                        *, impl: str = "auto", block_l: int = 1024,
                        precompute: bool | None = None,
                        shrinking: bool = False, mesh=None,
                        devices=None, diagnostics=None) -> FusedResult:
    """Solve the one-class (gamma, nu) grid as one fused lane batch.

    Every lane is the ν dual (``p = 0``, box ``[0, 1/(nu l)]``, ``sum(a) =
    1``) started from the LIBSVM feasible point with its closed-position
    gradient ``G0 = -K alpha0`` (one matvec per lane, paid once before the
    loop).  ``precompute`` picks the per-gamma Gram-bank row source as in
    :func:`solve_grid`.  Returns a
    :class:`~repro.core.solver_fused.FusedResult` with
    leading axes ``(n_gamma, n_nu)``; the decision offset is ``rho = -b``
    (``decision(x) = k(x, SVs) @ alpha + b``).  ``mesh``/``devices`` shard
    the lane batch over devices exactly as in :func:`solve_grid` (the lane
    cost proxy is the box width ``1/(nu l)``: small-nu lanes are the
    stragglers and spread round-robin across shards).  ``diagnostics``
    turns on the flight recorder as in :func:`solve_grid`, with per-lane
    events keyed by (gamma, nu).
    """
    X = jnp.asarray(X)
    dtype = X.dtype
    l = X.shape[0]
    nus_np = np.asarray(nus, np.float64).reshape(-1)
    gam_j = jnp.asarray(np.asarray(gammas, np.float64).reshape(-1), dtype)
    nG, nN = gam_j.shape[0], len(nus_np)
    A0 = jnp.stack([qp_mod.oneclass_alpha0(l, nu, dtype) for nu in nus_np])
    U_n = jnp.stack([qp_mod.oneclass_qp(l, nu, dtype).bounds.upper
                     for nu in nus_np])                           # (nN, l)
    Pf = jnp.zeros((nG * nN, l), dtype)
    Lf = jnp.zeros((nG * nN, l), dtype)
    Uf = jnp.tile(U_n, (nG, 1))
    gf = jnp.repeat(gam_j, nN)
    alpha0 = jnp.tile(A0, (nG, 1))
    bank_kw = {}
    if _use_bank(impl, precompute):
        bank = jnp.exp(-gam_j[:, None, None] * sqdist(X))
        G0 = -jnp.einsum("gij,nj->gni", bank, A0).reshape(nG * nN, l)
        bank_kw = dict(
            gram=bank,
            gram_idx=jnp.repeat(jnp.arange(nG, dtype=jnp.int32), nN))
    else:
        # Gram-free init: one blocked RBF matvec per (gamma, nu) lane
        G0 = -jax.vmap(lambda g: jax.vmap(
            lambda a: qp_mod.make_rbf(X, g).matvec(a))(A0))(gam_j)
        G0 = G0.reshape(nG * nN, l)
    tel = None if diagnostics is None else diagnostics.ring_config
    cm = (nullcontext() if diagnostics is None
          else diagnostics.scope("solve_grid_oneclass", lanes=nG * nN))
    with span("fit.solve"), cm:
        if mesh is not None or devices is not None:
            out = solve_fused_sharded_qp(
                X, Pf, Lf, Uf, gf, cfg, mesh=mesh, devices=devices,
                impl=impl, block_l=block_l, alpha0=alpha0, G0=G0,
                shrinking=shrinking, telemetry=tel, **bank_kw)
        else:
            out = solve_fused_batched_qp(X, Pf, Lf, Uf, gf, cfg, impl=impl,
                                         block_l=block_l, alpha0=alpha0,
                                         G0=G0, shrinking=shrinking,
                                         telemetry=tel, **bank_kw)
        ring = None
        if tel is not None:
            out, ring = out
        if diagnostics is not None:
            jax.block_until_ready(out.alpha)
    if ring is not None:
        meta = [{"gamma": float(g), "nu": float(nu)}
                for g in np.asarray(gam_j) for nu in nus_np]
        diagnostics.drain_ring(ring, meta, out)
    return jax.tree.map(
        lambda leaf: leaf.reshape((nG, nN) + leaf.shape[1:]), out)


def grid_decision(Xq, X, gammas, alpha: jax.Array,
                  b: jax.Array) -> jax.Array:
    """Decision values of every grid point on query inputs.

    ``alpha``: (n_gamma, k, n_C, l) signed duals from :func:`solve_grid`;
    ``b``: (n_gamma, k, n_C).  Returns (n_gamma, k, n_C, m) — the query
    cross-Gram is computed once per gamma and shared by all (class, C)
    heads.
    """
    Xq = jnp.asarray(Xq)
    X = jnp.asarray(X)
    gammas = jnp.atleast_1d(jnp.asarray(gammas, X.dtype))
    sq_q = jnp.sum(Xq * Xq, axis=-1)
    sq_x = jnp.sum(X * X, axis=-1)
    d2 = jnp.maximum(sq_q[:, None] + sq_x[None, :]
                     - 2.0 * jnp.dot(Xq, X.T, precision=HIGHEST), 0.0)

    def per_gamma(gamma, a_g, b_g):
        Kq = jnp.exp(-gamma * d2)                      # (m, l) once per gamma
        return (jnp.einsum("ml,kcl->kcm", Kq, a_g, precision=HIGHEST)
                + b_g[..., None])

    return jax.vmap(per_gamma)(gammas, alpha, b)
