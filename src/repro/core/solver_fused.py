"""Fused two-pass PA-SMO solver (the beyond-paper optimized iteration).

The standard solver (:mod:`repro.core.solver`) mirrors LIBSVM's structure:
row fetch, selection, second row fetch, update, stopping scan — ~4 logical
passes over O(l) state per iteration.  This solver restructures the
iteration into exactly the two fused passes implemented by the Pallas
kernels in :mod:`repro.kernels`:

  pass A: second-order j-selection                   (reads X, G, masks)
  pass B: k_i and k_j (VMEM-only) + gradient update + next i-pick + KKT
          gap ends

All O(1) work in between — the truncated Newton step, the planning-ahead
step size (eq. 8), the ≤4x4 kernel minor, Alg. 3's B^(t-2) candidate —
runs on per-lane scalars, with single-entry RBF evaluations costing O(d).
Semantics are those of ``solver.solve`` with an RBF oracle (same
Algorithms 3/4/5); trajectories agree modulo floating-point reassociation.
``impl`` selects pallas/interpret/jnp exactly as in ``repro.kernels.ops``.

:func:`solve_fused_batched_qp` is the one fused engine.  It runs a whole
*batch of lanes* — one lane per *general* dual QP (:mod:`repro.core.qp`:
per-lane linear term ``P`` and box ``L``/``U``; classification, ε-SVR
with ``doubled=True`` lanes over a shared base ``X``, one-class via
feasible warm starts) — through ONE ``lax.while_loop`` whose body is TWO
batched kernel launches plus O(B) per-lane algebra; a single QP is the
batch of one.  Pass A returns only the selection, and pass B recomputes
both rows k_i/k_j against the shared X tile: no kernel row makes an HBM
round-trip, and when Alg. 3's B^(t-2) candidate wins no pass is
relaunched.  Converged lanes are frozen *in kernel*: their step size is
forced to 0, so pass B's update is a bitwise no-op on G.  A lane
converges only through the exit check, which reads its gap from exact
gradient rows (see :func:`solve_fused_batched_qp`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qp as qp_mod
from repro.core import step as step_mod
from repro.core.qp import TAU
from repro.core.solver import DEFAULT_SHRINK_EVERY, SolverConfig
from repro.kernels import ops
from repro.kernels import row_source
from repro.kernels.ref import take_lane as _take_lane
from repro.telemetry import phase_scope
from repro.telemetry.ring import (RingConfig, TelemetryRing, ring_init,
                                  ring_update)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FusedResult:
    alpha: jax.Array
    b: jax.Array
    G: jax.Array
    iterations: jax.Array
    objective: jax.Array
    kkt_gap: jax.Array
    converged: jax.Array
    n_planning: jax.Array
    # number of reactivations: a lane looked solved, the full exact check
    # failed, and the lane was reactivated.  Counts both shrinking's
    # unshrink events (the masked problem looked solved) and the batched
    # engine's exit-check reopenings (the carried gradient read a gap of
    # eps, the gap from exact rows did not)
    n_unshrink: jax.Array


# ---------------------------------------------------------------------------
# Lane-batched fused solver
# ---------------------------------------------------------------------------


class _BatchState(NamedTuple):
    alpha: jax.Array          # (B, l)
    G: jax.Array              # (B, l)
    i: jax.Array              # (B,) next working-set first index (pass B)
    g_i: jax.Array            # (B,) G[i] == max gradient over I_up
    gap: jax.Array            # (B,)
    t: jax.Array              # () global iteration counter
    iters: jax.Array          # (B,) per-lane iterations until convergence
    done: jax.Array           # (B,)
    pi: jax.Array             # (B,) planning history B^(t-1)
    pj: jax.Array
    qi: jax.Array             # (B,) planning history B^(t-2)
    qj: jax.Array
    n_hist: jax.Array         # (B,)
    p_smo: jax.Array          # (B,)
    prev_free: jax.Array      # (B,)
    prev_ratio_ok: jax.Array  # (B,)
    n_planning: jax.Array     # (B,)
    act: jax.Array            # (B, n) bool active set ((B, 1) dummy when
                              # shrinking is off)
    n_unshrink: jax.Array     # (B,) reactivation events (FusedResult)
    pend: jax.Array           # (B,) carried gap reached eps: exit check due
    b: jax.Array              # (B,) bias from the last exit check's exact ends


class _ConjState(NamedTuple):
    """Per-lane Conjugate-SMO carry (``cfg.step == "conjugate"`` only).

    Rides the while_loop carry *next to* the batch state, exactly like the
    telemetry ring: with ``step="plain"`` it does not exist, so the plain
    engine's traced jaxpr stays byte-identical to the pre-conjugate
    goldens under ``tests/golden/``.
    """

    u: jax.Array    # (B, n) Q (e_pi - e_pj): previous direction's Q-product
                    # (pass B's in-VMEM row difference k_i - k_j)
    ok: jax.Array   # (B,) direction valid (reset on clip / shrink events)


VERIFY_ROWS = 64      # rows checked exactly at each end of a lane's gap
VERIFY_ROUNDS = 3     # rounds of trips, each ended by an exit check


def _end_rows(G, up, dn, r: int):
    """(B, 2r) rows nearest each end of every lane's gap by ``G``: the r
    largest over ``up``, then the r smallest over ``dn``.  Picked one by
    one in (value, index) order, each pick the first after the last;
    a sort of an l-long row costs the TPU compiler tens of seconds, and
    a loop that rewrote G-sized arrays would claim fast memory the trips
    need."""
    inf = jnp.asarray(jnp.inf, G.dtype)
    v = jnp.stack([jnp.where(up, G, -inf), jnp.where(dn, -G, -inf)])
    idx = jnp.arange(G.shape[1], dtype=jnp.int32)

    def pick(t, c):
        last_v, last_i, rows = c                       # (2, B) each
        after = ((v < last_v[..., None])
                 | ((v == last_v[..., None]) & (idx > last_i[..., None])))
        w = jnp.where(after, v, -inf)
        i = jax.lax.argmax(w, 2, jnp.int32)
        val = jax.vmap(_take_lane)(w, i)
        rows = jax.lax.dynamic_update_slice_in_dim(rows, i[..., None], t, 2)
        return val, i, rows

    B = G.shape[0]
    c0 = (jnp.full((2, B), inf, G.dtype), jnp.full((2, B), -1, jnp.int32),
          jnp.zeros((2, B, r), jnp.int32))
    rows = jax.lax.fori_loop(jnp.int32(0), jnp.int32(r), pick, c0)[2]
    return jnp.concatenate([rows[0], rows[1]], axis=1)


def _set_lane(M, idx, val):
    """Per-lane scatter ``M[b, idx[b]] = val[b]`` (idx (B,) or (B, m)),
    on the int32 index channel like :func:`_take_lane`."""
    return jax.vmap(lambda row, i, v: row.at[i].set(v))(
        M, idx, jnp.broadcast_to(val, idx.shape).astype(M.dtype))


def _exit_check(src, P, L, U, alpha, G, eps):
    """LIBSVM's stopping test for every lane, on exact gradient rows.

    ``G`` is the carried gradient, which drifts in float32 over many
    rank-2 updates.  The :data:`VERIFY_ROWS` rows nearest each end of a
    lane's gap by carried value (the largest ``G`` over ``alpha < U``, the
    smallest over ``alpha > L``) are recomputed as ``p - Q alpha``
    (:meth:`~repro.kernels.row_source.RowSource.exact_rows`).  The largest
    correction found bounds the drift of the rows left unchecked, whose
    carried values count widened by twice it (as the benchmark's float64
    check widens its float32 pass).  Returns the gradient with the checked
    rows exact, that bounded gap, the bias (the midpoint of the checked
    exact ends) and whether the gap is at most ``eps``, per lane.
    """
    B, n = G.shape
    up, dn = alpha < U, alpha > L
    inf = jnp.asarray(jnp.inf, G.dtype)
    rows = _end_rows(G, up, dn, min(VERIFY_ROWS, n))
    take = lambda M: _take_lane(M, rows)
    g_ex = take(P) - src.exact_rows(rows, alpha)
    widen = 2.0 * jnp.max(jnp.abs(g_ex - take(G)), axis=1)
    rest = _set_lane(jnp.ones((B, n), bool), rows, False)
    e_up = jnp.max(jnp.where(take(up), g_ex, -inf), axis=1)
    e_dn = jnp.min(jnp.where(take(dn), g_ex, inf), axis=1)
    g_up = jnp.maximum(e_up, jnp.max(jnp.where(up & rest, G, -inf), axis=1)
                       + widen)
    g_dn = jnp.minimum(e_dn, jnp.min(jnp.where(dn & rest, G, inf), axis=1)
                       - widen)
    gap = qp_mod.finite_gap(g_up - g_dn)
    return (_set_lane(G, rows, g_ex), gap, qp_mod.safe_bias(e_up, e_dn),
            gap <= eps)


@partial(jax.jit, static_argnames=("cfg", "impl", "block_l", "doubled",
                                   "shrinking", "telemetry"))
def solve_fused_batched_qp(X, P, L, U, gamma,
                           cfg: SolverConfig = SolverConfig(),
                           *, impl: str = "auto", block_l: int = 1024,
                           alpha0=None, G0=None, gram=None, gram_idx=None,
                           doubled: bool = False,
                           shrinking: bool = False,
                           telemetry: RingConfig | None = None):
    """Solve a batch of B *general* dual QPs over shared ``X`` in ONE
    while_loop: per-lane linear term ``P`` (B, n), per-coordinate box
    ``L``/``U`` (B, n), per-lane RBF ``gamma`` (scalar or (B,)).

    This is the general-dual core behind :func:`solve_fused_batched`
    (classification), the ε-SVR lanes (``doubled=True``) and the one-class
    lanes (``P = 0``, warm ``alpha0``/``G0`` since 0 is infeasible there).

    ``doubled=True`` runs the 2l-variable ε-SVR operator: ``X`` stays the
    base (l, d) matrix while the lane state is (B, 2l); kernel rows are
    base rows tiled (:mod:`repro.kernels` ``dup``), Gram-bank entries
    index ``k mod l`` — the 2l x 2l matrix never exists anywhere.

    Optional (B, n) ``alpha0``/``G0`` warm starts must come as a pair.
    Per iteration the body launches the batched pass A (selection) and
    pass B (both-rows + update + stopping scan) kernels; all remaining
    algebra — steps, planning, Alg. 3 candidates — is O(B) vector math
    plus O(B d) single-entry kernel evaluations.  Converged lanes freeze
    in-kernel: mu is forced to 0, so the update pass leaves their state
    bitwise unchanged while the loop runs until every lane is done (or
    ``cfg.max_iter``).  The returned :class:`FusedResult` leaves carry a
    leading lane axis; ``iterations`` counts per-lane iterations *until
    that lane converged*.

    **The exit check** holds every lane to LIBSVM's guarantee, a true KKT
    gap of at most ``cfg.eps``.  The carried gradient G drifts in float32
    over tens of thousands of rank-2 updates, so a lane whose carried gap
    reaches eps is only *pending*: it freezes like a done lane, and once
    every lane is pending or done the trips stop and the pending lanes'
    gaps are recomputed on exact rows (:func:`_exit_check`: the
    :data:`VERIFY_ROWS` rows nearest each end by carried G, recomputed as
    ``p - Q alpha`` by
    :meth:`~repro.kernels.row_source.RowSource.exact_rows`; unchecked rows
    count widened by twice the largest correction found).  At most eps,
    the lane is done, with ``kkt_gap`` that checked gap and ``b`` the
    midpoint of its exact ends.  Otherwise the exact rows are written into
    G and the lane *reopens* (counted in ``FusedResult.n_unshrink``): the
    reopened lanes run another round of trips and are checked again, for
    at most :data:`VERIFY_ROUNDS` rounds; a lane that fails its last check
    returns unconverged, with that gap.  The check runs under the named
    scope ``fused_verify``, on the row-major X, and the rounds follow one
    another in the program, none inside a loop: the trip loop of the first
    round, which holds nearly all of the trips, then keeps the lane state
    in the TPU's fast memory, as the engine without the
    check does (in any loop that also holds a check, the compiler places
    it in HBM; AOT for v5e).  A cold start's gradient ``P`` is exact, so
    its gap decides at once; a warm start's ``G0`` is checked.

    Two row sources (:mod:`repro.kernels.row_source`):

    * default — rows are recomputed from ``X`` inside the kernels (the
      accelerator memory mode: O(B n) state, no Gram ever materialized;
      ``impl`` picks pallas/interpret/jnp as in :mod:`repro.kernels.ops`;
      with ``doubled=True`` the kernels read the base row tile once per
      variable half — the matmuls never widen past l).
    * ``gram``/``gram_idx`` — a shared (n_stack, l, l) *base* Gram bank
      plus the per-lane stack index: rows become gathers and the exp work
      is paid once per distinct gamma instead of per iteration.  This is
      the CPU throughput mode (it mirrors the vmapped engine's memory
      layout); ``impl`` applies here too — ``"jnp"`` runs the selection /
      update algebra as XLA-fused jnp, ``"interpret"``/``"pallas"`` route
      the gathered rows through the rows-variant Pallas kernels.  Lanes
      sharing a gamma index the same bank entry — no per-lane Gram copies.

    ``shrinking=True`` enables LIBSVM-style *soft* active-set shrinking:
    every ``cfg.shrink_every`` iterations (default
    :data:`~repro.core.solver.DEFAULT_SHRINK_EVERY`) bound-pinned
    variables that cannot belong to any violating pair are masked out of
    the pass A/B scans via a per-lane (B, n) active mask threaded through
    the kernels.  The gradient update itself is never masked, so G stays
    current everywhere and unshrinking is free: a lane whose *masked* gap
    reaches ``eps`` with a partial mask is reactivated in-loop (counted in
    ``FusedResult.n_unshrink``) and only goes to the exit check once the
    gap over the FULL active set reaches eps — objectives are identical
    to the unshrunk engine up to selection-order float reassociation.
    Soft shrinking keeps the scans O(n) (masked lanes still ride through
    the kernels); the wall-clock win on CPU/host comes from
    :func:`solve_fused_chunked_qp`, which periodically *compacts* rows and
    lanes so the kernels launch over the live prefix only.

    ``telemetry`` (a static :class:`~repro.telemetry.ring.RingConfig`)
    turns on the in-loop flight recorder: a
    :class:`~repro.telemetry.ring.TelemetryRing` rides the while_loop
    carry sampling per-lane KKT gap / active-set size / unshrink counts
    every ``sample_every`` iterations (plus the freeze iteration) and
    every accepted planning-step mu/mu* ratio — the classic engine's
    Fig. 3 ``record_trace`` channel, per lane.  The return value becomes
    the ``(FusedResult, TelemetryRing)`` pair.  With ``telemetry=None``
    (default) no ring exists in the carry and the traced jaxpr is
    byte-identical to the telemetry-free engine — the hot path pays
    nothing when observability is off.

    ``cfg.step == "conjugate"`` (plain-SMO lanes only) enables the
    Conjugate-SMO two-direction step: each iteration solves the exact 2x2
    subproblem spanned by the current WSS direction and the *previous*
    update direction, whose Q-product is carried per lane as a
    :class:`_ConjState` element riding the while_loop carry next to the
    batch state (like the ring, Python-gated: with ``step="plain"`` the
    traced jaxpr is byte-identical to the pre-conjugate engine).  The
    conjugate step is accepted only when the carried direction is valid,
    the 2x2 minor is safely positive definite, all four touched
    coordinates stay strictly interior, and the exact 2-D gain dominates
    the 1-D Newton gain; otherwise the lane falls back to the plain
    clipped SMO step bitwise.  The direction resets on clipped steps,
    shrink-mask refreshes and unshrink events (reset-on-clip).  Accepted
    steps are counted in ``FusedResult.n_planning`` and surface on the
    telemetry plan-event/ratio channels (planning and conjugate are
    mutually exclusive — ``SolverConfig`` forbids ``pasmo`` here).
    """
    assert cfg.algorithm in ("smo", "pasmo")
    assert cfg.plan_candidates == 1
    assert cfg.wss == "wss2", \
        "the fused passes hardcode WSS2 selection (use the standard solver)"
    assert not (cfg.record_trace or cfg.record_steps), \
        "the fused solver does not record traces/steps"
    assert (alpha0 is None) == (G0 is None), \
        "warm starts need the (alpha0, G0) pair"
    assert (gram is None) == (gram_idx is None), \
        "the Gram bank needs the (gram, gram_idx) pair"
    bank = gram is not None
    X = jnp.asarray(X)
    P = jnp.asarray(P)
    dtype = P.dtype
    B, n = P.shape
    lb = X.shape[0]                       # base example count (n or n // 2)
    assert n == (2 * lb if doubled else lb)
    L = jnp.asarray(L, dtype)
    U = jnp.asarray(U, dtype)
    eps = cfg.eps
    eta = cfg.eta
    planning = cfg.algorithm == "pasmo"
    # Conjugate-SMO (static knob, cfg asserts algorithm == "smo"): like the
    # ring, the extra carried state is a *separate* carry element gated at
    # the Python level, so step="plain" traces byte-identical to the
    # pre-conjugate engine.
    conjugate = cfg.step == "conjugate"
    period = cfg.shrink_every if cfg.shrink_every > 0 else DEFAULT_SHRINK_EVERY
    lanes = jnp.arange(B, dtype=jnp.int32)
    # Flight recorder (static knob).  ``collect=False`` must leave the
    # traced jaxpr structurally identical to the telemetry-free engine, so
    # every telemetry hook below is a *Python-level* branch: no ring in the
    # carry, no extra traced ops.  The named scopes (``fused_pass_a``,
    # ``fused_pass_b``, ``fused_step``, ``fused_shrink``) are always on:
    # they change op metadata only, so a profile of the engine as deployed
    # splits the loop body by scope.
    collect = telemetry is not None
    if bank:
        src = row_source.bank_source(gram, gram_idx, gamma, dup=doubled)
    else:
        src = row_source.rbf_source(X, gamma, B, dup=doubled)
        if ops.resolve_impl(impl) != "jnp":
            # X is loop-invariant: build the passes' feature-major stream
            # once here, not in every trip
            src = ops.pad_source(src, B, block_l, masked=shrinking,
                                 conj=conjugate)

    # The loop body is dispatch-bound on CPU (dozens of O(B) ops between the
    # two passes), so the per-lane scalar algebra below leans on two
    # fusions: (a) paired gathers/entries stack their index vectors and
    # gather once, and (b) the two alpha scatters merge into one.

    def body(carry):
        s, conj, ring = carry
        with jax.named_scope("fused_step"):
            alpha, G = s.alpha, s.G
            idx2 = jnp.concatenate([lanes, lanes])

            def at_idx(idx):
                """(alpha, G, L, U) at per-lane coordinate ``idx`` — four tiny
                (B,) gathers (the general box is data, not a label formula)."""
                return (_take_lane(alpha, idx), _take_lane(G, idx),
                        _take_lane(L, idx), _take_lane(U, idx))

            active = ~s.done & ~s.pend
            use_exact = jnp.asarray(planning) & (~s.p_smo) & (~s.prev_ratio_ok)
            act_kw = s.act if shrinking else None

            # ---- pass A: j-selection (k_i stays in VMEM / the bank) --------
            a_i, _, L_i, U_i = at_idx(s.i)
        with jax.named_scope("fused_pass_a"):
            j0, gain0 = ops.source_row_wss(src, G, alpha, L, U, s.i, a_i,
                                           L_i, U_i, s.g_i, use_exact,
                                           impl=impl, block_l=block_l,
                                           act=act_kw)
        with jax.named_scope("fused_step"):
            a_j0, G_j0, L_j0, U_j0 = at_idx(j0)

            # ---- Alg. 3 extra candidate B^(t-2) (O(B d)) -------------------
            if planning:
                # both "historic" entries in one stacked lookup:
                # K(qi, qj) for the candidate, K(pi, pj) for planning's Q22
                e2 = src.entry_pairs(jnp.concatenate([s.qi, s.pi]),
                                     jnp.concatenate([s.qj, s.pj]), 2)
                K_qq, K_pp = e2[:B], e2[B:]
                a_qi, G_qi, L_qi, U_qi = at_idx(s.qi)
                a_qj, G_qj, L_qj, U_qj = at_idx(s.qj)
                l_q = G_qi - G_qj
                q_q = jnp.maximum(2.0 - 2.0 * K_qq, TAU)
                sb_q = step_mod.step_bounds(a_qi, a_qj, L_qi, U_qi, L_qj, U_qj)
                mu_q = step_mod.clip_step(l_q / q_q, sb_q)
                cg_exact = step_mod.gain_of_step(mu_q, l_q, q_q)
                cg_tilde = 0.5 * l_q * l_q / q_q
                cg = jnp.where(use_exact, cg_exact, cg_tilde)
                adm = ((a_qi < U_qi) & (a_qj > L_qj)
                       & (l_q > 0) & (s.qi != s.qj) & (s.n_hist > 1))
                take = (~s.p_smo) & adm & (cg > gain0)
                # no relaunch needed: pass B recomputes the winning row anyway,
                # and the candidate's scalars are selects of already-gathered
                # values — no fresh gathers for (i_sel, j_sel)
                i_sel = jnp.where(take, s.qi, s.i)
                j_sel = jnp.where(take, s.qj, j0)
                g_i_sel = jnp.where(take, G_qi, s.g_i)
                a_isel = jnp.where(take, a_qi, a_i)
                L_isel = jnp.where(take, L_qi, L_i)
                U_isel = jnp.where(take, U_qi, U_i)
                a_jsel = jnp.where(take, a_qj, a_j0)
                G_jsel = jnp.where(take, G_qj, G_j0)
                L_jsel = jnp.where(take, L_qj, L_j0)
                U_jsel = jnp.where(take, U_qj, U_j0)
            else:
                i_sel, j_sel, g_i_sel = s.i, j0, s.g_i
                a_isel, L_isel, U_isel = a_i, L_i, U_i
                a_jsel, G_jsel, L_jsel, U_jsel = a_j0, G_j0, L_j0, U_j0

            # ---- O(B) step computation ------------------------------------
            lw = g_i_sel - G_jsel
            K_ij = src.entry_pairs(i_sel, j_sel, 1)
            q11 = jnp.maximum(2.0 - 2.0 * K_ij, TAU)
            sb = step_mod.step_bounds(a_isel, a_jsel, L_isel, U_isel,
                                      L_jsel, U_jsel)
            mu_star = lw / q11
            mu_smo, free_smo = step_mod.smo_step(lw, q11, sb)

            do_plan = jnp.zeros((B,), bool)
            mu_plan = mu_smo
            ratio_ok = s.prev_ratio_ok
            if planning:
                a_pi, G_pi, L_pi, U_pi = at_idx(s.pi)
                a_pj, G_pj, L_pj, U_pj = at_idx(s.pj)
                w2 = G_pi - G_pj
                q22 = jnp.maximum(2.0 - 2.0 * K_pp, TAU)
                e4 = src.entry_pairs(
                    jnp.concatenate([i_sel, i_sel, j_sel, j_sel]),
                    jnp.concatenate([s.pi, s.pj, s.pi, s.pj]), 4)
                q12 = e4[:B] - e4[B:2 * B] - e4[2 * B:3 * B] + e4[3 * B:]
                terms = step_mod.PlanningTerms(w1=lw, w2=w2, Q11=q11, Q22=q22,
                                               Q12=q12)
                mu1, okdet = step_mod.planning_step(terms)
                mu2 = step_mod.planned_second_step(mu1, terms)
                interior1 = (sb.lo < mu1) & (mu1 < sb.hi)
                d_pi = ((s.pi == i_sel).astype(dtype)
                        - (s.pi == j_sel).astype(dtype))
                d_pj = ((s.pj == i_sel).astype(dtype)
                        - (s.pj == j_sel).astype(dtype))
                sb2 = step_mod.step_bounds(a_pi + mu1 * d_pi, a_pj + mu1 * d_pj,
                                           L_pi, U_pi, L_pj, U_pj)
                interior2 = (sb2.lo < mu2) & (mu2 < sb2.hi)
                feasible = okdet & interior1 & interior2 & (s.n_hist > 0)
                do_plan = s.prev_free & feasible
                mu_plan = jnp.where(do_plan, mu1, mu_smo)
                ratio = mu1 / jnp.where(jnp.abs(mu_star) > 0, mu_star, 1.0)
                ratio_ok = jnp.where(do_plan,
                                     (ratio >= 1.0 - eta)
                                     & (ratio <= 1.0 + eta),
                                     s.prev_ratio_ok)

            if conjugate:
                # ---- Conjugate-SMO 2x2 step (O(B), no extra kernel rows) ---
                # Directions: v1 = e_i - e_j (current WSS pair), v2 =
                # e_pi - e_pj (previous pair).  Q v2 is carried in
                # ``conj.u`` — pass B's in-VMEM row difference from last
                # iteration — so every restriction term below is a per-lane
                # gather.
                a_pi, G_pi, L_pi, U_pi = at_idx(s.pi)
                a_pj, G_pj, L_pj, U_pj = at_idx(s.pj)
                w2 = G_pi - G_pj
                q22 = _take_lane(conj.u, s.pi) - _take_lane(conj.u, s.pj)
                q12 = _take_lane(conj.u, i_sel) - _take_lane(conj.u, j_sel)
                terms = step_mod.PlanningTerms(w1=lw, w2=w2, Q11=q11, Q22=q22,
                                               Q12=q12)
                mu1c, mu2c, okdet = step_mod.conjugate_step(terms)

                def moved(c):
                    # net displacement of coordinate c under mu1c v1 + mu2c v2;
                    # indicator arithmetic handles overlapping pairs exactly
                    return (mu1c * ((c == i_sel).astype(dtype)
                                    - (c == j_sel).astype(dtype))
                            + mu2c * ((c == s.pi).astype(dtype)
                                      - (c == s.pj).astype(dtype)))

                def interior(c, a_c, L_c, U_c):
                    a2 = a_c + moved(c)
                    return (L_c < a2) & (a2 < U_c)

                inter = (interior(i_sel, a_isel, L_isel, U_isel)
                         & interior(j_sel, a_jsel, L_jsel, U_jsel)
                         & interior(s.pi, a_pi, L_pi, U_pi)
                         & interior(s.pj, a_pj, L_pj, U_pj))
                # exact gain of the unconstrained 2-direction solve; it
                # dominates the 1-D Newton gain along v1 for a PD minor, so
                # the comparison guards near-degenerate numerics only
                g2 = 0.5 * (lw * mu1c + w2 * mu2c)
                g1 = step_mod.gain_newton(lw, q11)
                do_plan = (conj.ok & (s.n_hist >= 1) & okdet & inter
                           & (g2 + TAU >= g1))
                mu_plan = jnp.where(do_plan, mu1c, mu_smo)
                ratio = mu1c / jnp.where(jnp.abs(mu_star) > 0, mu_star, 1.0)

            # lane freeze: converged lanes take a zero step — pass B becomes a
            # bitwise no-op on their G, alpha is untouched.  Both working-set
            # coordinates update through ONE stacked scatter.  The isfinite
            # guard freezes a lane for one repair iteration when an unshrink
            # event left it with a stale -inf g_i (empty masked I_up).
            mu = jnp.where(active & jnp.isfinite(lw),
                           jnp.where(do_plan, mu_plan, mu_smo), 0.0)
            if conjugate:
                # second-direction coefficient; 0 on rejected/frozen lanes, so
                # both the extra scatter coordinates and pass B's axpy against
                # ``conj.u`` are exact no-ops there (lane freeze stays bitwise)
                mu2v = jnp.where(active & jnp.isfinite(lw) & do_plan, mu2c, 0.0)
                idx4 = jnp.concatenate([idx2, idx2])
                alpha_new = alpha.at[
                    idx4, jnp.concatenate([i_sel, j_sel, s.pi, s.pj])].add(
                    jnp.concatenate([mu, -mu, mu2v, -mu2v]))
            else:
                alpha_new = alpha.at[idx2, jnp.concatenate([i_sel, j_sel])].add(
                    jnp.concatenate([mu, -mu]))

        # ---- pass B: k_i/k_j + update + next i + gap -----------------------
        with jax.named_scope("fused_pass_b"):
            if conjugate:
                G_new, i_next, g_i_next, g_dn, r_new = ops.source_update_wss(
                    src, G, alpha_new, L, U, i_sel, j_sel, mu, impl=impl,
                    block_l=block_l, act=act_kw, dirv=conj.u, mu2=mu2v)
            else:
                G_new, i_next, g_i_next, g_dn = ops.source_update_wss(
                    src, G, alpha_new, L, U, i_sel, j_sel, mu, impl=impl,
                    block_l=block_l, act=act_kw)
        with jax.named_scope("fused_step"):
            gap_new = qp_mod.finite_gap(g_i_next - g_dn)
            # a lane whose carried gap reaches eps is not done yet: it is
            # pending, frozen until the round's exit check (see below)
            if shrinking:
                # a lane only goes to the check when its mask was FULL at the
                # scan that produced the gap; a partial-mask "solved" lane is
                # unshrunk in place and keeps iterating (G is updated
                # everywhere, so reactivation costs nothing).
                full_now = jnp.all(s.act, axis=1)
                locally_done = gap_new <= eps
                newly = active & locally_done & full_now
                with jax.named_scope("fused_shrink"):
                    refresh = (s.t % period) == (period - 1)
                    act2 = jax.lax.cond(
                        refresh,
                        lambda: qp_mod.shrink_mask(G_new, alpha_new, L, U),
                        lambda: s.act)
                    act2 = act2 | (locally_done & ~full_now)[:, None]
                    act_new = jnp.where((active & ~newly)[:, None], act2,
                                        s.act)
                n_unshrink = s.n_unshrink + (
                    active & locally_done & ~full_now).astype(jnp.int32)
            else:
                newly = active & (gap_new <= eps)
                act_new = s.act
                n_unshrink = s.n_unshrink
            gap = jnp.where(active, gap_new, s.gap)

            if conjugate:
                # next iteration's carried direction: Q (e_i - e_j) is exactly
                # pass B's in-VMEM row difference, returned for free.  The
                # direction is reset (ok = False) whenever the step clipped
                # (plain SMO hit the box), the shrink mask refreshed, or the
                # lane unshrunk — per Conjugate-SMO's reset-on-clip rule.
                cu_new = jnp.where(active[:, None], r_new, conj.u)
                c_ok = do_plan | free_smo
                if shrinking:
                    c_ok = c_ok & ~refresh & ~(locally_done & ~full_now)
                c_ok = jnp.where(active, c_ok, conj.ok)
                conj_new = _ConjState(u=cu_new, ok=c_ok)

            new_s = _BatchState(
                alpha=alpha_new, G=G_new,
                i=jnp.where(active, i_next.astype(jnp.int32), s.i),
                g_i=jnp.where(active, g_i_next, s.g_i),
                gap=gap, t=s.t + 1, iters=s.iters + active.astype(jnp.int32),
                done=s.done,
                pi=jnp.where(active, i_sel, s.pi).astype(jnp.int32),
                pj=jnp.where(active, j_sel, s.pj).astype(jnp.int32),
                qi=jnp.where(active, s.pi, s.qi),
                qj=jnp.where(active, s.pj, s.qj),
                n_hist=jnp.where(active, jnp.minimum(s.n_hist + 1, 2),
                                 s.n_hist),
                p_smo=jnp.where(active, ~do_plan, s.p_smo),
                prev_free=jnp.where(active, (~do_plan) & free_smo, s.prev_free),
                prev_ratio_ok=jnp.where(active, ratio_ok, s.prev_ratio_ok),
                n_planning=s.n_planning + (do_plan & active).astype(jnp.int32),
                act=act_new, n_unshrink=n_unshrink, pend=s.pend | newly,
                b=s.b)
        if not collect:
            return new_s, (conj_new if conjugate else None), None
        # ---- flight recorder (O(B) only; see repro.telemetry.ring) ---------
        with jax.named_scope("telemetry_ring"):
            if shrinking:
                n_act = jnp.sum(act_new, axis=1).astype(jnp.int32)
            else:
                n_act = jnp.full((B,), n, jnp.int32)
            # conjugate reuses the planning channels (the modes are mutually
            # exclusive): plan_event/n_planning count accepted conjugate
            # steps and ratio samples mu1/mu* for accepted steps.
            ratio_v = (ratio if (planning or conjugate)
                       else jnp.zeros_like(mu_smo))
            ring = ring_update(
                ring, telemetry, t=s.t, active=active,
                newly_done=newly, gap=gap, n_active=n_act,
                n_unshrink=n_unshrink, plan_event=do_plan & active,
                ratio=ratio_v)
        return new_s, (conj_new if conjugate else None), ring

    # ---- init ---------------------------------------------------------------
    # A cold start's gradient is P itself, exact, so its gap decides at
    # once; a warm start's G0 may have drifted, so its gap is checked.
    cold = alpha0 is None
    if cold:
        # grad f(0) = P; alpha = 0 must be feasible (classification, SVR —
        # NOT one-class, whose drivers always pass (alpha0, G0))
        alpha0 = jnp.zeros_like(P)
        G0 = P
    else:
        alpha0 = jnp.asarray(alpha0, dtype)
        G0 = jnp.asarray(G0, dtype)
    up0 = alpha0 < U
    dn0 = alpha0 > L
    v_up = jnp.where(up0, G0, -jnp.inf)
    i0 = jax.lax.argmax(v_up, 1, jnp.int32)
    g_i0 = _take_lane(v_up, i0)
    g_dn0 = jnp.min(jnp.where(dn0, G0, jnp.inf), axis=1)
    gap0 = qp_mod.finite_gap(g_i0 - g_dn0)
    zB = jnp.zeros((B,), jnp.int32)
    fB = jnp.zeros((B,), bool)
    act0 = jnp.ones((B, n) if shrinking else (B, 1), bool)
    s0 = _BatchState(alpha=alpha0, G=G0, i=i0, g_i=g_i0, gap=gap0,
                     t=jnp.asarray(0, jnp.int32), iters=zB,
                     done=(gap0 <= eps) if cold else fB, pi=zB, pj=zB,
                     qi=zB, qj=zB, n_hist=zB, p_smo=~fB, prev_free=fB,
                     prev_ratio_ok=~fB, n_planning=zB,
                     act=act0, n_unshrink=zB,
                     pend=fB if cold else (gap0 <= eps),
                     b=qp_mod.safe_bias(g_i0, g_dn0))

    def verify(carry):
        """Exit check of the pending lanes: done where the gap holds on
        exact rows, else reopened from the corrected gradient."""
        s, conj, ring = carry
        with jax.named_scope("fused_verify"):
            G, gap, b, ok = _exit_check(src, P, L, U, s.alpha, s.G, eps)
            due, reopen = s.pend, s.pend & ~ok
            v_up = jnp.where(s.alpha < U, G, -jnp.inf)
            i = jax.lax.argmax(v_up, 1, jnp.int32)
            s = s._replace(
                G=jnp.where(due[:, None], G, s.G),
                gap=jnp.where(due, gap, s.gap), b=jnp.where(due, b, s.b),
                done=s.done | (due & ok), pend=fB,
                i=jnp.where(reopen, i, s.i),
                g_i=jnp.where(reopen, _take_lane(v_up, i), s.g_i),
                n_unshrink=s.n_unshrink + reopen.astype(jnp.int32))
            if conjugate:
                conj = conj._replace(ok=conj.ok & ~reopen)
        return s, conj, ring

    def run(carry):
        """Trips until every lane is pending or done, then the check of
        the pending lanes (made whether or not one is: behind a
        ``lax.cond`` the TPU compiler takes alpha out of fast memory for
        the trips)."""
        return verify(jax.lax.while_loop(
            lambda c: (jnp.any(~c[0].done & ~c[0].pend)
                       & (c[0].t < cfg.max_iter)), body, carry))

    conj0 = ring0 = None
    if conjugate:
        conj0 = _ConjState(u=jnp.zeros((B, n), dtype),
                           ok=jnp.zeros((B,), bool))
    if collect:
        ring0 = ring_init(telemetry, B, dtype)
    # The rounds follow one another at the top of the program, never
    # inside a loop: a trip loop and a check inside one outer loop would
    # cost the trips the compiler's fast-memory placement of their carry.
    # Lanes that reopen go round again; a round with no live lane costs
    # its check alone.
    carry = (s0, conj0, ring0)
    for _ in range(VERIFY_ROUNDS):
        carry = run(carry)
    s, _, ring = carry

    up = s.alpha < U
    dn = s.alpha > L
    g_up = jnp.max(jnp.where(up, s.G, -jnp.inf), axis=1)
    g_dn = jnp.min(jnp.where(dn, s.G, jnp.inf), axis=1)
    res = FusedResult(
        alpha=s.alpha,
        b=jnp.where(s.done, s.b, qp_mod.safe_bias(g_up, g_dn)), G=s.G,
        iterations=s.iters,
        objective=0.5 * (jnp.sum(P * s.alpha, axis=1)
                         + jnp.sum(s.G * s.alpha, axis=1)),
        kkt_gap=s.gap, converged=s.done, n_planning=s.n_planning,
        n_unshrink=s.n_unshrink)
    return (res, ring) if collect else res


def solve_fused_batched(X, Y, C, gamma, cfg: SolverConfig = SolverConfig(),
                        *, impl: str = "auto", block_l: int = 1024,
                        alpha0=None, G0=None, gram=None,
                        gram_idx=None, shrinking: bool = False,
                        telemetry: RingConfig | None = None):
    """Solve a batch of B RBF *classification* QPs over shared ``X`` in ONE
    while_loop — the ``p = y`` instance of :func:`solve_fused_batched_qp`.

    ``Y`` is (B, l) signed label vectors; ``gamma`` is a scalar or (B,);
    ``C`` is a scalar, (B,) per-lane budgets, or (B, l) per-sample budgets
    (class-weighted SVC) — all traced, so heterogeneous batches share one
    compilation.  See :func:`solve_fused_batched_qp` for warm starts, the
    Gram-bank row source, lane freezing and the result layout.
    """
    Y = jnp.asarray(Y)
    dtype = Y.dtype
    B = Y.shape[0]
    C = jnp.asarray(C, dtype)
    if C.ndim < 2:
        C = jnp.broadcast_to(C, (B,))[:, None]
    YC = Y * C
    return solve_fused_batched_qp(
        X, Y, jnp.minimum(0.0, YC), jnp.maximum(0.0, YC), gamma, cfg,
        impl=impl, block_l=block_l, alpha0=alpha0, G0=G0, gram=gram,
        gram_idx=gram_idx, doubled=False, shrinking=shrinking,
        telemetry=telemetry)


# ---------------------------------------------------------------------------
# Chunked host driver: hard row compaction + lane compaction
# ---------------------------------------------------------------------------


def _pow2(n: int) -> int:
    """Smallest power of two >= n (bucketing keeps compile count log)."""
    b = 1
    while b < n:
        b *= 2
    return b


def _merge_chunk_ring(rc: RingConfig, ring, live, it_off, un_off, tel):
    """Fold one chunk's ring into the run-global host accumulators.

    Chunk rings stamp chunk-local iteration counters and chunk-local
    unshrink counts; ``it_off``/``un_off`` (per live lane, *before* this
    chunk was accumulated) rebase them to run-global values.  Slot
    assignment repeats the device-tier oldest-wins rule, so a chunked
    run's per-lane sample stream matches what one long unchunked ring
    would have kept.
    """
    m_live = len(live)
    r = {k: np.asarray(getattr(ring, k))[:m_live] for k in (
        "t", "gap", "n_active", "n_unshrink", "n_samples",
        "ratio", "ratio_t", "n_ratio")}
    tel_t, tel_gap, tel_act, tel_un, tel_ns, tel_r, tel_rt, tel_nr = tel
    for k, lane in enumerate(live):
        ns = int(min(r["n_samples"][k], rc.cap))
        if ns:
            # duplicate trailing slots resolve to the last (newest) write
            slots = np.minimum(tel_ns[lane] + np.arange(ns), rc.cap - 1)
            tel_t[lane, slots] = r["t"][k, :ns] + it_off[k]
            tel_gap[lane, slots] = r["gap"][k, :ns]
            tel_act[lane, slots] = r["n_active"][k, :ns]
            tel_un[lane, slots] = r["n_unshrink"][k, :ns] + un_off[k]
            tel_ns[lane] += int(r["n_samples"][k])
        nr = int(min(r["n_ratio"][k], rc.ratio_cap))
        if nr:
            slots = np.minimum(tel_nr[lane] + np.arange(nr),
                               rc.ratio_cap - 1)
            tel_r[lane, slots] = r["ratio"][k, :nr]
            tel_rt[lane, slots] = r["ratio_t"][k, :nr] + it_off[k]
            tel_nr[lane] += int(r["n_ratio"][k])


def solve_fused_chunked_qp(X, P, L, U, gamma,
                           cfg: SolverConfig = SolverConfig(), *,
                           impl: str = "auto", block_l: int = 1024,
                           chunk: int = 96, shrinking: bool = False,
                           doubled: bool = False, alpha0=None, G0=None,
                           gram=None, gram_idx=None, mesh=None,
                           devices=None, diagnostics=None):
    """Host-chunked :func:`solve_fused_batched_qp` with HARD compaction.

    The in-loop shrinking of the batched engine is *soft* — masked rows
    still ride through the kernels, so it saves selection work but not
    FLOPs (JAX while_loop shapes are static).  This driver runs the
    engine in chunks of ``chunk`` iterations and, between chunks,
    physically compacts BOTH axes on the host:

    * **lanes** — converged lanes are dropped from the batch (after the
      full KKT check below), so the kernels launch over the live lanes
      only;
    * **rows** — with ``shrinking=True`` the LIBSVM shrink rule
      (:func:`repro.core.qp.shrink_mask`, union over live lanes, doubled
      halves folded onto the base axis) gathers the surviving base rows
      into a dense prefix: the next chunk's kernels run at the shrunken
      width.  Both axes are power-of-two bucketed to keep the compile
      count logarithmic.

    Row compaction makes the per-chunk state G *stale on dropped
    coordinates* (updates only touch kept rows; the kept-coordinate G
    stays exact because the kept set only shrinks between unshrink
    events).  Convergence is therefore never declared from the shrunken
    problem alone: a lane whose chunk converges while rows are dropped
    gets the LIBSVM unshrink treatment — its full gradient is
    reconstructed (``G = P - Q alpha`` via
    :meth:`~repro.kernels.row_source.RowSource.matvec`) and the full-set
    KKT gap is checked on the host.  Pass -> the lane retires; fail ->
    ``n_unshrink`` increments, every live lane's gradient is
    reconstructed and the row set resets to full for the next chunk.

    Arguments mirror :func:`solve_fused_batched_qp` (including the
    Gram-bank row source, which is sliced to the kept rows per chunk);
    ``chunk`` is the iteration budget per sub-solve.  ``mesh``/``devices``
    lane-shard every chunk over a device mesh
    (:func:`repro.core.sharded_lanes.solve_fused_sharded_qp` becomes the
    chunk engine — lane compaction happens on the host between chunks, so
    sharding and compaction stack).  Returns a B-flat
    :class:`FusedResult` whose ``iterations``/``n_planning``/
    ``n_unshrink`` accumulate across chunks and whose ``G`` is exact on
    every coordinate for every lane.

    Every round runs under a ``chunk_solve`` span
    (:mod:`repro.telemetry.spans`: the round, live lanes and rows as
    attributes), from the chunk's dispatch until its state is back on
    the host.  ``diagnostics`` (a :class:`repro.telemetry.Diagnostics`)
    turns on the flight recorder at this host level: each round's span
    also lands in the sink as a ``chunk_solve`` phase event with its wall
    seconds / live lane and row counts, a
    :class:`repro.runtime.fault.StepMonitor` EWMA
    over chunk wall-times emits ``straggler_warning`` events when a
    chunk breaches the deadline factor, and — when
    ``diagnostics.ring_config`` is set — the per-chunk device rings are
    rebased to run-global iteration stamps and merged per original lane,
    with the return value becoming ``(FusedResult, TelemetryRing)``.
    """
    assert (alpha0 is None) == (G0 is None), \
        "warm starts need the (alpha0, G0) pair"
    assert (gram is None) == (gram_idx is None), \
        "the Gram bank needs the (gram, gram_idx) pair"
    if mesh is not None or devices is not None:
        # local import: sharded_lanes imports this module at top level
        from repro.core.sharded_lanes import (resolve_lane_mesh,
                                              solve_fused_sharded_qp)
        mesh = resolve_lane_mesh(mesh, devices)
        chunk_solver = partial(solve_fused_sharded_qp, mesh=mesh)
    else:
        chunk_solver = solve_fused_batched_qp
    bank = gram is not None
    X = jnp.asarray(X)
    dtype = X.dtype
    P_np = np.asarray(P, np.float64)
    B, n = P_np.shape
    lb = X.shape[0]
    assert n == (2 * lb if doubled else lb)
    L_np = np.broadcast_to(np.asarray(L, np.float64), (B, n))
    U_np = np.broadcast_to(np.asarray(U, np.float64), (B, n))
    gam_np = np.broadcast_to(
        np.asarray(gamma, np.float64).reshape(-1), (B,))
    X_np = np.asarray(X, np.float64)
    gram_np = None if not bank else np.asarray(gram, np.float64)
    gidx_np = None if not bank else np.asarray(gram_idx, np.int32)
    eps = float(cfg.eps)
    ccfg = dataclasses.replace(cfg, max_iter=min(chunk, cfg.max_iter))

    if alpha0 is None:
        alpha = np.zeros((B, n))
        G = P_np.copy()
    else:
        alpha = np.asarray(alpha0, np.float64).copy()
        G = np.asarray(G0, np.float64).copy()

    out_b = np.zeros(B)
    out_gap = np.zeros(B)
    out_obj = np.zeros(B)
    out_conv = np.zeros(B, bool)
    out_iter = np.zeros(B, np.int64)
    out_plan = np.zeros(B, np.int64)
    out_unshrink = np.zeros(B, np.int64)

    live = np.arange(B)
    keep = np.arange(lb)

    # ---- flight recorder (host tier): a span per round, always on; the
    # ``phase`` events, straggler monitor and rings need ``diagnostics`` ----
    sink = rc = monitor = tel = None
    if diagnostics is not None:
        from repro.runtime.fault import StepMonitor
        sink, rc = diagnostics.sink, diagnostics.ring_config
        monitor = StepMonitor(warmup_steps=1)
    if rc is not None:
        tel = (np.zeros((B, rc.cap), np.int32), np.zeros((B, rc.cap)),
               np.zeros((B, rc.cap), np.int32),
               np.zeros((B, rc.cap), np.int32), np.zeros(B, np.int32),
               np.zeros((B, rc.ratio_cap)),
               np.zeros((B, rc.ratio_cap), np.int32),
               np.zeros(B, np.int32))

    def reconstruct(idx):
        """Exact full-width G = P - Q alpha for lanes ``idx``."""
        if bank:
            src = row_source.bank_source(gram, jnp.asarray(gidx_np[idx]),
                                         dup=doubled)
        else:
            src = row_source.rbf_source(X, jnp.asarray(gam_np[idx], dtype),
                                        len(idx), dup=doubled)
        mv = src.matvec(jnp.asarray(alpha[idx], dtype))
        G[idx] = P_np[idx] - np.asarray(mv, np.float64)

    def finalize(idx):
        """Full-set (b, kkt_gap, objective) from exact host state."""
        a, g = alpha[idx], G[idx]
        up = a < U_np[idx]
        dn = a > L_np[idx]
        g_up = np.where(up, g, -np.inf).max(axis=1)
        g_dn = np.where(dn, g, np.inf).min(axis=1)
        gap = g_up - g_dn
        gap = np.where(np.isfinite(gap), gap, 0.0)
        fu, fd = np.isfinite(g_up), np.isfinite(g_dn)
        gu = np.where(fu, g_up, np.where(fd, g_dn, 0.0))
        gd = np.where(fd, g_dn, np.where(fu, g_up, 0.0))
        b = np.where(fu | fd, 0.5 * (gu + gd), 0.0)
        obj = 0.5 * np.sum((P_np[idx] + g) * a, axis=1)
        return b, gap, obj

    max_rounds = 4 * max(1, -(-cfg.max_iter // max(1, chunk))) + 16
    for rnd in range(max_rounds):
        if len(live) == 0:
            break
        m, m_live = len(keep), len(live)
        bsz, rb = _pow2(m_live), _pow2(m)
        lanes = np.concatenate([live, np.repeat(live[:1], bsz - m_live)])
        padc = rb - m

        def gather(A):
            """Kept-coordinate lane state, padded to the row bucket with
            inert coords (L = U = 0: never selectable, G irrelevant)."""
            sub = A[np.ix_(lanes, keep)]
            z = np.zeros((bsz, padc))
            if doubled:
                sub2 = A[np.ix_(lanes, keep + lb)]
                return np.concatenate([sub, z, sub2, z], axis=1)
            return np.concatenate([sub, z], axis=1)

        X_sub = jnp.asarray(np.concatenate(
            [X_np[keep], np.zeros((padc, X_np.shape[1]))]), dtype)
        bank_kw = {}
        if bank:
            gsub = np.zeros(gram_np.shape[:1] + (rb, rb))
            gsub[:, :m, :m] = gram_np[:, keep[:, None], keep[None, :]]
            bank_kw = dict(gram=jnp.asarray(gsub, dtype),
                           gram_idx=jnp.asarray(gidx_np[lanes]))

        if rc is not None:
            bank_kw["telemetry"] = rc
        # the round's span ends once its state is back on the host
        with phase_scope("chunk_solve", sink, round=rnd, lanes=m_live,
                         rows=m) as sp:
            res = chunk_solver(
                X_sub, jnp.asarray(gather(P_np), dtype),
                jnp.asarray(gather(L_np), dtype),
                jnp.asarray(gather(U_np), dtype),
                jnp.asarray(gam_np[lanes], dtype), ccfg, impl=impl,
                block_l=block_l, alpha0=jnp.asarray(gather(alpha), dtype),
                G0=jnp.asarray(gather(G), dtype), doubled=doubled,
                shrinking=shrinking, **bank_kw)
            ring = None
            if rc is not None:
                res, ring = res
            ra = np.asarray(res.alpha, np.float64)[:m_live]
            rg = np.asarray(res.G, np.float64)[:m_live]
        # EWMA straggler deadline over chunk wall-times — the same monitor
        # the resilient LM step loop uses (runtime/fault.py)
        if monitor is not None and monitor.record(sp.seconds):
            diagnostics.event(
                "straggler_warning", round=rnd, seconds=sp.seconds,
                deadline=monitor.deadline, lanes=live.tolist(), rows=m)
        if ring is not None:
            _merge_chunk_ring(rc, ring, live, out_iter[live],
                              out_unshrink[live], tel)

        alpha[np.ix_(live, keep)] = ra[:, :m]
        G[np.ix_(live, keep)] = rg[:, :m]
        if doubled:
            alpha[np.ix_(live, keep + lb)] = ra[:, rb:rb + m]
            G[np.ix_(live, keep + lb)] = rg[:, rb:rb + m]
        out_iter[live] += np.asarray(res.iterations, np.int64)[:m_live]
        out_plan[live] += np.asarray(res.n_planning, np.int64)[:m_live]
        out_unshrink[live] += np.asarray(res.n_unshrink,
                                         np.int64)[:m_live]
        conv = np.asarray(res.converged)[:m_live]

        # ---- retire converged lanes (full KKT check when rows dropped) ----
        need_unshrink = False
        retired = np.zeros(m_live, bool)
        cand = live[conv]
        if len(cand):
            if m < lb:
                reconstruct(cand)
            b_c, gap_c, obj_c = finalize(cand)
            ok = gap_c <= eps
            good = cand[ok]
            out_b[good] = b_c[ok]
            out_gap[good] = gap_c[ok]
            out_obj[good] = obj_c[ok]
            out_conv[good] = True
            failed = cand[~ok]
            if len(failed):
                out_unshrink[failed] += 1
                need_unshrink = True
            retired[np.nonzero(conv)[0][ok]] = True

        # ---- retire exhausted lanes (budget spent, unconverged) -----------
        exh_pos = np.nonzero((~retired)
                             & (out_iter[live] >= cfg.max_iter))[0]
        if len(exh_pos):
            exh = live[exh_pos]
            if m < lb:
                reconstruct(exh)
            b_e, gap_e, obj_e = finalize(exh)
            out_b[exh] = b_e
            out_gap[exh] = gap_e
            out_obj[exh] = obj_e
            out_conv[exh] = gap_e <= eps
            retired[exh_pos] = True

        live = live[~retired]
        if len(live) == 0:
            break

        if need_unshrink:
            # stored G is stale on dropped coords for EVERY live lane
            if m < lb:
                reconstruct(live)
            keep = np.arange(lb)
        elif shrinking and m > 1:
            # monotone row shrink from the exact kept-coordinate state:
            # a base row survives if ANY live lane still needs it
            cols = (np.concatenate([keep, keep + lb]) if doubled else keep)
            a_k = alpha[np.ix_(live, cols)]
            g_k = G[np.ix_(live, cols)]
            L_k = L_np[np.ix_(live, cols)]
            U_k = U_np[np.ix_(live, cols)]
            up = a_k < U_k
            dn = a_k > L_k
            g_up = np.where(up, g_k, -np.inf).max(axis=1, keepdims=True)
            g_dn = np.where(dn, g_k, np.inf).min(axis=1, keepdims=True)
            act = ~((~dn & (g_k < g_dn)) | (~up & (g_k > g_up)))
            union = act.any(axis=0)
            if doubled:
                union = union[:m] | union[m:]
            if union.any() and not union.all():
                keep = keep[union]

    if len(live):
        # safety bound hit: finalize the stragglers from exact state
        if len(keep) < lb:
            reconstruct(live)
        b_l, gap_l, obj_l = finalize(live)
        out_b[live] = b_l
        out_gap[live] = gap_l
        out_obj[live] = obj_l
        out_conv[live] = gap_l <= eps

    result = FusedResult(
        alpha=jnp.asarray(alpha, dtype), b=jnp.asarray(out_b, dtype),
        G=jnp.asarray(G, dtype),
        iterations=jnp.asarray(out_iter, jnp.int32),
        objective=jnp.asarray(out_obj, dtype),
        kkt_gap=jnp.asarray(out_gap, dtype),
        converged=jnp.asarray(out_conv),
        n_planning=jnp.asarray(out_plan, jnp.int32),
        n_unshrink=jnp.asarray(out_unshrink, jnp.int32))
    if rc is None:
        return result
    tel_t, tel_gap, tel_act, tel_un, tel_ns, tel_r, tel_rt, tel_nr = tel
    ring_out = TelemetryRing(
        t=jnp.asarray(tel_t), gap=jnp.asarray(tel_gap, dtype),
        n_active=jnp.asarray(tel_act), n_unshrink=jnp.asarray(tel_un),
        n_samples=jnp.asarray(tel_ns),
        ratio=jnp.asarray(tel_r, dtype), ratio_t=jnp.asarray(tel_rt),
        n_ratio=jnp.asarray(tel_nr))
    return result, ring_out
