"""jit'd wrappers around the Pallas kernels: padding, tiling, dispatch.

``impl`` selects the backend:
  * "pallas"    — compiled Pallas (TPU target),
  * "interpret" — Pallas interpret mode (CPU correctness validation),
  * "jnp"       — pure-jnp fallback with identical semantics (XLA-fused;
                  the fast path on CPU and the numerical oracle in tests).
  * "auto"      — pallas on TPU, jnp elsewhere.

All wrappers pad the lane state's example dimension to the block
multiple with *inert* rows (L = U = 0 so they can never be selected; see
sharded.py for the same trick).  The shared X and its row norms are padded
to the block multiple and X's feature dimension to a lane multiple for the
MXU — by the fused engine, once per call before its while loop
(:func:`pad_source`), so the batched wrappers get X already padded and
pass it through; a caller that hands them raw X has it padded per call.
The block sizes come from the shapes (:func:`plan_tiles`): the caller's
``block_l`` is an upper bound that shrinks — and the lane batch splits
into blocks — until one grid step's working set fits the VMEM budget.

The batched wrappers dispatch over a row-source axis as well (see
:mod:`repro.kernels.row_source`): rows recomputed from shared X tiles
(plain or the doubled ε-SVR operator — lane state stacked as (H, B, lpad)
variable halves so the base row tile is computed once and read H times
in-kernel) or gathered from a shared base Gram bank.  Integer working-set
indices travel through dedicated int32 inputs, never through the data
dtype (a float32 round-trip is lossy beyond l = 2^24).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_ops
from repro.kernels.gram_block import gram_pallas
from repro.kernels.rbf_row_wss import (LANES, rbf_row_wss_batched_pallas,
                                       row_wss_batched_rows_pallas)
from repro.kernels.rbf_update_wss import (rbf_update_wss_batched_pallas,
                                          update_wss_batched_rows_pallas)
from repro.kernels.row_source import RowSource


def resolve_impl(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _pad_l(a, lpad, value=0.0):
    pad = lpad - a.shape[0]
    if pad == 0:
        return a
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=value)


def _pad_d(a, dpad):
    pad = dpad - a.shape[-1]
    if pad == 0:
        return a
    widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    return jnp.pad(a, widths)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _iscal(i_idx, n: int):
    """Pack integer per-lane indices into the int32 side channel (n, 1).

    Indices must NEVER round-trip through the data dtype: float32 has a
    24-bit significand, so ``jnp.asarray(i, jnp.float32)`` silently
    corrupts indices beyond 2^24 — a real bound for large-l training sets.
    """
    return jnp.asarray(i_idx, jnp.int32).reshape(n, 1)


# ---------------------------------------------------------------------------
# Tile planning: the VMEM footprint of one grid step, from shapes
# ---------------------------------------------------------------------------
#
# Each grid step holds its input and output blocks twice (Pallas double-
# buffers the HBM<->VMEM pipeline).  Calibrated against the v5e compiler:
# the scoped allocation it reports is that double-buffered block total
# (to within 0.5 MiB at B = 512..1024 for every variant) — the selection
# and update algebra streams through vector registers.  The estimate adds
# two (b, BL) tiles of slack, and the budget keeps a margin under the
# 16 MiB scoped-VMEM default of v5e's compiler.  tests/test_tpu_compile.py
# compiles every variant at its planned tiles.

VMEM_BUDGET = 12 * 2**20
_SUBLANES = 8                # lane batches pad to a sublane multiple


class Tiles(NamedTuple):
    block_b: int      # lanes per grid block (multiple of 8, divides bpad)
    block_l: int      # examples per grid block
    bpad: int         # padded lane count
    lpad: int         # padded example count


def vmem_bytes(bb: int, bl: int, *, H: int, stacks: int, rows: int,
               dpad: int, itemsize: int = 4) -> int:
    """Estimated VMEM bytes of one grid step of a batched pass.

    ``stacks`` counts the (H, bb, bl) lane-state tiles in and out (G,
    alpha, L, U, optional act, pass B's G_out); ``rows`` the (bb, bl)
    row tiles (gathered kernel rows, the Conjugate-SMO direction in and
    out); ``dpad`` is the padded feature width of the X tile and the
    query rows (0 for the Gram-bank passes).
    """
    blocks = (stacks * H * bb * bl + rows * bb * bl
              + bl * dpad + _SUBLANES * bl            # X tile, sqn row
              + 2 * bb * dpad + 6 * bb * LANES)       # query rows, scalars
    return itemsize * (2 * blocks + 2 * bb * bl)


def plan_tiles(B: int, l: int, block_l: int, **footprint) -> Tiles:
    """Tiles for a batched pass over ``B`` lanes and ``l`` examples.

    The l block starts at the caller's ``block_l`` and halves (down to
    128, the hardware lane width) until the step fits :data:`VMEM_BUDGET`
    with every lane in one block; if even that overflows, the lanes split
    into the fewest blocks that fit.  ``footprint`` are the per-pass
    counts of :func:`vmem_bytes`.  A ``block_l`` below 128 (interpret-mode
    tests) is taken as given.
    """
    bpad = _round_up(max(B, 1), _SUBLANES)
    bl = block_l
    while bl > 128 and vmem_bytes(bpad, bl, **footprint) > VMEM_BUDGET:
        bl = max(128, bl // 2)
    n_c = 1
    bb = bpad
    while bb > _SUBLANES and vmem_bytes(bb, bl, **footprint) > VMEM_BUDGET:
        n_c += 1
        bb = _round_up(-(-bpad // n_c), _SUBLANES)
    return Tiles(bb, bl, bb * n_c, _round_up(l, bl))


def _dpad(d: int) -> int:
    return _round_up(d, 128)


def pass_a_tiles(B, l, d, block_l, *, H=1, masked=False, rows=False):
    """:func:`plan_tiles` for pass A (``rows``: the Gram-bank variant)."""
    return plan_tiles(B, l, block_l, H=H, stacks=4 + masked,
                      rows=int(rows), dpad=0 if rows else _dpad(d))


def pass_b_tiles(B, l, d, block_l, *, H=1, masked=False, conj=False,
                 rows=False):
    """:func:`plan_tiles` for pass B (``rows``: the Gram-bank variant)."""
    return plan_tiles(B, l, block_l, H=H, stacks=5 + masked,
                      rows=2 * conj + 2 * rows,
                      dpad=0 if rows else _dpad(d))


# ---------------------------------------------------------------------------
# Single-lane wrappers (the B = 1 instance of the batched kernels)
# ---------------------------------------------------------------------------


def _row1(a, lpad, value=0.0):
    """(l,) vector -> (1, 1, lpad) single-lane state stack."""
    return _pad_l(a, lpad, value).reshape(1, 1, lpad)


def rbf_row_wss(X, sqn, G, alpha, L, U, xq, a_i, L_i, U_i, g_i, i_idx,
                use_exact, gamma, *, impl: str = "auto",
                block_l: int = 1024):
    """Pass A: returns (k_i (l,), j (int32), gain_j)."""
    impl = resolve_impl(impl)
    l, d = X.shape
    if impl == "jnp":
        return ref_ops.rbf_row_wss(X, sqn, G, alpha, L, U, xq, a_i, L_i,
                                   U_i, g_i, i_idx, use_exact, gamma)
    lpad, dpad = _round_up(l, block_l), _dpad(d)
    dtype = X.dtype
    scal = jnp.stack([jnp.dot(xq, xq, precision=ref_ops.HIGHEST),
                      jnp.asarray(gamma, dtype), a_i, L_i,
                      U_i, g_i, use_exact.astype(dtype)]).reshape(1, 7)
    j, gain, k = rbf_row_wss_batched_pallas(
        _pad_d(_pad_l(X, lpad), dpad), _pad_l(sqn, lpad), _row1(G, lpad),
        _row1(alpha, lpad), _row1(L, lpad), _row1(U, lpad),
        _pad_d(xq, dpad).reshape(1, dpad), scal.astype(dtype),
        _iscal(i_idx, 1), block_l=block_l,
        interpret=(impl == "interpret"), base_l=l, emit_k=True)
    return k[0, :l], j[0], gain[0]


def rbf_update_wss(X, sqn, G, k_i, alpha_new, L, U, xq_j, mu, gamma,
                   *, impl: str = "auto", block_l: int = 1024):
    """Pass B: returns (G_new (l,), i_next, g_i_next, g_dn)."""
    impl = resolve_impl(impl)
    l, d = X.shape
    if impl == "jnp":
        return ref_ops.rbf_update_wss(X, sqn, G, k_i, xq_j, mu, alpha_new,
                                      L, U, gamma)
    lpad, dpad = _round_up(l, block_l), _dpad(d)
    dtype = X.dtype
    xq = jnp.broadcast_to(_pad_d(xq_j, dpad), (2, dpad))   # (i, j) rows
    scal = jnp.stack([jnp.zeros((), dtype),
                      jnp.dot(xq_j, xq_j, precision=ref_ops.HIGHEST),
                      jnp.asarray(mu, dtype),
                      jnp.asarray(gamma, dtype)]).reshape(1, 4)
    G_new, i_next, g_i_next, g_dn = rbf_update_wss_batched_pallas(
        _pad_d(_pad_l(X, lpad), dpad), _pad_l(sqn, lpad), _row1(G, lpad),
        _row1(alpha_new, lpad), _row1(L, lpad), _row1(U, lpad), xq,
        scal.astype(dtype), ki=_pad_l(k_i, lpad).reshape(1, lpad),
        block_l=block_l, interpret=(impl == "interpret"), base_l=l)
    return G_new[0, 0, :l], i_next[0], g_i_next[0], g_dn[0]


# ---------------------------------------------------------------------------
# Lane-batched wrappers (one lane = one QP; X is shared across lanes)
# ---------------------------------------------------------------------------
#
# The example dimension is padded exactly as above; the lane dimension is
# padded to the lane-block multiple with *inert* lanes: L = U = alpha = 0
# rows can never be selected in pass A, and mu = 0 makes pass B a no-op, so
# padded lanes never influence any lane's result.
#
# The doubled ε-SVR operator (dup=True, lane state n = 2l) is carried as an
# (2, bpad, lpad) half stack: the kernels compute the base row tile once
# per grid step and apply it to both halves via index arithmetic, so the
# matmul width, the VMEM X tile, and the padded HBM traffic all stay those
# of the base problem.


def _pad_bl(a, bpad, lpad, value=0.0):
    """Pad a (B, l) per-lane state array on both axes."""
    return jnp.pad(a, ((0, bpad - a.shape[0]), (0, lpad - a.shape[1])),
                   constant_values=value)


def _pad_b(a, bpad, value=0.0):
    widths = [(0, bpad - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=value)


def _stack_halves(a, H: int, bpad: int, lpad: int, value=0.0):
    """(B, H*l) lane state -> (H, bpad, lpad) inert-padded half stack."""
    l = a.shape[1] // H
    return jnp.stack([_pad_bl(a[:, h * l:(h + 1) * l], bpad, lpad, value)
                      for h in range(H)], axis=0)


def _unstack_halves(a, B: int, l: int):
    """(H, bpad, lpad) kernel output -> (B, H*l) lane state."""
    return jnp.concatenate([a[h, :B, :l] for h in range(a.shape[0])],
                           axis=1)


def _state_stacks(t: Tiles, H: int, act, *state):
    """Half-stack the (B, n) lane-state leaves (and the optional act
    mask, as 1.0/0.0 in the data dtype) at the planned padding."""
    with jax.named_scope("lane_state"):
        stacks = [_stack_halves(a, H, t.bpad, t.lpad) for a in state]
        act_st = (None if act is None else
                  _stack_halves(act.astype(state[0].dtype), H, t.bpad,
                                t.lpad))
    return stacks, act_st


def _pad_x(X, sqn, lpad: int):
    """The shared X and its row norms at ``lpad`` rows, X lane-padded.

    An X that already has at least ``lpad`` rows and a lane-multiple width
    (:func:`pad_source`) passes through untouched: the kernels' grid reads
    ``lpad`` rows of it.  The norms are cut to exactly ``lpad``.
    """
    with jax.named_scope("x_pad"):
        if X.shape[0] < lpad:
            X = _pad_l(X, lpad)
        X = _pad_d(X, _dpad(X.shape[1]))
        sqn = sqn[:lpad] if sqn.shape[0] > lpad else _pad_l(sqn, lpad)
        return X, sqn


def pad_source(src: RowSource, B: int, block_l: int, *,
               masked: bool = False, conj: bool = False) -> RowSource:
    """The rbf row source with X and its row norms padded once.

    Rows go to the larger ``lpad`` that pass A and pass B plan for ``B``
    lanes (``masked``: shrinking's active mask, ``conj``: Conjugate-SMO),
    columns to a lane multiple; the true example count rides along as
    ``src.l``.  Called before the engine's while loop, it leaves the loop
    body a loop-invariant padded X, so neither batched pass pads it again.
    """
    l, d = src.X.shape
    H = 2 if src.dup else 1
    lpad = max(pass_a_tiles(B, l, d, block_l, H=H, masked=masked).lpad,
               pass_b_tiles(B, l, d, block_l, H=H, masked=masked,
                            conj=conj).lpad)
    X, sqn = _pad_x(src.X, src.sqn, lpad)
    return dataclasses.replace(src, X=X, sqn=sqn, l=l)


def rbf_row_wss_batched(X, sqn, G, alpha, L, U, XQ, sqq, a_i, L_i, U_i,
                        g_i, i_idx, use_exact, gammas, *, impl: str = "auto",
                        block_l: int = 1024, dup: bool = False, act=None):
    """Batched pass A: per-lane WSS2 selection, returns (j (B,), gain (B,)).

    ``X``/``sqn`` are shared, raw or already padded (:func:`pad_source`;
    the true example count is read off the (B, n) lane state);
    ``G``/``alpha``/``L``/``U`` are (B, n); ``XQ`` is the (B, d) gathered
    *base* query rows; the rest are (B,) per-lane scalars.  ``dup=True``
    runs the doubled ε-SVR operator (n = 2l over base ``X``/``sqn``): the
    jnp oracle computes the base (B, l) row and tiles it; the Pallas path
    stacks the lane state into (2, B, lpad) halves and the kernel reads
    the base row tile twice — the matmul never widens past l.  ``act`` is an optional (B, n) active-set mask (soft
    shrinking: restricts the j-scan only).
    """
    impl = resolve_impl(impl)
    if impl == "jnp":
        return ref_ops.rbf_row_wss_batched(X, sqn, G, alpha, L, U, XQ, sqq,
                                           a_i, L_i, U_i, g_i, i_idx,
                                           use_exact, gammas, dup=dup,
                                           act=act)
    H = 2 if dup else 1
    B, l, d = G.shape[0], G.shape[1] // H, X.shape[1]
    t = pass_a_tiles(B, l, d, block_l, H=H, masked=act is not None)
    dtype = X.dtype
    scal = jnp.stack([sqq, jnp.broadcast_to(gammas, (B,)),
                      a_i, L_i, U_i, g_i,
                      use_exact.astype(dtype)], axis=1).astype(dtype)
    stacks, act_st = _state_stacks(t, H, act, G, alpha, L, U)
    Xp, sqnp = _pad_x(X, sqn, t.lpad)
    j, gain = rbf_row_wss_batched_pallas(
        Xp, sqnp, *stacks,
        _pad_b(_pad_d(XQ, _dpad(d)), t.bpad), _pad_b(scal, t.bpad),
        _pad_b(_iscal(i_idx, B), t.bpad), act_st, block_l=t.block_l,
        block_b=t.block_b, interpret=(impl == "interpret"), base_l=l)
    return j[:B], gain[:B]


def _stack_queries(XQi, XQj, t: Tiles):
    """(B, d) i and j query rows -> the (2 bpad, d) pass B layout: per
    lane block, its i rows then its j rows."""
    n_c = t.bpad // t.block_b
    qi, qj = (_pad_b(q, t.bpad).reshape(n_c, t.block_b, -1)
              for q in (XQi, XQj))
    return jnp.concatenate([qi, qj], axis=1).reshape(2 * t.bpad, -1)


def _pass_b_result(out, B: int, l: int, dup: bool, conj: bool):
    """Unpad a pass B launch: (G_new (B, n), i_next, g_i_next, g_dn), plus
    the full-width direction row ``r`` under Conjugate-SMO."""
    G_new, i_next, g_i_next, g_dn = out[:4]
    with jax.named_scope("lane_state"):
        res = (_unstack_halves(G_new, B, l), i_next[:B], g_i_next[:B],
               g_dn[:B])
        if conj:
            r = out[4][:B, :l]
            res += (ref_ops.tile_rows(r) if dup else r,)
    return res


def rbf_update_wss_batched(X, sqn, G, alpha_new, L, U, XQi, sqqi, XQj, sqqj,
                           mu, gammas, *, impl: str = "auto",
                           block_l: int = 1024, dup: bool = False, act=None,
                           dirv=None, mu2=None):
    """Batched pass B: returns (G_new (B, n), i_next, g_i_next, g_dn).

    Recomputes both *base* rows k_i/k_j against the shared X (no HBM
    round-trip for either); a lane with ``mu == 0`` leaves G bitwise
    unchanged.  ``X``/``sqn`` and ``dup`` (the doubled ε-SVR operator) are
    as in :func:`rbf_row_wss_batched` (in-kernel half reads, l-wide
    matmuls).
    ``act`` optionally restricts the next-i scan and gap endpoints (the
    gradient update is never masked).  ``dirv``/``mu2`` engage the
    Conjugate-SMO second-direction axpy and grow the return by
    ``r = k_i - k_j`` (at full lane-state width) — see
    :func:`repro.kernels.ref.update_wss_batched_from_rows`.
    """
    impl = resolve_impl(impl)
    if impl == "jnp":
        return ref_ops.rbf_update_wss_batched(X, sqn, G, alpha_new, L, U,
                                              XQi, sqqi, XQj, sqqj, mu,
                                              gammas, dup=dup, act=act,
                                              dirv=dirv, mu2=mu2)
    H = 2 if dup else 1
    B, l, d = G.shape[0], G.shape[1] // H, X.shape[1]
    conj = dirv is not None
    t = pass_b_tiles(B, l, d, block_l, H=H, masked=act is not None,
                     conj=conj)
    dtype = X.dtype
    cols = [sqqi, sqqj, jnp.broadcast_to(mu, (B,)),
            jnp.broadcast_to(gammas, (B,))]
    # the doubled operator's direction rows are half-symmetric (tiled base
    # rows), so the kernels carry the base half only
    dirv_row = None
    if conj:
        cols.append(jnp.broadcast_to(mu2, (B,)))
        dirv_row = _pad_bl(dirv[:, :l].astype(dtype), t.bpad, t.lpad)
    scal = jnp.stack(cols, axis=1).astype(dtype)
    stacks, act_st = _state_stacks(t, H, act, G, alpha_new, L, U)
    Xp, sqnp = _pad_x(X, sqn, t.lpad)
    out = rbf_update_wss_batched_pallas(
        Xp, sqnp, *stacks,
        _stack_queries(_pad_d(XQi, _dpad(d)), _pad_d(XQj, _dpad(d)), t),
        _pad_b(scal, t.bpad), act_st, dirv_row, block_l=t.block_l, block_b=t.block_b,
        interpret=(impl == "interpret"), base_l=l)
    return _pass_b_result(out, B, l, dup, conj)


def row_wss_batched_rows(KR, G, alpha, L, U, a_i, L_i, U_i, g_i, i_idx,
                         use_exact, *, impl: str = "auto",
                         block_l: int = 1024, dup: bool = False, act=None):
    """Batched pass A from pre-gathered *base* rows ``KR`` (B, l) — the
    Gram-bank row source.  Same contract as :func:`rbf_row_wss_batched`
    (including the optional ``act`` mask); the jnp path tiles the rows for
    the doubled operator, the Pallas path reads the row tile once per half
    in-kernel."""
    impl = resolve_impl(impl)
    if impl == "jnp":
        k = ref_ops.tile_rows(KR) if dup else KR
        return ref_ops.row_wss_batched_from_k(k, G, alpha, L, U, a_i, L_i,
                                              U_i, g_i, i_idx, use_exact,
                                              act=act)
    B, l = KR.shape
    H = 2 if dup else 1
    t = pass_a_tiles(B, l, 0, block_l, H=H, masked=act is not None,
                     rows=True)
    dtype = KR.dtype
    scal = jnp.stack([a_i, L_i, U_i, g_i,
                      use_exact.astype(dtype)], axis=1).astype(dtype)
    stacks, act_st = _state_stacks(t, H, act, G, alpha, L, U)
    j, gain = row_wss_batched_rows_pallas(
        _pad_bl(KR, t.bpad, t.lpad), *stacks, _pad_b(scal, t.bpad),
        _pad_b(_iscal(i_idx, B), t.bpad), act_st, block_l=t.block_l,
        block_b=t.block_b, interpret=(impl == "interpret"), base_l=l)
    return j[:B], gain[:B]


def update_wss_batched_rows(KRi, KRj, G, alpha_new, L, U, mu, *,
                            impl: str = "auto", block_l: int = 1024,
                            dup: bool = False, act=None, dirv=None,
                            mu2=None):
    """Batched pass B from pre-gathered *base* rows — the Gram-bank row
    source.  Same contract as :func:`rbf_update_wss_batched` (including
    the ``dirv``/``mu2`` Conjugate-SMO extension)."""
    impl = resolve_impl(impl)
    if impl == "jnp":
        ki = ref_ops.tile_rows(KRi) if dup else KRi
        kj = ref_ops.tile_rows(KRj) if dup else KRj
        return ref_ops.update_wss_batched_from_rows(G, ki, kj, mu,
                                                    alpha_new, L, U,
                                                    act=act, dirv=dirv,
                                                    mu2=mu2)
    B, l = KRi.shape
    H = 2 if dup else 1
    conj = dirv is not None
    t = pass_b_tiles(B, l, 0, block_l, H=H, masked=act is not None,
                     conj=conj, rows=True)
    dtype = KRi.dtype
    cols = [jnp.broadcast_to(mu, (B,))]
    dirv_row = None
    if conj:
        cols.append(jnp.broadcast_to(mu2, (B,)))
        dirv_row = _pad_bl(dirv[:, :l].astype(dtype), t.bpad, t.lpad)
    scal = jnp.stack(cols, axis=1).astype(dtype)
    stacks, act_st = _state_stacks(t, H, act, G, alpha_new, L, U)
    out = update_wss_batched_rows_pallas(
        _pad_bl(KRi, t.bpad, t.lpad), _pad_bl(KRj, t.bpad, t.lpad), *stacks,
        _pad_b(scal, t.bpad), act_st, dirv_row, block_l=t.block_l,
        block_b=t.block_b, interpret=(impl == "interpret"), base_l=l)
    return _pass_b_result(out, B, l, dup, conj)


# ---------------------------------------------------------------------------
# RowSource dispatchers: one call site per pass, any supplier x backend
# ---------------------------------------------------------------------------


def source_row_wss(src: RowSource, G, alpha, L, U, i_idx, a_i, L_i, U_i,
                   g_i, use_exact, *, impl: str = "auto",
                   block_l: int = 1024, act=None):
    """Batched pass A against any :class:`~repro.kernels.row_source.RowSource`.

    ``act`` is an optional (B, n) active-set mask (soft shrinking).
    Returns (j (B,), gain (B,)) — the per-lane WSS2 selection.
    """
    if src.is_bank:
        KR = src.query(i_idx).astype(G.dtype)
        return row_wss_batched_rows(KR, G, alpha, L, U, a_i, L_i, U_i, g_i,
                                    i_idx, use_exact, impl=impl,
                                    block_l=block_l, dup=src.dup, act=act)
    XQ, sqq = src.query(i_idx)
    return rbf_row_wss_batched(src.X, src.sqn, G, alpha, L, U, XQ, sqq,
                               a_i, L_i, U_i, g_i, i_idx, use_exact,
                               src.gammas, impl=impl, block_l=block_l,
                               dup=src.dup, act=act)


def source_update_wss(src: RowSource, G, alpha_new, L, U, i_idx, j_idx, mu,
                      *, impl: str = "auto", block_l: int = 1024, act=None,
                      dirv=None, mu2=None):
    """Batched pass B against any :class:`~repro.kernels.row_source.RowSource`.

    ``act`` is an optional (B, n) active-set mask (soft shrinking; the
    gradient update itself is never masked).
    Returns (G_new (B, n), i_next (B,), g_i_next (B,), g_dn (B,)).

    ``dirv``/``mu2`` (Conjugate-SMO): apply the extra per-lane axpy
    ``- mu2 dirv`` to the gradient in the same pass and grow the return by
    ``r = k_i - k_j`` (B, n) — the direction Q-product the caller carries
    into the next iteration.  Left at ``None`` the contract (and the
    traced jaxpr) is exactly the plain 4-tuple.
    """
    B = G.shape[0]
    stacked = jnp.concatenate([i_idx, j_idx])
    if src.is_bank:
        rows = src.query(stacked).astype(G.dtype)   # ONE (2B, l) gather
        return update_wss_batched_rows(rows[:B], rows[B:], G, alpha_new,
                                       L, U, mu, impl=impl,
                                       block_l=block_l, dup=src.dup,
                                       act=act, dirv=dirv, mu2=mu2)
    XQ, sqq = src.query(stacked)
    return rbf_update_wss_batched(src.X, src.sqn, G, alpha_new, L, U,
                                  XQ[:B], sqq[:B], XQ[B:], sqq[B:], mu,
                                  src.gammas, impl=impl, block_l=block_l,
                                  dup=src.dup, act=act, dirv=dirv, mu2=mu2)


def gram(X1, X2=None, gamma=1.0, *, impl: str = "auto",
         block_i: int = 256, block_j: int = 256):
    """(Cross-)Gram matrix k(X1, X2) -> (l1, l2)."""
    impl = resolve_impl(impl)
    if X2 is None:
        X2 = X1
    if impl == "jnp":
        return ref_ops.gram_cross(X1, X2, gamma)
    l1, d = X1.shape
    l2 = X2.shape[0]
    l1p = _round_up(l1, block_i)
    l2p = _round_up(l2, block_j)
    dpad = _dpad(d)
    s1 = jnp.sum(X1 * X1, axis=-1)
    s2 = jnp.sum(X2 * X2, axis=-1)
    out = gram_pallas(
        _pad_d(_pad_l(X1, l1p), dpad), _pad_d(_pad_l(X2, l2p), dpad),
        _pad_l(s1, l1p), _pad_l(s2, l2p), gamma,
        block_i=block_i, block_j=block_j,
        interpret=(impl == "interpret"))
    return out[:l1, :l2]
