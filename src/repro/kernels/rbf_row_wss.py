"""Pass A Pallas kernel: fused RBF kernel-row + WSS2 j-selection.

Grid: ``(lane blocks, l blocks)``.  The inner axis walks the example
dimension in blocks of BL (a multiple of 128 so the lane dimension is
hardware-aligned); the outer axis splits the lane batch into blocks of
``block_b`` lanes when one VMEM working set cannot hold them all (the ops
wrapper sizes both from the shapes, see :func:`repro.kernels.ops.plan_tiles`).
Per grid step the VMEM working set is one (BL, d) tile of X plus the
(H, block_b, BL) lane-state tiles.

The (block_b, d) x (d, BL) matmul runs on the MXU (d padded to a multiple
of 128 by the ops wrapper); the gain algebra and the masked argmax run on
the VPU in the same pass, so G, alpha, L, U are read from HBM exactly once
and the gains are never materialized to HBM.  The per-lane selection is
reduced *in the kernel*: a running (max, argmax) pair lives in a
lane-dense (block_b, 128) output block that stays resident in VMEM across
the sequential l axis, so no per-block partials reach HBM and no epilogue
runs after the launch.

The selection algebra is dual-generic: L/U are arbitrary per-coordinate
boxes (classification, class-weighted, ε-SVR doubled, one-class lanes all
look identical from here); only the RBF ``diag == 1`` identity is
specialized.  Row sources (see :mod:`repro.kernels.row_source`):

* **rbf** — the (B, d) x (d, BL) matmul against the shared X tile;
* **doubled rbf** (``H = 2`` state halves) — the ε-SVR operator: the lane
  state arrives as an (2, B, lpad) stack of the two variable halves, the
  base row tile is computed ONCE per grid step and the selection algebra
  reads it twice via half-offset index arithmetic — the matmul stays
  l-wide;
* **rows** — pre-gathered base kernel rows (Gram-bank mode): no X at all,
  the tile is a (B, BL) slab of the gathered row block (also honouring
  the doubled half structure).

The single-lane pass A is the same kernel at B = 1 that also writes the
kernel row k_i back (``emit_k``).

Working-set indices travel through a dedicated int32 channel (``iscal``),
never through the data dtype — exact for any l (a float32 round-trip is
lossy beyond 2^24).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import HIGHEST, rbf_exp

TAU = 1e-12
# lane-dense width of the per-lane running-selection outputs: TPU blocks
# must be (8k, 128m) or span the whole array, so each lane's scalar result
# is broadcast across one 128-wide vector row
LANES = 128
_NO_INDEX = jnp.iinfo(jnp.int32).max


def compiler_params():
    """Lane blocks are independent; the l axis carries the running
    reduction, so it must run in order."""
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def fold_max(best_ref, arg_ref, m, a, first):
    """Fold one block's per-lane (max (b, 1), argmax (b, 1)) into the
    running pair held in the resident (b, LANES) output blocks.

    Ties keep the LOWEST global index — ``jnp.argmax`` semantics over the
    whole (doubled) coordinate range, even though the doubled half stack
    visits indices out of order (half 1 of block b carries larger indices
    than half 0 of block b + 1).
    """
    @pl.when(first)
    def _():
        best_ref[...] = jnp.full(best_ref.shape, -jnp.inf, best_ref.dtype)
        arg_ref[...] = jnp.full(arg_ref.shape, _NO_INDEX, jnp.int32)

    cur_m = best_ref[:, 0:1]
    cur_a = arg_ref[:, 0:1]
    take = (m > cur_m) | ((m == cur_m) & (a < cur_a))
    best_ref[...] = jnp.broadcast_to(jnp.where(take, m, cur_m),
                                     best_ref.shape)
    arg_ref[...] = jnp.broadcast_to(jnp.where(take, a, cur_a), arg_ref.shape)


def _select_from_k(k, G, alpha, L, U, scal, i_idx, b, *, block_l: int,
                   base_l: int, act=None):
    """Shared WSS2 selection algebra over the (H, B, BL) state halves.

    ``k`` is the (B, BL) *base* kernel-row tile; the doubled ε-SVR operator
    (H = 2) reads it once per half — row k of Q = [[K, K], [K, K]] is the
    base row tiled, so the duplication is index arithmetic, not a second
    matmul.  The global coordinate of half h is ``h * base_l + offset``
    (``base_l`` is the TRUE base example count — padded tails are inert).
    ``act`` is an optional (H, B, BL) active-set tile in the data dtype
    (1.0 active / 0.0 shrunk) that further masks the j-scan.
    Returns the per-block (best (B, 1), arg (B, 1) int32) pair.
    """
    H = G.shape[0]
    # per-lane scalars: [a_i, L_i, U_i, g_i, use_exact] columns of scal
    a_i = scal[:, 0:1]
    L_i = scal[:, 1:2]
    U_i = scal[:, 2:3]
    g_i = scal[:, 3:4]
    use_exact = scal[:, 4:5] > 0.5
    q_vec = jnp.maximum(2.0 - 2.0 * k, TAU)      # RBF diag == 1
    best = None
    barg = None
    for h in range(H):
        Gh, ah, Lh, Uh = G[h], alpha[h], L[h], U[h]
        l_vec = g_i - Gh
        g_tilde = 0.5 * l_vec * l_vec / q_vec
        lo = jnp.maximum(L_i - a_i, ah - Uh)
        hi = jnp.minimum(U_i - a_i, ah - Lh)
        mu_c = jnp.clip(l_vec / q_vec, lo, hi)
        g_exact = l_vec * mu_c - 0.5 * q_vec * mu_c * mu_c
        gains = jnp.where(use_exact, g_exact, g_tilde)
        gidx = (h * base_l + b * block_l
                + jax.lax.broadcasted_iota(jnp.int32, k.shape, 1))
        mask = (ah > Lh) & (l_vec > 0) & (gidx != i_idx)
        if act is not None:
            mask = mask & (act[h] > 0.5)
        vals = jnp.where(mask, gains, -jnp.inf)
        arg = jax.lax.argmax(vals, 1, jnp.int32)[:, None]
        m = jnp.max(vals, axis=1, keepdims=True)
        g_arg = h * base_l + b * block_l + arg
        if best is None:
            best, barg = m, g_arg
        else:
            barg = jnp.where(m > best, g_arg, barg)
            best = jnp.maximum(m, best)
    return best, barg


def _kernel_batched(*refs, block_l: int, base_l: int, masked: bool = False,
                    emit_k: bool = False):
    """Lane-batched pass A (rbf row source): every lane shares the (BL, d)
    X tile.

    The B query rows hit the tile as ONE (B, d) x (d, BL) MXU matmul; the
    per-lane gain algebra and masked argmax run on the VPU over (B, BL)
    registers.  The batched engine writes no k-row back — its pass B
    recomputes it, trading one extra matmul for an HBM round-trip of
    (B, l) and for launch-free Alg. 3 candidate swaps; the single-lane
    engine asks for the row (``emit_k``).  With ``masked=True`` an
    (H, B, BL) active-set tile rides first in the ref list and restricts
    the j-scan (soft shrinking).
    """
    act_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    (xq_ref, scal_ref, iscal_ref, X_ref, sqn_ref, G_ref, alpha_ref,
     L_ref, U_ref, bmax_out, barg_out) = refs[:11]
    b = pl.program_id(1)
    sqq = scal_ref[:, 0:1]
    gamma = scal_ref[:, 1:2]

    x = X_ref[...]                      # (BL, d) shared tile
    q = xq_ref[...]                     # (B, d) per-lane query rows
    prod = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.promote_types(x.dtype, jnp.float32))
    d2 = sqq + sqn_ref[...] - 2.0 * prod                    # (B, BL)
    # a row that only ranks candidates keeps the hardware exp; the row the
    # single-lane engine updates G with takes the accurate one
    k = (rbf_exp if emit_k else jnp.exp)(-gamma * jnp.maximum(d2, 0.0))
    if emit_k:
        refs[11][...] = k.astype(refs[11].dtype)

    m, a = _select_from_k(
        k, G_ref[...], alpha_ref[...], L_ref[...], U_ref[...],
        scal_ref[:, 2:], iscal_ref[...], b, block_l=block_l, base_l=base_l,
        act=None if act_ref is None else act_ref[...])
    fold_max(bmax_out, barg_out, m, a, b == 0)


def _kernel_batched_rows(*refs, block_l: int, base_l: int,
                         masked: bool = False):
    """Lane-batched pass A (rows source): the kernel-row tile arrives
    pre-gathered (Gram-bank mode) — same selection algebra, no matmul."""
    act_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    (kr_ref, scal_ref, iscal_ref, G_ref, alpha_ref, L_ref, U_ref,
     bmax_out, barg_out) = refs
    b = pl.program_id(1)
    m, a = _select_from_k(
        kr_ref[...], G_ref[...], alpha_ref[...], L_ref[...], U_ref[...],
        scal_ref[...], iscal_ref[...], b, block_l=block_l, base_l=base_l,
        act=None if act_ref is None else act_ref[...])
    fold_max(bmax_out, barg_out, m, a, b == 0)


def _selection_out(B: int, block_b: int, dtype):
    """(shapes, specs) of the lane-dense running (max, argmax) outputs."""
    spec = pl.BlockSpec((block_b, LANES), lambda c, b: (c, 0))
    shapes = [jax.ShapeDtypeStruct((B, LANES), dtype),
              jax.ShapeDtypeStruct((B, LANES), jnp.int32)]
    return shapes, [spec, spec]


@functools.partial(jax.jit, static_argnames=("block_l", "block_b",
                                             "interpret", "base_l", "emit_k"))
def rbf_row_wss_batched_pallas(X, sqn, G, alpha, L, U, XQ, scalars,
                               iscalars, act=None, *, block_l: int = 1024,
                               block_b: int | None = None,
                               interpret: bool = False, base_l: int = 0,
                               emit_k: bool = False):
    """Launch lane-batched pass A.  ``G``/``alpha``/``L``/``U`` are
    (H, B, lpad) stacks of the variable halves (H = 1 plain, H = 2 the
    doubled ε-SVR operator) with both trailing dims padded by the ops
    wrapper; ``XQ`` is (B, d) *base* query rows; ``scalars`` is the packed
    (B, 7) float array [sqq, gamma, a_i, L_i, U_i, g_i, use_exact] and
    ``iscalars`` the (B, 1) int32 channel [i_idx] (global doubled index).
    ``base_l`` is the true base example count (half-1 coordinates are
    ``base_l + offset``).  ``act`` is an optional (H, B, lpad) active-set
    stack in the data dtype (1.0/0.0; soft shrinking).  ``block_b``
    (default: all B lanes) splits the lanes into independent grid blocks.

    Returns (j (B,) int32, gain (B,)), plus the base kernel rows
    (B, lpad) when ``emit_k``.
    """
    H, B, lpad = G.shape
    d = X.shape[1]
    bb = B if block_b is None else block_b
    assert lpad % block_l == 0 and B % bb == 0, (lpad, block_l, B, bb)
    dtype = X.dtype

    lane_spec = pl.BlockSpec((H, bb, block_l), lambda c, b: (0, c, b))
    lane_row = lambda w: pl.BlockSpec((bb, w), lambda c, b: (c, 0))
    out_shapes, out_specs = _selection_out(B, bb, dtype)
    if emit_k:
        out_shapes.append(jax.ShapeDtypeStruct((B, lpad), dtype))
        out_specs.append(pl.BlockSpec((bb, block_l), lambda c, b: (c, b)))
    masked = act is not None
    in_specs = [
        lane_row(d),                                           # XQ
        lane_row(7),                                           # scalars
        lane_row(1),                                           # iscalars
        pl.BlockSpec((block_l, d), lambda c, b: (b, 0)),       # X
        pl.BlockSpec((1, block_l), lambda c, b: (0, b)),       # sqn
        lane_spec, lane_spec, lane_spec, lane_spec,
    ]
    args = [XQ, scalars, iscalars, X, sqn.reshape(1, lpad), G, alpha, L, U]
    if masked:
        in_specs.insert(0, lane_spec)
        args.insert(0, act)
    out = pl.pallas_call(
        functools.partial(_kernel_batched, block_l=block_l, base_l=base_l,
                          masked=masked, emit_k=emit_k),
        grid=(B // bb, lpad // block_l),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=tuple(out_shapes),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="rbf_row_wss_batched_pallas",
    )(*args)
    sel = (out[1][:, 0], out[0][:, 0])
    return sel + (out[2],) if emit_k else sel


@functools.partial(jax.jit, static_argnames=("block_l", "block_b",
                                             "interpret", "base_l"))
def row_wss_batched_rows_pallas(KR, G, alpha, L, U, scalars, iscalars,
                                act=None, *, block_l: int = 1024,
                                block_b: int | None = None,
                                interpret: bool = False, base_l: int = 0):
    """Launch lane-batched pass A from pre-gathered base rows ``KR``
    (B, lpad) — the Gram-bank row source.  ``scalars`` is the packed
    (B, 5) float array [a_i, L_i, U_i, g_i, use_exact]; the state stack,
    optional ``act`` stack, ``iscalars``/``base_l`` and ``block_b`` are as
    in :func:`rbf_row_wss_batched_pallas`.  Returns (j (B,), gain (B,)).
    """
    H, B, lpad = G.shape
    bb = B if block_b is None else block_b
    assert lpad % block_l == 0 and B % bb == 0, (lpad, block_l, B, bb)

    lane_spec = pl.BlockSpec((H, bb, block_l), lambda c, b: (0, c, b))
    lane_row = lambda w: pl.BlockSpec((bb, w), lambda c, b: (c, 0))
    out_shapes, out_specs = _selection_out(B, bb, KR.dtype)
    masked = act is not None
    in_specs = [
        pl.BlockSpec((bb, block_l), lambda c, b: (c, b)),      # KR
        lane_row(5),                                           # scalars
        lane_row(1),                                           # iscalars
        lane_spec, lane_spec, lane_spec, lane_spec,
    ]
    args = [KR, scalars, iscalars, G, alpha, L, U]
    if masked:
        in_specs.insert(0, lane_spec)
        args.insert(0, act)
    bmax, barg = pl.pallas_call(
        functools.partial(_kernel_batched_rows, block_l=block_l,
                          base_l=base_l, masked=masked),
        grid=(B // bb, lpad // block_l),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=tuple(out_shapes),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="row_wss_batched_rows_pallas",
    )(*args)
    return barg[:, 0], bmax[:, 0]
