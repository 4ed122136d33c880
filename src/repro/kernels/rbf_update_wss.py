"""Pass B Pallas kernel: fused k_j recompute + gradient update + next i-pick.

The second row k_j is computed tile-by-tile in VMEM and is *never written to
HBM* — it only feeds the update G <- G - mu (k_i - k_j) in-register.  The
same pass folds the first-order argmax over I_up(alpha_new) (the next
iteration's i-selection) and both KKT gap endpoints into running per-lane
results held in VMEM across the sequential l axis (the lane-dense output
blocks of :mod:`repro.kernels.rbf_row_wss`), so the stopping rule costs no
extra pass over G and no epilogue after the launch.

HBM traffic per iteration for the whole solver (pass A + pass B):
read X twice, read G twice, write G once, plus the lane-state tiles
(alpha, L, U, optional act) once per pass.  For small d (the paper's
datasets have d <= 60) the lane state dominates; the structural win is
two launches per iteration and no HBM round-trip for gains/k_j.

Like pass A, the update/stopping algebra is dual-generic (arbitrary L/U
boxes) and row-source-generic: the batched kernels take the lane state as
an (H, B, lpad) stack of variable halves.  With H = 2 (the ε-SVR doubled
operator) both base rows k_i / k_j are computed ONCE per grid step from
the base (d8, BL) tile of the feature-major X (see
:mod:`repro.kernels.rbf_row_wss`) and applied to each half via index
arithmetic — the matmuls stay l-wide.  The rows variant consumes
pre-gathered base rows instead (Gram-bank mode).  The rbf kernel always
recomputes k_i from the i query rows; no row reaches it from HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rbf_row_wss import LANES, compiler_params, fold_max
from repro.kernels.ref import HIGHEST, rbf_exp


def _fold_min(min_ref, m, first):
    """Fold one block's per-lane minimum into the running (b, LANES)
    output block (the lower KKT gap endpoint)."""
    @pl.when(first)
    def _():
        min_ref[...] = jnp.full(min_ref.shape, jnp.inf, min_ref.dtype)

    min_ref[...] = jnp.broadcast_to(jnp.minimum(min_ref[:, 0:1], m),
                                    min_ref.shape)


def _update_from_rows(k_i, k_j, G, alpha, L, U, mu, b, *, block_l: int,
                      base_l: int, act=None, dirv=None, mu2=None):
    """Shared pass-B algebra over the (H, B, BL) state halves.

    ``k_i``/``k_j`` are the (B, BL) *base* row tiles — the doubled ε-SVR
    operator (H = 2) applies them to each half in turn, so the duplicated
    row is index arithmetic, never a second matmul or a wider tile.  A lane
    with ``mu == 0`` leaves every half of G bitwise unchanged (the
    in-kernel lane freeze).  ``act`` is an optional (H, B, BL) active-set
    tile in the data dtype (1.0/0.0) restricting the next-i scan and the
    gap endpoints; the gradient update itself stays unmasked — soft
    shrinking keeps G exact on every coordinate.  ``dirv``/``mu2`` engage
    the Conjugate-SMO second direction: ``dirv`` is the carried (H, B, BL)
    previous-direction Q-product tile and the update gains the in-register
    axpy ``- mu2 dirv`` (``mu2 == 0`` on rejected steps keeps the lane
    freeze / plain trajectory bitwise).  Returns
    (G_new (H, B, BL), bmax (B, 1), barg (B, 1) int32, bmin (B, 1)).
    """
    H = G.shape[0]
    G_new = G - mu[None] * (k_i - k_j)[None]
    if dirv is not None:
        G_new = G_new - mu2[None] * dirv
    best = barg = bmin = None
    for h in range(H):
        up = alpha[h] < U[h]
        dn = alpha[h] > L[h]
        if act is not None:
            up = up & (act[h] > 0.5)
            dn = dn & (act[h] > 0.5)
        vals_up = jnp.where(up, G_new[h], -jnp.inf)
        arg = jax.lax.argmax(vals_up, 1, jnp.int32)[:, None]
        m = jnp.max(vals_up, axis=1, keepdims=True)
        g_arg = h * base_l + b * block_l + arg
        mn = jnp.min(jnp.where(dn, G_new[h], jnp.inf), axis=1, keepdims=True)
        if best is None:
            best, barg, bmin = m, g_arg, mn
        else:
            barg = jnp.where(m > best, g_arg, barg)
            best = jnp.maximum(m, best)
            bmin = jnp.minimum(bmin, mn)
    return G_new, best, barg, bmin


def _emit(refs, G_new, bmax, barg, bmin, b):
    """Write the updated state tile and fold the block's selection."""
    G_out, bmax_out, barg_out, bmin_out = refs
    G_out[...] = G_new.astype(G_out.dtype)
    fold_max(bmax_out, barg_out, bmax, barg, b == 0)
    _fold_min(bmin_out, bmin, b == 0)


def _kernel_batched(*refs, block_l: int, base_l: int, masked: bool = False,
                    conj: bool = False):
    """Lane-batched pass B (rbf source): recompute BOTH base rows k_i, k_j
    against the shared X tile, update every state half in-register, and
    fold the per-lane next-i argmax plus both KKT gap endpoints.

    Both query blocks ride in ONE (2B, d8) x (d8, BL) matmul (the i rows
    then the j rows of the lane block): two matmuls against the same tile
    make the compiler hold several extra copies of it in VMEM.

    Neither row ever touches HBM.  A lane with ``mu == 0`` writes G back
    bitwise unchanged — that is the in-kernel lane freeze: converged lanes
    ride along as masked no-ops until every lane is done.  With
    ``masked=True`` an (H, B, BL) active-set tile rides first in the ref
    list and restricts the next-i scan / gap endpoints (soft shrinking).
    With ``conj=True`` (Conjugate-SMO) a (B, BL) previous-direction tile
    ``dirv`` rides after U, the per-lane scalars gain ``mu2``, the update
    gains the axpy ``- mu2 dirv`` and the *base* row difference
    ``r = k_i - k_j`` — next iteration's direction — is emitted as a fifth
    output (base width: the doubled halves tile it outside the kernel).
    """
    act_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    (xq_ref, scal_ref, Xt_ref, sqn_ref, G_ref, alpha_ref, L_ref,
     U_ref) = refs[:8]
    refs = refs[8:]
    dirv_ref, refs = (refs[0], refs[1:]) if conj else (None, refs)
    b = pl.program_id(1)
    bb = scal_ref.shape[0]
    # per-lane scalars: [sqq_i, sqq_j, mu, gamma] (+ [mu2] when conj)
    sqq_i = scal_ref[:, 0:1]
    sqq_j = scal_ref[:, 1:2]
    mu = scal_ref[:, 2:3]
    gamma = scal_ref[:, 3:4]
    mu2 = scal_ref[:, 4:5] if conj else None

    x = Xt_ref[...]                     # (d8, BL) shared tile
    prod = jnp.dot(xq_ref[...], x, precision=HIGHEST,
                   preferred_element_type=jnp.promote_types(x.dtype,
                                                            jnp.float32))
    sqn = sqn_ref[...]

    def row(p, sqq):
        return rbf_exp(-gamma * jnp.maximum(sqq + sqn - 2.0 * p, 0.0))

    k_i = row(prod[:bb], sqq_i)
    k_j = row(prod[bb:], sqq_j)

    G_new, bmax, barg, bmin = _update_from_rows(
        k_i, k_j, G_ref[...], alpha_ref[...], L_ref[...], U_ref[...], mu,
        b, block_l=block_l, base_l=base_l,
        act=None if act_ref is None else act_ref[...],
        dirv=None if dirv_ref is None else dirv_ref[...][None], mu2=mu2)
    _emit(refs[:4], G_new, bmax, barg, bmin, b)
    if conj:
        refs[4][...] = (k_i - k_j).astype(refs[4].dtype)


def _kernel_batched_rows(*refs, block_l: int, base_l: int,
                         masked: bool = False, conj: bool = False):
    """Lane-batched pass B (rows source): both base row tiles arrive
    pre-gathered (Gram-bank mode) — same update algebra, no matmuls.
    ``conj`` as in :func:`_kernel_batched` (scalars become [mu, mu2])."""
    act_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    (kri_ref, krj_ref, scal_ref, G_ref, alpha_ref, L_ref, U_ref) = refs[:7]
    refs = refs[7:]
    dirv_ref, refs = (refs[0], refs[1:]) if conj else (None, refs)
    b = pl.program_id(1)
    mu = scal_ref[:, 0:1]
    mu2 = scal_ref[:, 1:2] if conj else None
    k_i, k_j = kri_ref[...], krj_ref[...]
    G_new, bmax, barg, bmin = _update_from_rows(
        k_i, k_j, G_ref[...], alpha_ref[...], L_ref[...],
        U_ref[...], mu, b, block_l=block_l, base_l=base_l,
        act=None if act_ref is None else act_ref[...],
        dirv=None if dirv_ref is None else dirv_ref[...][None], mu2=mu2)
    _emit(refs[:4], G_new, bmax, barg, bmin, b)
    if conj:
        refs[4][...] = (k_i - k_j).astype(refs[4].dtype)


def _launch(kernel, args, in_specs, *, H, B, bb, lpad, block_l, dtype, act,
            dirv, interpret, name):
    """Shared pass-B launch: lane-state / selection outputs, optional act
    and Conjugate-SMO direction operands, and the result unpacking.
    ``name`` names the ``pallas_call`` (the profiler's kernel name)."""
    lane_spec = pl.BlockSpec((H, bb, block_l), lambda c, b: (0, c, b))
    row_spec = pl.BlockSpec((bb, block_l), lambda c, b: (c, b))
    sel_spec = pl.BlockSpec((bb, LANES), lambda c, b: (c, 0))
    in_specs = in_specs + [lane_spec] * 4
    out_shapes = [
        jax.ShapeDtypeStruct((H, B, lpad), dtype),
        jax.ShapeDtypeStruct((B, LANES), dtype),
        jax.ShapeDtypeStruct((B, LANES), jnp.int32),
        jax.ShapeDtypeStruct((B, LANES), dtype),
    ]
    out_specs = [lane_spec, sel_spec, sel_spec, sel_spec]
    if dirv is not None:
        in_specs.append(row_spec)
        args.append(dirv)
        out_specs.append(row_spec)
        out_shapes.append(jax.ShapeDtypeStruct((B, lpad), dtype))
    if act is not None:
        in_specs.insert(0, lane_spec)
        args.insert(0, act)
    out = pl.pallas_call(
        kernel,
        grid=(B // bb, lpad // block_l),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=tuple(out_shapes),
        compiler_params=compiler_params(),
        interpret=interpret,
        name=name,
    )(*args)
    G_new, bmax, barg, bmin = out[:4]
    return (G_new, barg[:, 0], bmax[:, 0], bmin[:, 0]) + tuple(out[4:])


@functools.partial(jax.jit, static_argnames=("block_l", "block_b",
                                             "interpret", "base_l"))
def rbf_update_wss_batched_pallas(Xt, sqn, G, alpha_new, L, U, XQ, scalars,
                                  act=None, dirv=None, *,
                                  block_l: int = 1024,
                                  block_b: int | None = None,
                                  interpret: bool = False, base_l: int = 0):
    """Launch lane-batched pass B.  ``Xt``/``sqn`` are the feature-major X
    and its norms, as in
    :func:`repro.kernels.rbf_row_wss.rbf_row_wss_batched_pallas`.  The
    state leaves are (H, B, lpad) half
    stacks (H = 2 for the doubled ε-SVR operator); ``XQ`` is (2B, d8): per
    lane block of ``block_b`` lanes, their *base* i query rows then their
    j query rows; ``scalars`` is the packed (B, 4) array
    [sqq_i, sqq_j, mu, gamma] per lane.  ``act`` is an optional
    (H, B, lpad) active-set stack (data dtype 1.0/0.0); ``block_b``
    (default: all B lanes) splits the lanes into independent grid blocks.
    Returns (G_new (H, B, lpad), i_next (B,) int32, g_i_next (B,),
    g_dn (B,)).

    ``dirv`` (Conjugate-SMO) is an optional (B, lpad) *base-width*
    previous-direction row (the doubled operator's direction is
    half-symmetric, so one base row serves both halves); with it,
    ``scalars`` is (B, 5) [..., mu2] and a fifth output ``r`` (B, lpad) —
    the base row difference k_i - k_j — is returned."""
    H, B, lpad = G.shape
    d8 = Xt.shape[0]
    bb = B if block_b is None else block_b
    assert lpad % block_l == 0 and B % bb == 0, (lpad, block_l, B, bb)
    assert Xt.shape[1] >= lpad, (Xt.shape, lpad)
    in_specs = [
        pl.BlockSpec((2 * bb, d8), lambda c, b: (c, 0)),       # XQ
        pl.BlockSpec((bb, scalars.shape[1]), lambda c, b: (c, 0)),
        pl.BlockSpec((d8, block_l), lambda c, b: (0, b)),      # Xt
        pl.BlockSpec((1, block_l), lambda c, b: (0, b)),       # sqn
    ]
    args = [XQ, scalars, Xt, sqn.reshape(1, -1), G, alpha_new, L, U]
    kernel = functools.partial(_kernel_batched, block_l=block_l,
                               base_l=base_l, masked=act is not None,
                               conj=dirv is not None)
    return _launch(kernel, args, in_specs, H=H, B=B, bb=bb, lpad=lpad,
                   block_l=block_l, dtype=Xt.dtype, act=act, dirv=dirv,
                   interpret=interpret, name="rbf_update_wss_batched_pallas")


@functools.partial(jax.jit, static_argnames=("block_l", "block_b",
                                             "interpret", "base_l"))
def update_wss_batched_rows_pallas(KRi, KRj, G, alpha_new, L, U, scalars,
                                   act=None, dirv=None, *,
                                   block_l: int = 1024,
                                   block_b: int | None = None,
                                   interpret: bool = False, base_l: int = 0):
    """Launch lane-batched pass B from pre-gathered base rows ``KRi``/``KRj``
    (B, lpad) — the Gram-bank row source.  ``scalars`` is the packed (B, 1)
    array [mu]; state stack, optional ``act`` stack, ``base_l`` and
    ``block_b`` as in :func:`rbf_update_wss_batched_pallas`.  ``dirv``
    (Conjugate-SMO) as there: (B, lpad) base-width direction row,
    ``scalars`` becomes (B, 2) [mu, mu2] and a fifth output ``r`` (B, lpad)
    is returned."""
    H, B, lpad = G.shape
    bb = B if block_b is None else block_b
    assert lpad % block_l == 0 and B % bb == 0, (lpad, block_l, B, bb)
    row_spec = pl.BlockSpec((bb, block_l), lambda c, b: (c, b))
    in_specs = [
        row_spec,                                                     # KRi
        row_spec,                                                     # KRj
        pl.BlockSpec((bb, scalars.shape[1]), lambda c, b: (c, 0)),    # scal
    ]
    args = [KRi, KRj, scalars, G, alpha_new, L, U]
    kernel = functools.partial(_kernel_batched_rows, block_l=block_l,
                               base_l=base_l, masked=act is not None,
                               conj=dirv is not None)
    return _launch(kernel, args, in_specs, H=H, B=B, bb=bb, lpad=lpad,
                   block_l=block_l, dtype=KRi.dtype, act=act, dirv=dirv,
                   interpret=interpret, name="update_wss_batched_rows_pallas")
