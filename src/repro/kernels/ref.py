"""Pure-jnp oracles for the Pallas kernels (the allclose reference)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

TAU = 1e-12
# Kernel distances expand |a|^2 + |b|^2 - 2 a.b, which cancels: the a.b
# products need full float32.  TPUs run DEFAULT-precision f32 matmuls as
# one bf16 pass, so every distance and decision matmul asks for HIGHEST.
HIGHEST = jax.lax.Precision.HIGHEST

# The TPU's float32 exp (XLA and Mosaic alike) errs by up to 5e-6 relative
# and reads low by 8e-7 on average (TPU v5e, against float64).  Summed over
# the support vectors of K alpha that bias moves G by ~1e-4, a tenth of
# LIBSVM's eps, so every kernel entry of the fused engine that updates G
# takes rbf_exp: Cody-Waite reduction z = k ln2 + r, then Cephes' expf
# polynomial on |r| <= ln2 / 2, about one ulp on every backend.
_LOG2E = 1.4426950408889634
_LN2_HI = 0.693145751953125          # 16 bits: k * _LN2_HI is exact
_LN2_LO = 1.428606765330187e-06
_EXP_POLY = (1.9875691500e-4, 1.3981999507e-3, 8.3334519073e-3,
             4.1665795894e-2, 1.6666665459e-1, 5.0000001201e-1)


def rbf_exp(z):
    """``exp(z)`` of RBF exponents ``z <= 0``, to float32's own accuracy
    on every backend (other dtypes take ``jnp.exp``).  Below -87, where
    float32 leaves its normal range, it returns exp(-87)."""
    if z.dtype != jnp.float32:
        return jnp.exp(z)
    z = jnp.maximum(z, -87.0)
    k = jnp.floor(z * _LOG2E + 0.5)
    r = (z - k * _LN2_HI) - k * _LN2_LO
    p = r * _EXP_POLY[0] + _EXP_POLY[1]
    for c in _EXP_POLY[2:]:
        p = p * r + c
    scale = jax.lax.bitcast_convert_type(
        (k.astype(jnp.int32) + 127) << 23, jnp.float32)
    return ((p * r * r + r) + 1.0) * scale


def _act_bool(act):
    """Boolean view of an active-set mask.

    The jnp path hands the mask through as bool; the Pallas path pads it
    as 0/1 floats.  Comparing a bool mask against the Python float 0.5
    weak-promotes it to f64 under x64, so only the float form gets the
    threshold compare.
    """
    return act if act.dtype == jnp.bool_ else act > 0.5


def take_lane(M, idx):
    """Per-lane gather: M (B, l), idx (B,) -> (B,).

    A vmapped scalar index keeps the int32 index channel;
    ``jnp.take_along_axis`` widens its indices to int64 under x64.
    """
    return jax.vmap(lambda row, i: row[i])(M, idx)


def rbf_row(X, sqn, xq, gamma):
    """k(x_q, X) for one query row."""
    d2 = (jnp.dot(xq, xq, precision=HIGHEST) + sqn
          - 2.0 * jnp.dot(X, xq, precision=HIGHEST))
    return rbf_exp(-gamma * jnp.maximum(d2, 0.0))


def rbf_row_wss(X, sqn, G, alpha, L, U, xq, a_i, L_i, U_i, g_i, i_idx,
                use_exact, gamma):
    """Pass A oracle: kernel row k_i + WSS2 j-selection.

    Returns (k_i, j, gain_j).  RBF diag == 1 is hardcoded (paper setting).
    """
    k = rbf_row(X, sqn, xq, gamma)
    l = g_i - G
    q = jnp.maximum(2.0 - 2.0 * k, TAU)
    g_tilde = 0.5 * l * l / q
    lo = jnp.maximum(L_i - a_i, alpha - U)
    hi = jnp.minimum(U_i - a_i, alpha - L)
    mu_c = jnp.clip(l / q, lo, hi)
    g_exact = l * mu_c - 0.5 * q * mu_c * mu_c
    gains = jnp.where(use_exact, g_exact, g_tilde)
    idx = jnp.arange(X.shape[0], dtype=jnp.int32)
    mask = (alpha > L) & (l > 0) & (idx != i_idx)
    vals = jnp.where(mask, gains, -jnp.inf)
    j = jax.lax.argmax(vals, 0, jnp.int32)
    return k, j, vals[j]


def rbf_update_wss(X, sqn, G, k_i, xq_j, mu, alpha_new, L, U, gamma):
    """Pass B oracle: row k_j + gradient update + next i-pick + gap ends.

    Returns (G_new, i_next, g_i_next, g_dn).
    """
    k_j = rbf_row(X, sqn, xq_j, gamma)
    G_new = G - mu * (k_i - k_j)
    up = alpha_new < U
    dn = alpha_new > L
    vals_up = jnp.where(up, G_new, -jnp.inf)
    i_next = jax.lax.argmax(vals_up, 0, jnp.int32)
    g_dn = jnp.min(jnp.where(dn, G_new, jnp.inf))
    return G_new, i_next, vals_up[i_next], g_dn


# ---------------------------------------------------------------------------
# Batched (lane-dimension) oracles
# ---------------------------------------------------------------------------
#
# One lane = one QP (a (C, gamma, labels) grid point).  All lanes share the
# same X / sqn; per-lane state is stacked on a leading B axis.  The O(l d B)
# part — the squared-distance rows — is ONE (B, d) x (d, l) matmul over the
# shared X, and per-lane gamma costs one extra exp on that shared d2 row
# (mirroring the solve_grid factorization).  Unlike the single-lane pass A,
# the batched pass A returns only the selection (j, gain): pass B recomputes
# both rows k_i / k_j in place of an HBM round-trip, which also lets the
# Alg. 3 candidate swap the i-row without a data-dependent relaunch.


def tile_rows(k):
    """Doubled-operator row tiling: (B, l) base rows -> (B, 2l).

    Row k of ``Q = [[K, K], [K, K]]`` is the base row tiled — the jnp
    oracle's counterpart of the Pallas kernels' in-kernel half reads.
    """
    return jnp.concatenate([k, k], axis=1)


def rbf_rows_batched(X, sqn, XQ, sqq, gammas, dup: bool = False,
                     exp=rbf_exp):
    """k(x_q^b, X) for a batch of query rows -> (B, l).

    ``dup=True`` returns the *doubled-operator* rows (B, 2l) used by the
    ε-SVR dual (:func:`tile_rows`): the O(B l d) distance matmul runs
    against the base ``X`` only and the 2l half is a free broadcast —
    never a 2l-wide matmul, never a 2l x 2l Gram.  ``exp`` is
    :func:`rbf_exp` for rows that update G, ``jnp.exp`` for rows that
    only rank candidates (the batched pass A).
    """
    d2 = (sqq[:, None] + sqn[None, :]
          - 2.0 * jnp.dot(XQ, X.T, precision=HIGHEST))
    k = exp(-gammas[:, None] * jnp.maximum(d2, 0.0))
    return tile_rows(k) if dup else k


def row_wss_batched_from_k(k, G, alpha, L, U, a_i, L_i, U_i, g_i, i_idx,
                           use_exact, act=None):
    """Pass A selection algebra given the (B, l) kernel rows ``k``.

    Shared by the X-backed oracle below and the Gram-bank gather mode of
    :func:`repro.core.solver_fused.solve_fused_batched`.  RBF diag == 1 is
    hardcoded (paper setting).  ``act`` optionally restricts the j-scan to
    a per-lane (B, n) active set (soft shrinking: G stays exact
    everywhere, only the selection is masked).  Returns
    (j (B,) int32, gain_j (B,)).
    """
    lv = g_i[:, None] - G
    q = jnp.maximum(2.0 - 2.0 * k, TAU)
    g_tilde = 0.5 * lv * lv / q
    lo = jnp.maximum((L_i - a_i)[:, None], alpha - U)
    hi = jnp.minimum((U_i - a_i)[:, None], alpha - L)
    mu_c = jnp.clip(lv / q, lo, hi)
    g_exact = lv * mu_c - 0.5 * q * mu_c * mu_c
    gains = jnp.where(use_exact[:, None], g_exact, g_tilde)
    idx = jnp.arange(G.shape[1], dtype=jnp.int32)
    mask = (alpha > L) & (lv > 0) & (idx[None, :] != i_idx[:, None])
    if act is not None:
        mask = mask & _act_bool(act)
    vals = jnp.where(mask, gains, -jnp.inf)
    j = jax.lax.argmax(vals, 1, jnp.int32)
    return j, take_lane(vals, j)


def rbf_row_wss_batched(X, sqn, G, alpha, L, U, XQ, sqq, a_i, L_i, U_i,
                        g_i, i_idx, use_exact, gammas, dup: bool = False,
                        act=None):
    """Batched pass A oracle: WSS2 j-selection per lane.

    ``G``/``alpha``/``L``/``U`` are (B, n); ``XQ`` is (B, d); the remaining
    per-lane scalars are (B,).  With ``dup=True`` the lane state is doubled
    (n = 2l, the ε-SVR dual) while ``X``/``sqn`` stay the base (l, d)/(l,)
    — the selection algebra is box-general, so the only structural change
    is the tiled row.  Returns (j (B,) int32, gain_j (B,)).
    """
    k = rbf_rows_batched(X, sqn, XQ, sqq, gammas, dup=dup, exp=jnp.exp)
    return row_wss_batched_from_k(k, G, alpha, L, U, a_i, L_i, U_i, g_i,
                                  i_idx, use_exact, act=act)


def update_wss_batched_from_rows(G, k_i, k_j, mu, alpha_new, L, U, act=None,
                                 dirv=None, mu2=None):
    """Pass B update + stopping-scan algebra given both (B, l) rows.

    A lane with ``mu == 0`` is a bitwise no-op on G (the in-kernel
    lane-freeze used by ``solve_fused_batched``).  ``act`` optionally
    restricts the next-i scan and the gap endpoints to a per-lane active
    set; the gradient update itself is NEVER masked (soft shrinking keeps
    G exact on every coordinate, so unshrinking is free).  Returns
    (G_new (B, l), i_next (B,), g_i_next (B,), g_dn (B,)).

    ``dirv``/``mu2`` engage the Conjugate-SMO second direction: ``dirv``
    is the carried (B, n) previous update direction's Q-product and the
    gradient update becomes ``G - mu (k_i - k_j) - mu2 dirv`` (a rejected
    conjugate step has ``mu2 == 0``, keeping the plain trajectory bitwise).
    The return grows a fifth element ``r = k_i - k_j`` — next iteration's
    ``dirv`` — ONLY when engaged, so the plain contract is unchanged.
    """
    G_new = G - mu[:, None] * (k_i - k_j)
    if dirv is not None:
        G_new = G_new - mu2[:, None] * dirv
    up = alpha_new < U
    dn = alpha_new > L
    if act is not None:
        up = up & _act_bool(act)
        dn = dn & _act_bool(act)
    vals_up = jnp.where(up, G_new, -jnp.inf)
    i_next = jax.lax.argmax(vals_up, 1, jnp.int32)
    g_i_next = take_lane(vals_up, i_next)
    g_dn = jnp.min(jnp.where(dn, G_new, jnp.inf), axis=1)
    if dirv is not None:
        return G_new, i_next, g_i_next, g_dn, k_i - k_j
    return G_new, i_next, g_i_next, g_dn


def rbf_update_wss_batched(X, sqn, G, alpha_new, L, U, XQi, sqqi, XQj, sqqj,
                           mu, gammas, dup: bool = False, act=None,
                           dirv=None, mu2=None):
    """Batched pass B oracle: k_i/k_j recompute + update + next i + gap ends.

    Both rows come from one stacked (2B, d) x (d, l) matmul (against the
    base ``X`` even when ``dup=True`` doubles the lane state to n = 2l).
    Returns (G_new (B, n), i_next (B,), g_i_next (B,), g_dn (B,)); with
    ``dirv``/``mu2`` (Conjugate-SMO, see
    :func:`update_wss_batched_from_rows`) a fifth ``r = k_i - k_j``.
    """
    B = G.shape[0]
    Kr = rbf_rows_batched(X, sqn,
                          jnp.concatenate([XQi, XQj], axis=0),
                          jnp.concatenate([sqqi, sqqj]),
                          jnp.concatenate([gammas, gammas]), dup=dup)
    return update_wss_batched_from_rows(G, Kr[:B], Kr[B:], mu, alpha_new,
                                        L, U, act=act, dirv=dirv, mu2=mu2)


def gram(X, gamma):
    """Full RBF Gram matrix."""
    sq = jnp.sum(X * X, axis=-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.dot(X, X.T, precision=HIGHEST)
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


def gram_cross(X1, X2, gamma):
    """Cross Gram matrix k(X1, X2) -> (l1, l2)."""
    s1 = jnp.sum(X1 * X1, axis=-1)
    s2 = jnp.sum(X2 * X2, axis=-1)
    d2 = (s1[:, None] + s2[None, :]
          - 2.0 * jnp.dot(X1, X2.T, precision=HIGHEST))
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))
