"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes."""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, row_source

# single-QP shapes (l, d): tier-1 runs the two small ones; the large
# tiling/padding shapes ride the slow tier (nightly full run +
# REPRO_IMPL=interpret leg)
SHAPES = [(64, 2), (200, 7), (1024, 16), (1500, 60), (2048, 128)]
TIER1_SHAPES = SHAPES[:2]
DTYPES = [jnp.float32, jnp.float64]
# the batched passes stream X feature-major, d padded to a multiple of 8:
# d not a multiple of 8 (3, 22) and d above 128 (130), at B = 1, 3, 10
STREAM_SHAPES = [(64, 3, 1), (200, 22, 3), (150, 130, 10)]


def _setup(l, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(l, d)), dtype)
    y = jnp.asarray(np.sign(rng.normal(size=l)), dtype)
    C = 10.0
    alpha = jnp.asarray(rng.uniform(-1, 1, size=l), dtype) * jnp.abs(y) * C
    alpha = jnp.clip(alpha, jnp.minimum(0.0, y * C), jnp.maximum(0.0, y * C))
    G = jnp.asarray(rng.normal(size=l), dtype)
    L = jnp.minimum(0.0, y * C)
    U = jnp.maximum(0.0, y * C)
    sqn = jnp.sum(X * X, axis=-1)
    gamma = jnp.asarray(0.3, dtype)
    return X, sqn, G, alpha, L, U, gamma


@pytest.mark.parametrize("l1,l2,d", [
    (64, 64, 2), (200, 100, 7),
    pytest.param(300, 513, 33, marks=pytest.mark.slow),
    pytest.param(1024, 256, 128, marks=pytest.mark.slow)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_gram_block(l1, l2, d, dtype):
    rng = np.random.default_rng(2)
    X1 = jnp.asarray(rng.normal(size=(l1, d)), dtype)
    X2 = jnp.asarray(rng.normal(size=(l2, d)), dtype)
    gamma = 0.4
    K_ref = ref.gram_cross(X1, X2, gamma)
    K_pl = ops.gram(X1, X2, gamma, impl="interpret", block_i=128,
                    block_j=128)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    np.testing.assert_allclose(np.asarray(K_pl), np.asarray(K_ref),
                               rtol=tol, atol=tol)


def test_gram_symmetric_psd():
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(100, 5)))
    K = np.asarray(ops.gram(X, gamma=0.5, impl="interpret",
                            block_i=128, block_j=128))
    np.testing.assert_allclose(K, K.T, atol=1e-12)
    w = np.linalg.eigvalsh(K)
    assert w.min() > -1e-8
    np.testing.assert_allclose(np.diag(K), 1.0, atol=1e-12)


def _setup_batched(l, d, B, dtype, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(l, d)), dtype)
    sqn = jnp.sum(X * X, axis=-1)
    ys = jnp.asarray(np.sign(rng.normal(size=(B, l))), dtype)
    C = 10.0
    L = jnp.minimum(0.0, ys * C)
    U = jnp.maximum(0.0, ys * C)
    alpha = jnp.clip(jnp.asarray(rng.uniform(-1, 1, (B, l)), dtype) * C, L, U)
    G = jnp.asarray(rng.normal(size=(B, l)), dtype)
    gammas = jnp.asarray(rng.uniform(0.2, 1.5, B), dtype)
    i_idx = jnp.asarray(rng.integers(0, l, B), jnp.int32)
    return X, sqn, G, alpha, L, U, gammas, i_idx


def _lane(M, idx):
    return jnp.take_along_axis(M, idx[:, None], axis=1)[:, 0]


class Lane(NamedTuple):
    """One QP run as a batch of one: :func:`_setup`'s data at ``seed``,
    first working-set index ``i``, pass A's gain rule ``use_exact``, pass
    B's second index ``j`` and step ``mu``, and the l block size."""
    seed: int
    i: int
    block_l: int
    use_exact: bool = False
    j: int = 11
    mu: float = 0.37


def _lane_batch(l, d, dtype, lane: Lane):
    """``lane``'s QP in :func:`_setup_batched`'s (B = 1) layout."""
    X, sqn, G, alpha, L, U, gamma = _setup(l, d, dtype, seed=lane.seed)
    return (X, sqn, G[None], alpha[None], L[None], U[None], gamma[None],
            jnp.asarray([lane.i], jnp.int32))


# the batched passes on _setup_batched's lanes, (l, d, B), the 257 shape
# on the slow tier
BATCHED_SHAPES = [(64, 2, 3), (257, 33, 5)] + STREAM_SHAPES


def _pass_params(lanes):
    """(dtype, l, d, B, lane) cases of a batched pass against its per-lane
    oracle: every batched shape at both dtypes (``lane`` None), then the
    single-QP ``lanes``, each a (dtype, l, d, Lane, id, slow) tuple, at
    B = 1."""
    slow = lambda on: [pytest.mark.slow] if on else []
    name = lambda dt: jnp.dtype(dt).name
    return ([pytest.param(dt, l, d, B, None, id=f"{name(dt)}-{l}-{d}-{B}",
                          marks=slow(l == 257))
             for dt in DTYPES for l, d, B in BATCHED_SHAPES]
            + [pytest.param(dt, l, d, 1, lane, id=f"{name(dt)}-{id_}",
                            marks=slow(on))
               for dt, l, d, lane, id_, on in lanes])


PASS_A_LANES = (
    [(dt, l, d, Lane(seed=0, i=3, block_l=256, use_exact=ue),
      f"{l}-{d}-1-{'exact' if ue else 'approx'}",
      (l, d) not in TIER1_SHAPES)
     for ue in (False, True) for dt in DTYPES for l, d in SHAPES]
    # the block shape must not change results (padding/tiling invariance)
    + [(jnp.float64, 389, 13, Lane(seed=4, i=42, block_l=bl),
        f"389-13-1-block{bl}", bl > 256) for bl in (128, 256, 512, 1024)])
PASS_B_LANES = [(dt, l, d, Lane(seed=1, i=3, block_l=256), f"{l}-{d}-1",
                 (l, d) not in TIER1_SHAPES)
                for dt in DTYPES for l, d in SHAPES]


@pytest.mark.parametrize("dtype,l,d,B,lane", _pass_params(PASS_A_LANES))
def test_pass_a_batched_matches_single_lane(dtype, l, d, B, lane):
    """Batched pass A (jnp + interpret) == per-lane single-lane oracle, on
    B lanes or on one QP as a batch of one (``lane``)."""
    if lane is None:
        X, sqn, G, alpha, L, U, gammas, i_idx = _setup_batched(l, d, B,
                                                               dtype)
        use_exact = jnp.asarray([b % 2 == 0 for b in range(B)])
        block_l = 128
    else:
        X, sqn, G, alpha, L, U, gammas, i_idx = _lane_batch(l, d, dtype,
                                                            lane)
        use_exact = jnp.asarray([lane.use_exact])
        block_l = lane.block_l
    XQ = jnp.take(X, i_idx, axis=0)
    sqq = jnp.take(sqn, i_idx)
    a_i, L_i, U_i = _lane(alpha, i_idx), _lane(L, i_idx), _lane(U, i_idx)
    g_i = _lane(G, i_idx)
    js, gains = [], []
    for b in range(B):
        _, j, g = ref.rbf_row_wss(X, sqn, G[b], alpha[b], L[b], U[b], XQ[b],
                                  a_i[b], L_i[b], U_i[b], g_i[b], i_idx[b],
                                  use_exact[b], gammas[b])
        js.append(int(j))
        gains.append(float(g))
    tol = 1e-4 if dtype == jnp.float32 else 1e-11
    for impl in ("jnp", "interpret"):
        j_b, gain_b = ops.rbf_row_wss_batched(
            X, sqn, G, alpha, L, U, XQ, sqq, a_i, L_i, U_i, g_i, i_idx,
            use_exact, gammas, impl=impl, block_l=block_l)
        assert [int(x) for x in j_b] == js, impl
        np.testing.assert_allclose(np.asarray(gain_b), gains, rtol=tol)


@pytest.mark.parametrize("dtype,l,d,B,lane", _pass_params(PASS_B_LANES))
def test_pass_b_batched_matches_single_lane(dtype, l, d, B, lane):
    """Batched pass B (jnp + interpret) == per-lane single-lane oracle, on
    B lanes, including a frozen (mu = 0) lane whose G must come back
    unchanged, or on one QP as a batch of one (``lane``)."""
    if lane is None:
        X, sqn, G, alpha, L, U, gammas, i_idx = _setup_batched(
            l, d, B, dtype, seed=1)
        rng = np.random.default_rng(2)
        j_idx = jnp.asarray(rng.integers(0, l, B), jnp.int32)
        mu = jnp.asarray(rng.uniform(-0.5, 0.5, B), dtype).at[0].set(0.0)
        block_l = 128
    else:
        X, sqn, G, alpha, L, U, gammas, i_idx = _lane_batch(l, d, dtype,
                                                            lane)
        j_idx = jnp.asarray([lane.j], jnp.int32)
        mu = jnp.asarray([lane.mu], dtype)
        block_l = lane.block_l
    XQi = jnp.take(X, i_idx, axis=0)
    XQj = jnp.take(X, j_idx, axis=0)
    sqqi, sqqj = jnp.take(sqn, i_idx), jnp.take(sqn, j_idx)
    lanes = jnp.arange(B)
    alpha_new = jnp.clip(alpha.at[lanes, i_idx].add(mu)
                         .at[lanes, j_idx].add(-mu), L, U)
    refs = []
    for b in range(B):
        k_i = ref.rbf_row(X, sqn, XQi[b], gammas[b])
        refs.append(ref.rbf_update_wss(X, sqn, G[b], k_i, XQj[b], mu[b],
                                       alpha_new[b], L[b], U[b], gammas[b]))
    tol = 1e-4 if dtype == jnp.float32 else 1e-11
    # the single-QP cases hold G to a tenth of that
    g_tol = tol if lane is None else tol / 10
    for impl in ("jnp", "interpret"):
        Gn, i_n, gi_n, gdn = ops.rbf_update_wss_batched(
            X, sqn, G, alpha_new, L, U, XQi, sqqi, XQj, sqqj, mu, gammas,
            impl=impl, block_l=block_l)
        np.testing.assert_allclose(np.asarray(Gn),
                                   np.stack([np.asarray(r[0]) for r in refs]),
                                   rtol=g_tol, atol=g_tol)
        assert [int(x) for x in i_n] == [int(r[1]) for r in refs], impl
        np.testing.assert_allclose(np.asarray(gi_n),
                                   [float(r[2]) for r in refs], rtol=tol)
        np.testing.assert_allclose(np.asarray(gdn),
                                   [float(r[3]) for r in refs], rtol=tol)
        if lane is None:   # the frozen lane: bitwise no-op on G
            np.testing.assert_array_equal(np.asarray(Gn[0]),
                                          np.asarray(G[0]))


def _setup_doubled(l, d, B, dtype, seed=0):
    """Doubled ε-SVR lane state (n = 2l) over a base (l, d) X."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(l, d)), dtype)
    sqn = jnp.sum(X * X, axis=-1)
    C = 5.0
    zl = jnp.zeros((B, l), dtype)
    L = jnp.concatenate([zl, zl - C], axis=1)
    U = jnp.concatenate([zl + C, zl], axis=1)
    alpha = jnp.clip(jnp.asarray(rng.uniform(-1, 1, (B, 2 * l)), dtype), L, U)
    G = jnp.asarray(rng.normal(size=(B, 2 * l)), dtype)
    gammas = jnp.asarray(rng.uniform(0.2, 1.5, B), dtype)
    i_idx = jnp.asarray(rng.integers(0, 2 * l, B), jnp.int32)
    return X, sqn, G, alpha, L, U, gammas, i_idx


@pytest.mark.parametrize("l,d,B", [
    (64, 2, 3), pytest.param(300, 17, 5, marks=pytest.mark.slow)]
    + STREAM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pass_a_doubled_in_kernel_matches_jnp_oracle(l, d, B, dtype):
    """Tentpole parity: the in-kernel doubled row mode (interpret) — base
    row tile computed once, read per half — equals the jnp oracle that
    tiles the base (B, l) row, including half-1 working-set indices."""
    X, sqn, G, alpha, L, U, gammas, i_idx = _setup_doubled(l, d, B, dtype)
    bi = i_idx % l
    XQ, sqq = jnp.take(X, bi, axis=0), jnp.take(sqn, bi)
    a_i, L_i, U_i = _lane(alpha, i_idx), _lane(L, i_idx), _lane(U, i_idx)
    g_i = _lane(G, i_idx)
    use_exact = jnp.asarray([b % 2 == 0 for b in range(B)])
    args = (X, sqn, G, alpha, L, U, XQ, sqq, a_i, L_i, U_i, g_i, i_idx,
            use_exact, gammas)
    j_ref, gain_ref = ops.rbf_row_wss_batched(*args, impl="jnp", dup=True)
    j_pl, gain_pl = ops.rbf_row_wss_batched(*args, impl="interpret",
                                            block_l=128, dup=True)
    tol = 1e-4 if dtype == jnp.float32 else 1e-11
    assert [int(x) for x in j_pl] == [int(x) for x in j_ref]
    np.testing.assert_allclose(np.asarray(gain_pl), np.asarray(gain_ref),
                               rtol=tol)
    # at least one lane must have selected a half-1 coordinate for the
    # half-offset index arithmetic to be exercised
    assert any(int(x) >= l for x in j_ref) or any(int(x) >= l for x in i_idx)


@pytest.mark.parametrize("l,d,B", [
    (64, 2, 3), pytest.param(300, 17, 5, marks=pytest.mark.slow)]
    + STREAM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pass_b_doubled_in_kernel_matches_jnp_oracle(l, d, B, dtype):
    """Tentpole parity for pass B in doubled mode, incl. the bitwise
    mu = 0 lane freeze across BOTH state halves."""
    X, sqn, G, alpha, L, U, gammas, i_idx = _setup_doubled(l, d, B, dtype,
                                                           seed=1)
    rng = np.random.default_rng(2)
    j_idx = jnp.asarray(rng.integers(0, 2 * l, B), jnp.int32)
    mu = jnp.asarray(rng.uniform(-0.4, 0.4, B), dtype).at[0].set(0.0)
    lanes = jnp.arange(B)
    alpha_new = jnp.clip(alpha.at[lanes, i_idx].add(mu)
                         .at[lanes, j_idx].add(-mu), L, U)
    bi, bj = i_idx % l, j_idx % l
    args = (X, sqn, G, alpha_new, L, U,
            jnp.take(X, bi, axis=0), jnp.take(sqn, bi),
            jnp.take(X, bj, axis=0), jnp.take(sqn, bj), mu, gammas)
    ref_out = ops.rbf_update_wss_batched(*args, impl="jnp", dup=True)
    pl_out = ops.rbf_update_wss_batched(*args, impl="interpret",
                                        block_l=128, dup=True)
    tol = 1e-4 if dtype == jnp.float32 else 1e-11
    np.testing.assert_allclose(np.asarray(pl_out[0]), np.asarray(ref_out[0]),
                               rtol=tol, atol=tol)
    assert [int(x) for x in pl_out[1]] == [int(x) for x in ref_out[1]]
    np.testing.assert_allclose(np.asarray(pl_out[2]), np.asarray(ref_out[2]),
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(pl_out[3]), np.asarray(ref_out[3]),
                               rtol=tol)
    np.testing.assert_array_equal(np.asarray(pl_out[0][0]), np.asarray(G[0]))


@pytest.mark.parametrize("dup", [False, True])
def test_rows_source_kernels_match_jnp(dup):
    """Gram-bank row source: the rows-variant Pallas kernels (interpret)
    equal the jnp from-rows oracle, plain and doubled."""
    l, d, B = 72, 4, 3
    dtype = jnp.float64
    if dup:
        X, sqn, G, alpha, L, U, gammas, i_idx = _setup_doubled(
            l, d, B, dtype, seed=3)
    else:
        X, sqn, G, alpha, L, U, gammas, i_idx = _setup_batched(
            l, d, B, dtype, seed=3)
    bank = jnp.stack([ref.gram(X, g) for g in np.asarray(gammas)])
    gidx = jnp.arange(B, dtype=jnp.int32)
    bi = i_idx % l if dup else i_idx
    KR = bank[gidx, bi]
    a_i, L_i, U_i = _lane(alpha, i_idx), _lane(L, i_idx), _lane(U, i_idx)
    g_i = _lane(G, i_idx)
    use_exact = jnp.asarray([True, False, True])
    aargs = (KR, G, alpha, L, U, a_i, L_i, U_i, g_i, i_idx, use_exact)
    j_ref, gain_ref = ops.row_wss_batched_rows(*aargs, impl="jnp", dup=dup)
    j_pl, gain_pl = ops.row_wss_batched_rows(*aargs, impl="interpret",
                                             block_l=128, dup=dup)
    assert [int(x) for x in j_pl] == [int(x) for x in j_ref]
    np.testing.assert_allclose(np.asarray(gain_pl), np.asarray(gain_ref),
                               rtol=1e-11)
    rng = np.random.default_rng(4)
    n = G.shape[1]
    j_idx = jnp.asarray(rng.integers(0, n, B), jnp.int32)
    mu = jnp.asarray(rng.uniform(-0.3, 0.3, B)).at[1].set(0.0)
    lanes = jnp.arange(B)
    alpha_new = jnp.clip(alpha.at[lanes, i_idx].add(mu)
                         .at[lanes, j_idx].add(-mu), L, U)
    KRj = bank[gidx, j_idx % l if dup else j_idx]
    bargs = (KR, KRj, G, alpha_new, L, U, mu)
    r_ref = ops.update_wss_batched_rows(*bargs, impl="jnp", dup=dup)
    r_pl = ops.update_wss_batched_rows(*bargs, impl="interpret",
                                       block_l=128, dup=dup)
    for a, b in zip(r_pl, r_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-11, atol=1e-12)
    np.testing.assert_array_equal(np.asarray(r_pl[0][1]), np.asarray(G[1]))


@pytest.mark.parametrize("l,d,B", STREAM_SHAPES)
@pytest.mark.parametrize("dup", [False, True])
@pytest.mark.parametrize("variant", ["masked", "conjugate"])
def test_masked_and_conjugate_passes_match_jnp_oracle(l, d, B, dup,
                                                      variant):
    """The feature-major passes under soft shrinking's active mask (pass A
    and pass B) and under Conjugate-SMO's direction (pass B) equal the jnp
    oracle, plain and doubled, including the bitwise mu = 0 lane freeze."""
    dtype = jnp.float64
    setup = _setup_doubled if dup else _setup_batched
    X, sqn, G, alpha, L, U, gammas, i_idx = setup(l, d, B, dtype, seed=5)
    n = G.shape[1]
    rng = np.random.default_rng(6)
    bi = i_idx % l
    kw = {}
    if variant == "masked":
        kw["act"] = jnp.asarray(rng.uniform(size=(B, n)) < 0.7)
        a_i, L_i, U_i = (_lane(a, i_idx) for a in (alpha, L, U))
        aargs = (X, sqn, G, alpha, L, U, jnp.take(X, bi, axis=0),
                 jnp.take(sqn, bi), a_i, L_i, U_i, _lane(G, i_idx), i_idx,
                 jnp.asarray([b % 2 == 0 for b in range(B)]), gammas)
        j_ref, gain_ref = ops.rbf_row_wss_batched(*aargs, impl="jnp",
                                                  dup=dup, **kw)
        j_pl, gain_pl = ops.rbf_row_wss_batched(*aargs, impl="interpret",
                                                block_l=128, dup=dup, **kw)
        assert [int(x) for x in j_pl] == [int(x) for x in j_ref]
        np.testing.assert_allclose(np.asarray(gain_pl),
                                   np.asarray(gain_ref), rtol=1e-11)
    else:
        base = jnp.asarray(rng.normal(size=(B, l)), dtype)
        kw["dirv"] = ref.tile_rows(base) if dup else base
        kw["mu2"] = jnp.asarray(rng.uniform(-0.2, 0.2, B), dtype).at[0].set(
            0.0)
    j_idx = jnp.asarray(rng.integers(0, n, B), jnp.int32)
    mu = jnp.asarray(rng.uniform(-0.4, 0.4, B), dtype).at[0].set(0.0)
    lanes = jnp.arange(B)
    alpha_new = jnp.clip(alpha.at[lanes, i_idx].add(mu)
                         .at[lanes, j_idx].add(-mu), L, U)
    bj = j_idx % l
    bargs = (X, sqn, G, alpha_new, L, U, jnp.take(X, bi, axis=0),
             jnp.take(sqn, bi), jnp.take(X, bj, axis=0), jnp.take(sqn, bj),
             mu, gammas)
    r_ref = ops.rbf_update_wss_batched(*bargs, impl="jnp", dup=dup, **kw)
    r_pl = ops.rbf_update_wss_batched(*bargs, impl="interpret", block_l=128,
                                      dup=dup, **kw)
    assert len(r_pl) == len(r_ref) == 4 + (variant == "conjugate")
    assert [int(x) for x in r_pl[1]] == [int(x) for x in r_ref[1]]
    for a, b in zip(r_pl, r_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-11, atol=1e-12)
    np.testing.assert_array_equal(np.asarray(r_pl[0][0]), np.asarray(G[0]))


@pytest.mark.parametrize("d", [3, 22, 130])
@pytest.mark.parametrize("dup", [False, True])
def test_pad_source_streams_x_feature_major(d, dup):
    """The passes' stream is X transposed to (round_up(d, 8), lpad), with
    zeros in the padded feature rows and example columns, and the norms
    padded alike; the row-major X the gathers read is left as it was."""
    l, B = 300, 3
    X = jnp.asarray(np.random.default_rng(7).normal(size=(l, d)))
    src = ops.pad_source(row_source.rbf_source(X, 0.5, B, dup=dup), B, 128)
    d8, lpad = -(-d // 8) * 8, -(-l // 128) * 128
    assert src.xt.shape == (d8, lpad) and src.xt_sqn.shape == (lpad,)
    xt = np.asarray(src.xt)
    np.testing.assert_array_equal(xt[:d, :l], np.asarray(X).T)
    assert not xt[d:].any() and not xt[:, l:].any()
    sqn = np.asarray(src.xt_sqn)
    np.testing.assert_array_equal(sqn[:l], np.asarray(src.sqn))
    assert not sqn[l:].any()
    assert src.X is X and src.base_l == l


def test_pallas_passes_require_the_stream():
    """An rbf source reaches a Pallas pass only with the stream
    :func:`ops.pad_source` builds: no pass pads X for itself (inside the
    engine's loop that would pad X on every trip)."""
    X, sqn, G, alpha, L, U, gammas, i_idx = _setup_batched(64, 3, 2,
                                                           jnp.float64)
    src = row_source.rbf_source(X, gammas, 2)
    lane = lambda M: _lane(M, i_idx)
    with pytest.raises(AssertionError, match="pad_source"):
        ops.source_row_wss(src, G, alpha, L, U, i_idx, lane(alpha),
                           lane(L), lane(U), lane(G),
                           jnp.asarray([True, False]), impl="interpret",
                           block_l=128)
    with pytest.raises(AssertionError, match="pad_source"):
        ops.source_update_wss(src, G, alpha, L, U, i_idx, i_idx,
                              jnp.zeros(2), impl="interpret", block_l=128)


def test_index_channel_is_exact_beyond_float32_significand():
    """Satellite regression: working-set indices travel through the int32
    side channel, never the data dtype — a float32 round-trip corrupts
    indices beyond 2^24 (the old scal packing did exactly that)."""
    big = 2 ** 24 + 1
    # the failure mode being guarded against:
    assert int(jnp.asarray(big, jnp.float32).astype(jnp.int32)) != big
    # the int channel is exact:
    np.testing.assert_array_equal(np.asarray(ops._iscal([big], 1)), [[big]])
    assert ops._iscal([big], 1).dtype == jnp.int32
    # behavioral: f32 data with the i-exclusion still selects exactly —
    # index 5 WOULD win the gain argmax (k(x_i, x_i) = 1 makes its q
    # collapse to TAU and l_vec = 50 > 0 there) if the != i_idx mask ever
    # mis-compared, so the selection below is decided by the int channel
    X, sqn, G, alpha, L, U, gamma = _setup(130, 3, jnp.float32, seed=7)
    alpha = alpha.at[5].set(0.5 * (L[5] + U[5]))   # strictly inside the box
    g_i = G[5] + 50.0
    i_idx = jnp.asarray([5], jnp.int32)
    _, j_ref, _ = ref.rbf_row_wss(X, sqn, G, alpha, L, U, X[5], alpha[5],
                                  L[5], U[5], g_i, i_idx[0],
                                  jnp.asarray(False), gamma)
    j_pl, _ = ops.rbf_row_wss_batched(
        X, sqn, G[None], alpha[None], L[None], U[None], X[5:6], sqn[5:6],
        alpha[5:6], L[5:6], U[5:6], g_i[None], i_idx, jnp.asarray([False]),
        gamma[None], impl="interpret", block_l=128)
    assert int(j_pl[0]) == int(j_ref) != 5
