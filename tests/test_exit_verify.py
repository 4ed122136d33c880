"""The fused engine's exit check: a lane stops only when its KKT gap,
recomputed from exact rows of ``G = p - Q alpha``, is at most eps.

(a) a warm start whose carried gradient is planted to read a gap below
eps, while the exact gap is above it, is caught, reopened and solved;
(b) the exact rows agree with float64 to eps / 20 in float32;
(c) in float64 nothing reopens, and iterations are those of the engine
before the check existed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qp as qp_mod
from repro.core.solver import SolverConfig
from repro.core.solver_fused import (VERIFY_ROWS, solve_fused_batched,
                                     solve_fused_batched_qp)
from repro.kernels import row_source
from repro.kernels.ref import rbf_exp
from repro.svm.data import chessboard, gaussian_blobs

EPS = 1e-3
TOL = EPS / 20        # what the exact rows may be off by


def _gram(X, gamma):
    X = np.asarray(X, np.float64)
    sq = np.einsum("ij,ij->i", X, X)
    return np.exp(-gamma * np.maximum(
        sq[:, None] + sq[None, :] - 2.0 * X @ X.T, 0.0))


def _ends(G, alpha, L, U):
    """Float64 (gap, b) of one lane: LIBSVM's ends of the gap."""
    g_up = np.where(alpha < U, G, -np.inf).max()
    g_dn = np.where(alpha > L, G, np.inf).min()
    return g_up - g_dn, 0.5 * (g_up + g_dn)


def _problem(kind, B):
    """(X, P, L, U, gammas, Q per lane) with l = 200 rows, so the check
    sees fewer rows than a lane has."""
    X, y = gaussian_blobs(200, seed=5, d=3, sep=1.0)
    gam = np.array([0.5, 1.0, 0.25])[:B]
    if kind == "doubled":
        rng = np.random.default_rng(2)
        t = np.sinc(X[:, 0]) + 0.1 * rng.normal(size=200)
        qp = qp_mod.svr_qp(jnp.asarray(t), 2.0, 0.05)
        P, L, U = (np.broadcast_to(np.asarray(v), (B, 400))
                   for v in (qp.p, qp.bounds.lower, qp.bounds.upper))
        Qs = [np.tile(_gram(X, g), (2, 2)) for g in gam]
    else:
        C = np.array([2.0, 8.0, 0.5])[:B, None]
        P = np.broadcast_to(y, (B, 200))
        L, U = np.minimum(0.0, P * C), np.maximum(0.0, P * C)
        Qs = [_gram(X, g) for g in gam]
    return X, P, L, U, gam, Qs


def _planted_start(kind, B):
    """A loose solve's alpha with its exact G, and G0 = that G with the
    rows beyond a band of 0.9 eps around each lane's midpoint pulled into
    the band: the carried gap reads 0.9 eps, the exact one more than eps."""
    X, P, L, U, gam, Qs = _problem(kind, B)
    loose = solve_fused_batched_qp(
        X, P, L, U, gam, SolverConfig(eps=1.5 * EPS, max_iter=100_000),
        impl="jnp", doubled=kind == "doubled")
    alpha = np.asarray(loose.alpha)
    G = np.stack([P[k] - Qs[k] @ alpha[k] for k in range(B)])
    G0 = G.copy()
    for k in range(B):
        gap, c = _ends(G[k], alpha[k], L[k], U[k])
        assert gap > EPS
        up, dn = alpha[k] < U[k], alpha[k] > L[k]
        G0[k] = np.where(up, np.minimum(G0[k], c + 0.45 * EPS), G0[k])
        G0[k] = np.where(dn, np.maximum(G0[k], c - 0.45 * EPS), G0[k])
        assert _ends(G0[k], alpha[k], L[k], U[k])[0] < EPS
        # a few end rows: all within one check's reach
        assert 1 <= np.sum(G0[k] != G[k]) <= VERIFY_ROWS
    return X, P, L, U, gam, Qs, alpha, G0


@pytest.mark.parametrize("kind,impl,B,shrinking", [
    ("plain", "jnp", 1, False), ("plain", "jnp", 3, False),
    ("plain", "jnp", 1, True), ("plain", "jnp", 3, True),
    ("plain", "interpret", 1, False), ("plain", "interpret", 3, False),
    ("plain", "interpret", 3, True), ("doubled", "jnp", 1, False),
    ("doubled", "jnp", 3, True), ("doubled", "interpret", 1, False),
])
def test_planted_drift_is_caught_and_corrected(kind, impl, B, shrinking):
    X, P, L, U, gam, Qs, alpha0, G0 = _planted_start(kind, B)
    kw = {"block_l": 64} if impl == "interpret" else {}
    res = solve_fused_batched_qp(
        X, P, L, U, gam, SolverConfig(eps=EPS, max_iter=100_000),
        impl=impl, alpha0=alpha0, G0=G0, doubled=kind == "doubled",
        shrinking=shrinking, **kw)
    assert np.all(np.asarray(res.converged))
    assert np.all(np.asarray(res.n_unshrink) >= 1)        # it reopened
    for k in range(B):
        a = np.asarray(res.alpha[k])
        G = P[k] - Qs[k] @ a                              # float64 truth
        gap, b = _ends(G, a, L[k], U[k])
        assert gap <= EPS
        np.testing.assert_allclose(float(res.kkt_gap[k]), gap, atol=TOL)
        np.testing.assert_allclose(float(res.b[k]), b, atol=TOL)


def _solved_alpha(d, kind):
    """A float64 solve at l = 2,048, C = 10: alpha and the float32
    problem it is read back in."""
    X, y = gaussian_blobs(2048, seed=1, d=d, sep=2.0)
    X32 = np.asarray(X, np.float32)
    if kind == "shifted":                 # far from the origin
        X32 = X32 + np.float32(100.0)
    gamma = 1.0 / (d * np.asarray(X32, np.float64).var())
    res = solve_fused_batched(X32.astype(np.float64), y[None], 10.0, gamma,
                              SolverConfig(eps=EPS, max_iter=200_000),
                              impl="jnp")
    return X32, y, np.float32(gamma), np.asarray(res.alpha[0], np.float32)


@pytest.mark.parametrize("d,kind", [
    (22, "rbf"), (780, "rbf"), (22, "shifted"), (22, "bank"),
    (22, "doubled"),
])
def test_exact_rows_agree_with_float64(d, kind):
    X32, y, gamma, a = _solved_alpha(d, kind)
    Q64 = _gram(X32, np.float64(gamma))
    v = a
    if kind == "bank":
        gram = jnp.asarray(Q64.astype(np.float32))[None]
        src = row_source.bank_source(gram, jnp.zeros((1,), jnp.int32))
        Q64 = np.asarray(gram[0], np.float64)
    else:
        src = row_source.rbf_source(jnp.asarray(X32), gamma, 1,
                                    dup=kind == "doubled")
    if kind == "doubled":             # the folded halves: a+ and -a-
        half = np.where(a > 0, a, 0).astype(np.float32)
        v = np.concatenate([half, (a - half).astype(np.float32)])
        Q64 = np.tile(Q64, (2, 2))
    G32 = y - np.asarray(src.matvec(jnp.asarray(v)[None])[0, :2048],
                         np.float64)
    up, dn = (np.where(c, G32, -np.inf) for c in (a < 10 * (y > 0),
                                                   a > -10 * (y < 0)))
    rows = np.concatenate([np.argsort(-up)[:VERIFY_ROWS],
                           np.argsort(dn)[:VERIFY_ROWS]])
    if kind == "doubled":
        rows = np.concatenate([rows, rows + 2048])
    got = jax.jit(lambda r, v: src.exact_rows(r[None], v[None])[0])(
        jnp.asarray(rows, jnp.int32), jnp.asarray(v))
    assert got.dtype == jnp.float32
    want = Q64[rows] @ np.asarray(v, np.float64)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=0, atol=TOL)


def test_exact_sum_keeps_what_float32_sums_lose():
    """Block sums of 2**30, 1 and -2**30, each exact in float32: added in
    float32 the 1 is lost, the compensated sum across blocks keeps it, so
    the error does not grow with the number of blocks."""
    w = row_source.EXACT_BLOCK
    K = np.ones((2, 3 * w), np.float32)
    v = np.zeros(3 * w, np.float32)
    v[:w] = 2.0 ** 20
    v[w] = 1.0
    v[2 * w:] = -(2.0 ** 20)
    got = jax.jit(lambda v: row_source._block_sum(
        lambda c, n: jax.lax.dynamic_slice_in_dim(jnp.asarray(K), c, n, 1),
        v, 2))(jnp.asarray(v))
    plain = (np.float32(2.0 ** 30) + np.float32(1.0)) - np.float32(2.0 ** 30)
    assert plain == 0.0
    np.testing.assert_array_equal(np.asarray(got), [1.0, 1.0])


@pytest.mark.parametrize("lo,hi", [(-1e-6, 0.0), (-1.0, 0.0), (-20.0, -1.0),
                                   (-87.0, -20.0)])
def test_rbf_exp_is_float32_accurate(lo, hi):
    """The kernel entries' exp: within 2 ulp of float64 and unbiased, where
    the TPU's own float32 exp reads up to 5e-6 off and 8e-7 low."""
    z = np.linspace(lo, hi, 100_003).astype(np.float32)
    got = np.asarray(jax.jit(rbf_exp)(jnp.asarray(z)), np.float64)
    want = np.exp(z.astype(np.float64))
    rel = (got - want) / want
    assert np.abs(rel).max() <= 2 * 2.0 ** -23
    assert abs(rel.mean()) <= 1e-8
    assert float(rbf_exp(jnp.zeros((), jnp.float32))) == 1.0


# per-lane iterations of the engine before the exit check, chess-board
# (l = 300, seed 3), C = (1, 10, 100), gamma = (0.5, 1, 2), eps = 1e-3
PARENT_ITERS = {("pasmo", "jnp"): [187, 843, 1789],
                ("pasmo", "interpret"): [187, 859, 1635],
                ("smo", "jnp"): [179, 891, 1549],
                ("smo", "interpret"): [179, 813, 1544]}


@pytest.mark.parametrize("algorithm,impl", sorted(PARENT_ITERS))
def test_no_false_reopen_in_float64(algorithm, impl):
    X, y = chessboard(300, seed=3)
    Y = np.broadcast_to(np.asarray(y, float), (3, 300))
    kw = {"block_l": 128} if impl == "interpret" else {}
    res = solve_fused_batched(
        X, Y, np.array([1.0, 10.0, 100.0]), np.array([0.5, 1.0, 2.0]),
        SolverConfig(algorithm=algorithm, eps=EPS, max_iter=200_000),
        impl=impl, **kw)
    assert res.kkt_gap.dtype == jnp.float64
    assert np.all(np.asarray(res.converged))
    assert np.asarray(res.n_unshrink).tolist() == [0, 0, 0]
    assert (np.asarray(res.iterations).tolist()
            == PARENT_ITERS[(algorithm, impl)])
