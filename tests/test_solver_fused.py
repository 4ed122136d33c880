"""Fused two-pass solver vs the standard solver: same optimum, same
algorithm semantics, on both the jnp and the Pallas-interpret backends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import iter_eqns
from repro.core import qp as qp_mod
from repro.core.solver import SolverConfig, solve
from repro.core.solver_fused import solve_fused, solve_fused_batched_qp
from repro.svm.data import gaussian_blobs, ring, xor_gaussians


def _problem(name, n, seed=0):
    gen = {"blobs": gaussian_blobs, "ring": ring, "xor": xor_gaussians}[name]
    X, y = gen(n, seed=seed)
    gamma = {"blobs": 0.05, "ring": 1.0, "xor": 0.5}[name]
    C = {"blobs": 1.0, "ring": 10.0, "xor": 100.0}[name]
    return X, y, C, gamma


@pytest.mark.parametrize("alg", ["smo", "pasmo"])
@pytest.mark.parametrize("name", [
    "blobs", pytest.param("ring", marks=pytest.mark.slow), "xor"])
def test_fused_jnp_matches_standard(alg, name):
    X, y, C, gamma = _problem(name, 64)
    cfg = SolverConfig(algorithm=alg, eps=1e-4, max_iter=100_000)
    rf = solve_fused(jnp.asarray(X), jnp.asarray(y), C, gamma, cfg,
                     impl="jnp")
    rs = solve(qp_mod.make_rbf(jnp.asarray(X), gamma), jnp.asarray(y), C,
               cfg)
    assert bool(rf.converged) and bool(rs.converged)
    np.testing.assert_allclose(float(rf.objective), float(rs.objective),
                               rtol=1e-6)
    assert float(rf.kkt_gap) <= 1e-4 + 1e-12
    # same algorithm: planning engages on both or neither
    if alg == "pasmo" and int(rs.n_planning) > 10:
        assert int(rf.n_planning) > 0


@pytest.mark.parametrize("alg", ["smo", "pasmo"])
def test_fused_pallas_interpret_matches_jnp(alg):
    """The Pallas kernels inside the full solve loop (interpret mode)."""
    X, y, C, gamma = _problem("xor", 64, seed=1)
    cfg = SolverConfig(algorithm=alg, eps=1e-3, max_iter=20_000)
    r_jnp = solve_fused(jnp.asarray(X), jnp.asarray(y), C, gamma, cfg,
                        impl="jnp")
    r_pl = solve_fused(jnp.asarray(X), jnp.asarray(y), C, gamma, cfg,
                       impl="interpret", block_l=128)
    assert bool(r_pl.converged)
    np.testing.assert_allclose(float(r_pl.objective), float(r_jnp.objective),
                               rtol=1e-6)
    assert abs(int(r_pl.iterations) - int(r_jnp.iterations)) <= max(
        3, 0.05 * int(r_jnp.iterations))


def test_fused_feasible():
    X, y, C, gamma = _problem("ring", 70, seed=2)
    cfg = SolverConfig(algorithm="pasmo", eps=1e-4)
    r = solve_fused(jnp.asarray(X), jnp.asarray(y), C, gamma, cfg,
                    impl="jnp")
    bounds = qp_mod.make_bounds(jnp.asarray(y), C)
    assert bool(qp_mod.is_feasible(r.alpha, bounds, atol=1e-8))
    # maintained gradient equals y - K alpha
    K = qp_mod.materialize(qp_mod.make_rbf(jnp.asarray(X), gamma))
    np.testing.assert_allclose(np.asarray(r.G),
                               y - np.asarray(K) @ np.asarray(r.alpha),
                               rtol=1e-7, atol=1e-7)


# ---------------------------------------------------------------------------
# Lane-batched engine on the Pallas path: X padded once per call
# ---------------------------------------------------------------------------

# l is not a multiple of the l block and d not one of 128, so both of X's
# axes need padding; three l blocks, the last one ragged
RAGGED_L, RAGGED_D, RAGGED_B, RAGGED_BLOCK = 40, 5, 3, 16


def _ragged_batch(mode):
    """(X, P, L, U, gammas), cfg and engine keywords for one engine mode."""
    rng = np.random.default_rng(4)
    l, B = RAGGED_L, RAGGED_B
    X = jnp.asarray(rng.normal(size=(l, RAGGED_D)))
    gam = jnp.asarray(rng.uniform(0.3, 1.0, B))
    kw = {}
    if mode == "doubled":
        qp = qp_mod.svr_qp(jnp.asarray(rng.normal(size=(l,))), 2.0, 0.1)
        P, L, U = (jnp.broadcast_to(v, (B, 2 * l)) for v in (
            qp.p, qp.bounds.lower, qp.bounds.upper))
        kw["doubled"] = True
    else:
        P = jnp.asarray(np.sign(rng.normal(size=(B, l))))
        L, U = jnp.minimum(0.0, 2.0 * P), jnp.maximum(0.0, 2.0 * P)
    if mode == "shrinking":
        kw["shrinking"] = True
    cfg = (SolverConfig(algorithm="smo", step="conjugate", eps=1e-3,
                        max_iter=2_000)
           if mode == "conjugate" else
           SolverConfig(eps=1e-3, max_iter=2_000, shrink_every=5))
    return (X, P, L, U, gam), cfg, kw


@pytest.mark.parametrize("mode", ["plain", "doubled", "shrinking",
                                  "conjugate"])
def test_x_is_padded_once_before_the_loop(mode):
    """No pad of X (l, d) or of its norms (l,) inside the while body.

    The check reads the jaxpr: XLA's CPU compiler hoists a loop-invariant
    pad out of the loop by itself, the TPU's does not, so compiled CPU HLO
    would pass either way.
    """
    args, cfg, kw = _ragged_batch(mode)
    fn = lambda *a: solve_fused_batched_qp(
        *a, cfg, impl="interpret", block_l=RAGGED_BLOCK, **kw)
    x_shapes = {(RAGGED_L, RAGGED_D), (RAGGED_L,)}
    pads = [("while" in path, tuple(eqn.invars[0].aval.shape))
            for path, eqn in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pad"]
    assert [sh for in_loop, sh in pads if in_loop and sh in x_shapes] == []
    assert {sh for in_loop, sh in pads if not in_loop} >= x_shapes
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert "x_pad" in text


@pytest.mark.parametrize("mode", ["plain", "doubled"])
def test_batched_interpret_matches_jnp_at_ragged_l(mode):
    """The padded X keeps the true l: the doubled operator's second half
    starts at l (not at the padded length), and every lane's gradient is
    exactly P - Q alpha over the true examples."""
    args, cfg, kw = _ragged_batch(mode)
    X, P, L, U, gam = args
    r_jnp = solve_fused_batched_qp(*args, cfg, impl="jnp", **kw)
    r_int = solve_fused_batched_qp(*args, cfg, impl="interpret",
                                   block_l=RAGGED_BLOCK, **kw)
    assert bool(jnp.all(r_int.converged))
    np.testing.assert_allclose(np.asarray(r_int.objective),
                               np.asarray(r_jnp.objective), rtol=1e-6)
    Xn = np.asarray(X)
    d2 = ((Xn[:, None, :] - Xn[None, :, :]) ** 2).sum(-1)
    K = np.exp(-np.asarray(gam)[:, None, None] * d2)          # (B, l, l)
    a = np.asarray(r_int.alpha)
    if mode == "doubled":
        Qa = np.einsum("bij,bj->bi", K, a[:, :RAGGED_L] + a[:, RAGGED_L:])
        Qa = np.concatenate([Qa, Qa], axis=1)
    else:
        Qa = np.einsum("bij,bj->bi", K, a)
    np.testing.assert_allclose(np.asarray(r_int.G), np.asarray(P) - Qa,
                               rtol=1e-7, atol=1e-7)
