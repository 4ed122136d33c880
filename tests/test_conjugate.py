"""Conjugate-SMO (``SolverConfig.step == "conjugate"``) differential tests.

The conjugate mode replaces the planning-ahead 2x2 *lookahead* with the
Conjugate-SMO 2-direction *solve* (current WSS direction + the carried
previous direction), falling back to plain clipped SMO whenever the
carried direction is invalid.  The contract under test:

* same optimum as SMO / PA-SMO (objective parity at eps scale),
* strictly fewer iterations than PA-SMO on the chess-board problem,
* the accept/reject machinery is bitwise-transparent on frozen lanes and
  composes with soft shrinking and warm-start resumes,
* with ``step="plain"`` nothing changes — the structural golden pins the
  conjugate trace itself (``tests/golden/structural.json``).
"""

import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FUSED_KW, run_multidevice
from repro.analysis import jaxpr_audit
from repro.core import grid as grid_mod
from repro.core import qp as qp_mod
from repro.core.solver import SolverConfig, solve
from repro.core.solver_fused import (solve_fused, solve_fused_batched,
                                     solve_fused_batched_qp)
from repro.svm.data import chessboard, gaussian_blobs


SMO = dict(algorithm="smo")
PASMO = dict(algorithm="pasmo")
CONJ = dict(algorithm="smo", step="conjugate")


def _chessboard_problem(n=240, seed=0):
    X, y = chessboard(n, seed=seed)
    return jnp.asarray(X), jnp.asarray(y), 1000.0, 0.5


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_conjugate_requires_plain_smo_base():
    with pytest.raises(AssertionError, match="algorithm='smo'"):
        SolverConfig(algorithm="pasmo", step="conjugate")
    with pytest.raises(AssertionError):
        SolverConfig(step="newton")


def test_single_lane_fused_rejects_conjugate():
    X, y, C, gamma = _chessboard_problem(n=32)
    cfg = SolverConfig(eps=1e-3, max_iter=100, **CONJ)
    with pytest.raises(AssertionError, match="lane-batched"):
        solve_fused(X, y, C, gamma, cfg, impl="jnp")


# ---------------------------------------------------------------------------
# classic engine: the differential claim (mirrors test_differential.py)
# ---------------------------------------------------------------------------

def test_classic_conjugate_fewer_iterations_than_pasmo_on_chessboard():
    """Conjugate directions beat the planning lookahead on the paper's
    hard problem: strictly fewer iterations than PA-SMO (which itself
    beats plain SMO), at the same optimum."""
    X, y, C, gamma = _chessboard_problem()
    kern = qp_mod.make_rbf(X, gamma)
    cfg = dict(eps=1e-3, max_iter=500_000)
    r_pa = solve(kern, y, C, SolverConfig(**PASMO, **cfg))
    r_cj = solve(kern, y, C, SolverConfig(**CONJ, **cfg))
    assert bool(r_pa.converged) and bool(r_cj.converged)
    assert int(r_cj.iterations) < int(r_pa.iterations)
    # the 2-direction step must actually engage, and often
    assert int(r_cj.n_planning) > int(r_cj.iterations) // 4
    f_pa, f_cj = float(r_pa.objective), float(r_cj.objective)
    assert abs(f_cj - f_pa) <= 1e-6 * (1.0 + abs(f_pa))


# ---------------------------------------------------------------------------
# fused engine: parity + iteration win (jnp in tier 1, interpret in the
# nightly leg via FUSED_KW)
# ---------------------------------------------------------------------------

def test_fused_conjugate_fewer_iterations_than_pasmo_on_chessboard():
    X, y, C, gamma = _chessboard_problem()
    cfg = dict(eps=1e-3, max_iter=500_000)
    r_pa = solve_fused_batched(X, y[None], C, gamma,
                               SolverConfig(**PASMO, **cfg), **FUSED_KW)
    r_cj = solve_fused_batched(X, y[None], C, gamma,
                               SolverConfig(**CONJ, **cfg), **FUSED_KW)
    assert bool(r_pa.converged[0]) and bool(r_cj.converged[0])
    assert int(r_cj.iterations[0]) < int(r_pa.iterations[0])
    assert int(r_cj.n_planning[0]) > 0
    f_pa, f_cj = float(r_pa.objective[0]), float(r_cj.objective[0])
    assert abs(f_cj - f_pa) <= 1e-6 * (1.0 + abs(f_pa))


@pytest.mark.parametrize("data", ["chessboard", "blobs"])
def test_fused_conjugate_grid_objective_parity(data):
    """Conjugate vs PA-SMO on a small (C, gamma) grid: every grid point
    reaches the same dual optimum to 1e-6 relative."""
    if data == "chessboard":
        Xn, y = chessboard(160, seed=0)
        Cs, gammas = np.array([1.0, 10.0]), np.array([0.5, 1.0])
    else:
        Xn, y = gaussian_blobs(120, seed=0)
        Cs, gammas = np.array([0.5, 2.0]), np.array([0.05, 0.2])
    X = jnp.asarray(Xn)
    Y = jnp.asarray(y)[None, :]
    cfg = dict(eps=1e-4, max_iter=200_000)
    r_pa = grid_mod.solve_grid(X, Y, Cs, gammas,
                               SolverConfig(**PASMO, **cfg), **FUSED_KW)
    r_cj = grid_mod.solve_grid(X, Y, Cs, gammas,
                               SolverConfig(**CONJ, **cfg), **FUSED_KW)
    assert bool(np.all(np.asarray(r_pa.converged)))
    assert bool(np.all(np.asarray(r_cj.converged)))
    f_pa = np.asarray(r_pa.objective)
    f_cj = np.asarray(r_cj.objective)
    np.testing.assert_array_less(np.abs(f_cj - f_pa),
                                 1e-6 * (1.0 + np.abs(f_pa)))


# ---------------------------------------------------------------------------
# lane freeze / warm starts / shrinking
# ---------------------------------------------------------------------------

def test_conjugate_lane_freeze_is_bitwise():
    """A lane that converges early must be bitwise frozen while the
    straggler lane keeps iterating: rejected-or-frozen lanes take
    mu = mu2 = 0, so pass B is a no-op on their state."""
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(48, 3)))
    y = jnp.asarray(np.where(rng.normal(size=48) >= 0, 1.0, -1.0))
    Y = jnp.stack([y, -y])
    C = jnp.asarray([0.1, 50.0])         # lane 0 converges far earlier
    cfg = SolverConfig(eps=1e-4, max_iter=5_000, **CONJ)
    res = solve_fused_batched(X, Y, C, 0.8, cfg, **FUSED_KW)
    assert bool(np.all(np.asarray(res.converged)))
    it = np.asarray(res.iterations)
    assert it[0] < it[1]
    # rerun with a budget that stops between the two lanes' freeze points:
    # lane 0's state must already be bitwise final
    cfg_cut = SolverConfig(eps=1e-4, max_iter=int(it[0]) + 1, **CONJ)
    cut = solve_fused_batched(X, Y, C, 0.8, cfg_cut, **FUSED_KW)
    assert bool(cut.converged[0]) and not bool(cut.converged[1])
    np.testing.assert_array_equal(np.asarray(cut.alpha[0]),
                                  np.asarray(res.alpha[0]))
    np.testing.assert_array_equal(np.asarray(cut.G[0]),
                                  np.asarray(res.G[0]))
    assert float(cut.b[0]) == float(res.b[0])


def test_conjugate_warm_start_resume_parity():
    """Stopping mid-run and resuming from (alpha, G) — the chunked-driver
    seam; the conjugate direction history resets at the boundary — lands
    on the same optimum as the uninterrupted solve."""
    X, y, C, gamma = _chessboard_problem(n=160)
    cfg_kw = dict(eps=1e-3, **CONJ)
    full = solve_fused_batched(X, y[None], C, gamma,
                               SolverConfig(max_iter=500_000, **cfg_kw),
                               **FUSED_KW)
    assert bool(full.converged[0])
    part = solve_fused_batched(X, y[None], C, gamma,
                               SolverConfig(max_iter=500, **cfg_kw),
                               **FUSED_KW)
    assert not bool(part.converged[0])
    resumed = solve_fused_batched(X, y[None], C, gamma,
                                  SolverConfig(max_iter=500_000, **cfg_kw),
                                  alpha0=part.alpha, G0=part.G, **FUSED_KW)
    assert bool(resumed.converged[0])
    f_full = float(full.objective[0])
    f_res = float(resumed.objective[0])
    assert abs(f_res - f_full) <= 1e-6 * (1.0 + abs(f_full))
    # the chunked grid driver exercises the same resume seam in-loop
    comp = grid_mod.solve_grid_compacted(
        X, y[None], np.array([C]), np.array([gamma]),
        SolverConfig(max_iter=500_000, **cfg_kw), chunk=700, **FUSED_KW)
    assert bool(comp.converged[0, 0, 0])
    f_c = float(comp.objective[0, 0, 0])
    assert abs(f_c - f_full) <= 1e-6 * (1.0 + abs(f_full))


def test_conjugate_composes_with_shrinking():
    """Soft shrinking + conjugate: the direction resets on mask refreshes
    and unshrink events, and the optimum matches the unshrunk run."""
    X, y, C, gamma = _chessboard_problem(n=200)
    cfg = SolverConfig(eps=1e-3, max_iter=500_000, **CONJ)
    base = solve_fused_batched(X, y[None], C, gamma, cfg, **FUSED_KW)
    shr = solve_fused_batched(X, y[None], C, gamma, cfg, shrinking=True,
                              **FUSED_KW)
    assert bool(base.converged[0]) and bool(shr.converged[0])
    assert int(shr.n_planning[0]) > 0
    f_b, f_s = float(base.objective[0]), float(shr.objective[0])
    assert abs(f_s - f_b) <= 1e-6 * (1.0 + abs(f_b))


# ---------------------------------------------------------------------------
# doubled (ε-SVR) lanes + facades
# ---------------------------------------------------------------------------

def test_conjugate_doubled_svr_lane_parity():
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(60, 2)))
    y = jnp.sin(X[:, 0]) + 0.1 * jnp.asarray(rng.normal(size=60))
    qp = qp_mod.svr_qp(y, 2.0, 0.05)
    cfg = dict(eps=1e-4, max_iter=100_000)
    kw = dict(doubled=True, **FUSED_KW)
    r_pa = solve_fused_batched_qp(
        X, qp.p[None], qp.bounds.lower[None], qp.bounds.upper[None], 0.7,
        SolverConfig(**PASMO, **cfg), **kw)
    r_cj = solve_fused_batched_qp(
        X, qp.p[None], qp.bounds.lower[None], qp.bounds.upper[None], 0.7,
        SolverConfig(**CONJ, **cfg), **kw)
    assert bool(r_pa.converged[0]) and bool(r_cj.converged[0])
    assert int(r_cj.n_planning[0]) > 0
    f_pa, f_cj = float(r_pa.objective[0]), float(r_cj.objective[0])
    assert abs(f_cj - f_pa) <= 1e-6 * (1.0 + abs(f_pa))


def test_facades_thread_the_step_knob():
    from repro.svm import SVC, SVR, OneClassSVM
    from repro.telemetry import Diagnostics, RingConfig
    rng = np.random.default_rng(2)
    X = rng.normal(size=(50, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    d = Diagnostics(ring=RingConfig(sample_every=8))
    clf = SVC(C=2.0, gamma=0.7, algorithm="smo", step="conjugate",
              impl=FUSED_KW["impl"], diagnostics=d).fit(X, y)
    ref = SVC(C=2.0, gamma=0.7, algorithm="smo",
              impl=FUSED_KW["impl"]).fit(X, y)
    assert clf.score(X, y) == ref.score(X, y)
    f_c = float(clf.fit_result_.objective)
    f_r = float(ref.fit_result_.objective)
    assert abs(f_c - f_r) <= 1e-6 * (1.0 + abs(f_r))
    # the accepted-conjugate-step share rides the PR-8 lane-event seam
    assert len(d.lanes) == 1
    rec = d.lanes[0]
    assert rec["n_planning"] == int(clf.fit_result_.n_planning)
    assert rec["accepted_step_share"] == pytest.approx(
        rec["n_planning"] / rec["iterations"])

    yr = np.sin(X[:, 0])
    reg = SVR(C=2.0, epsilon=0.1, gamma=0.7, algorithm="smo",
              step="conjugate", impl=FUSED_KW["impl"]).fit(X, yr)
    reg_ref = SVR(C=2.0, epsilon=0.1, gamma=0.7, algorithm="smo",
                  impl=FUSED_KW["impl"]).fit(X, yr)
    f_g = float(reg.fit_result_.objective)
    f_gr = float(reg_ref.fit_result_.objective)
    assert abs(f_g - f_gr) <= 1e-6 * (1.0 + abs(f_gr))
    np.testing.assert_allclose(np.asarray(reg.predict(X)),
                               np.asarray(reg_ref.predict(X)),
                               atol=5e-3)  # eps=1e-3 stopping slack

    oc = OneClassSVM(nu=0.3, gamma=0.7, algorithm="smo", step="conjugate",
                     impl=FUSED_KW["impl"]).fit(X)
    oc_ref = OneClassSVM(nu=0.3, gamma=0.7, algorithm="smo",
                         impl=FUSED_KW["impl"]).fit(X)
    f_o = float(oc.fit_result_.objective)
    f_or = float(oc_ref.fit_result_.objective)
    assert abs(f_o - f_or) <= 1e-6 * (1.0 + abs(f_or))


# ---------------------------------------------------------------------------
# trace stability: the conjugate entries of the structural golden
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", [
    "conjugate_jnp",
    "conjugate_interpret",
])
def test_conjugate_jaxpr_structure_matches_golden(entry):
    # structural audit against tests/golden/structural.json (see
    # test_telemetry.py)
    jaxpr_audit.assert_structural(entry)


# ---------------------------------------------------------------------------
# sharded lanes (the multidevice CI leg)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_conjugate_matches_batched_multidevice():
    out = run_multidevice(textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        import numpy as np
        from repro.core.sharded_lanes import solve_fused_sharded
        from repro.core.solver_fused import solve_fused_batched
        from repro.core.solver import SolverConfig

        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.normal(size=(24, 3)))
        y = jnp.asarray(np.where(rng.normal(size=24) >= 0, 1.0, -1.0))
        Y = jnp.stack([y, -y])
        cfg = SolverConfig(algorithm="smo", step="conjugate", eps=1e-3,
                           max_iter=2000)
        rs = solve_fused_sharded(X, Y, 2.0, 0.8, cfg, impl="jnp")
        rb = solve_fused_batched(X, Y, 2.0, 0.8, cfg, impl="jnp")
        assert np.array_equal(np.asarray(rs.iterations),
                              np.asarray(rb.iterations))
        assert np.array_equal(np.asarray(rs.n_planning),
                              np.asarray(rb.n_planning))
        np.testing.assert_allclose(np.asarray(rs.alpha),
                                   np.asarray(rb.alpha),
                                   rtol=1e-12, atol=0)
        print("SHARDED_CONJ_OK")
    """), n_devices=2)
    assert "SHARDED_CONJ_OK" in out
