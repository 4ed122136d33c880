"""Flight recorder (``repro.telemetry``): rings, sinks, drivers, report.

The load-bearing guarantee is structural: with ``telemetry=None`` the
fused engine must trace a jaxpr BYTE-IDENTICAL to the pre-telemetry
engine — the recorder is free when off, not merely cheap.  The goldens
under ``tests/golden/fused_jaxpr_*.txt`` were captured from the engine
BEFORE the telemetry seam existed; their first line records the jax
version that printed them (jaxpr pretty-printing is not stable across
jax versions, so the byte comparison only runs on a matching version —
the CI floor pin — and other versions fall back to a structural check).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FUSED_KW, run_multidevice
from repro.analysis import jaxpr_audit
from repro.core import grid as grid_mod
from repro.core.solver import SolverConfig, solve
from repro.core.solver_fused import solve_fused_batched, solve_fused_batched_qp
from repro.core import qp as qp_mod
from repro.launch import telemetry_report as report_mod
from repro.telemetry import (Diagnostics, JsonlSink, RingConfig,
                             env_fingerprint, fingerprint_diff, phase_scope,
                             read_jsonl, ring_init, ring_update)



def _rbf_problem(B=3, l=16, d=4, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(l, d)))
    Y = jnp.asarray(np.sign(rng.normal(size=(B, l))))
    C = 2.0
    YC = Y * C
    L, U = jnp.minimum(0.0, YC), jnp.maximum(0.0, YC)
    gam = jnp.asarray(rng.uniform(0.3, 1.0, B))
    return X, Y, L, U, gam


def _capture_jaxpr(**kw) -> str:
    """In-process jaxpr capture for structural (not byte-level) checks."""
    X, P, L, U, gam = _rbf_problem()
    cfg = SolverConfig(eps=1e-3, max_iter=500)
    return str(jax.make_jaxpr(
        lambda X, P, L, U, g: solve_fused_batched_qp(
            X, P, L, U, g, cfg, **kw))(X, P, L, U, gam))


# ---------------------------------------------------------------------------
# telemetry=None is structurally free
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", [
    "plain_jnp",
    "plain_shrink_jnp",
    "plain_interpret",
])
def test_jaxpr_structure_matches_pretelemetry_golden(entry):
    # structural audit (eqn-primitive multiset + while-carry pytree)
    # against tests/golden/structural.json; the carry check runs on
    # every jax version, the primitive multiset only on the pinned one.
    jaxpr_audit.assert_structural(entry)


def test_jaxpr_off_is_invariant_to_telemetry_use():
    # version-independent structural check: tracing a telemetry-on solve
    # must not perturb the telemetry-off jaxpr (no cache/trace bleed),
    # and the on-jaxpr must be strictly larger (the ring rides the carry)
    before = _capture_jaxpr(impl="jnp")
    on = _capture_jaxpr(impl="jnp", telemetry=RingConfig())
    after = _capture_jaxpr(impl="jnp")
    assert before == after
    assert len(on) > len(before)


def test_telemetry_does_not_perturb_the_solve():
    X, Y, L, U, gam = _rbf_problem()
    cfg = SolverConfig(eps=1e-3, max_iter=500)
    base = solve_fused_batched_qp(X, Y, L, U, gam, cfg, **FUSED_KW)
    res, ring = solve_fused_batched_qp(X, Y, L, U, gam, cfg,
                                       telemetry=RingConfig(sample_every=8),
                                       **FUSED_KW)
    assert np.array_equal(np.asarray(base.iterations),
                          np.asarray(res.iterations))
    assert np.array_equal(np.asarray(base.converged),
                          np.asarray(res.converged))
    np.testing.assert_allclose(np.asarray(base.alpha), np.asarray(res.alpha),
                               rtol=1e-12, atol=0)
    # every lane ends with a forced freeze sample at t = iterations - 1
    ns = np.asarray(ring.n_samples)
    t = np.asarray(ring.t)
    for lane in range(3):
        assert t[lane, min(ns[lane], 128) - 1] == \
            int(res.iterations[lane]) - 1


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_ring_overflow_oldest_wins():
    cfg = RingConfig(sample_every=1, cap=4, ratio_cap=3)
    ring = ring_init(cfg, 2, jnp.float64)
    on = jnp.ones(2, bool)
    off = jnp.zeros(2, bool)
    for t in range(7):
        ring = ring_update(
            ring, cfg, t=jnp.asarray(t), active=on, newly_done=off,
            gap=jnp.full(2, 10.0 - t), n_active=jnp.full(2, 5, jnp.int32),
            n_unshrink=jnp.zeros(2, jnp.int32),
            plan_event=jnp.asarray([True, False]),
            ratio=jnp.full(2, 1.0 + t))
    # count free-runs past cap (overflow detectable)...
    assert np.all(np.asarray(ring.n_samples) == 7)
    # ...first cap-1 slots keep the OLDEST samples verbatim...
    assert np.asarray(ring.t)[0, :3].tolist() == [0, 1, 2]
    # ...and the last slot holds the NEWEST
    assert np.asarray(ring.t)[0, 3] == 6
    assert np.asarray(ring.gap)[0, 3] == 4.0
    # event channel: lane 0 got 7 events into cap 3, lane 1 none
    assert np.asarray(ring.n_ratio).tolist() == [7, 0]
    assert np.asarray(ring.ratio)[0].tolist() == [1.0, 2.0, 7.0]
    assert np.all(np.asarray(ring.ratio)[1] == 0.0)


def test_ring_respects_active_and_sample_every():
    cfg = RingConfig(sample_every=4, cap=8, ratio_cap=4)
    ring = ring_init(cfg, 2, jnp.float64)
    off = jnp.zeros(2, bool)
    for t in range(9):
        active = jnp.asarray([True, t < 2])
        ring = ring_update(
            ring, cfg, t=jnp.asarray(t), active=active,
            newly_done=jnp.asarray([False, t == 1]),
            gap=jnp.full(2, float(t)), n_active=jnp.full(2, 9, jnp.int32),
            n_unshrink=jnp.zeros(2, jnp.int32), plan_event=off,
            ratio=jnp.zeros(2))
    # lane 0: periodic samples at t = 0, 4, 8
    assert np.asarray(ring.t)[0, :3].tolist() == [0, 4, 8]
    assert np.asarray(ring.n_samples).tolist() == [3, 2]
    # lane 1 froze at t=1: periodic t=0 plus the forced freeze sample
    assert np.asarray(ring.t)[1, :2].tolist() == [0, 1]


# ---------------------------------------------------------------------------
# Fig. 3 parity: fused ratio channel == classic record_trace
# ---------------------------------------------------------------------------

def test_fig3_ratio_parity_with_classic_record_trace():
    rng = np.random.default_rng(3)
    l, d, gamma, C = 24, 3, 0.8, 2.0
    X = jnp.asarray(rng.normal(size=(l, d)))
    y = jnp.asarray(np.where(rng.normal(size=l) >= 0, 1.0, -1.0))
    cfg = SolverConfig(eps=1e-4, max_iter=1000, record_trace=True)
    K = jnp.exp(-gamma * grid_mod.sqdist(X))
    classic = solve(qp_mod.PrecomputedKernel(K), y, C, cfg)

    res, ring = solve_fused_batched(
        X, y[None], C, gamma, SolverConfig(eps=1e-4, max_iter=1000),
        telemetry=RingConfig(ratio_cap=256), **FUSED_KW)
    assert int(res.iterations[0]) == int(classic.iterations)
    n = int(classic.n_trace)
    assert int(ring.n_ratio[0]) == n == int(classic.n_planning)
    np.testing.assert_allclose(np.asarray(ring.ratio)[0, :n],
                               np.asarray(classic.trace)[:n],
                               rtol=1e-9, atol=0)
    # the driver-level trace fields carry the same channel
    diag = Diagnostics(ring=RingConfig(ratio_cap=256))
    gres = grid_mod.solve_grid(X, y[None], np.array([C]), np.array([gamma]),
                               SolverConfig(eps=1e-4, max_iter=1000),
                               impl=FUSED_KW["impl"],
                               block_l=FUSED_KW.get("block_l", 1024),
                               diagnostics=diag)
    assert int(gres.n_trace[0, 0, 0]) == n
    np.testing.assert_allclose(np.asarray(gres.trace)[0, 0, 0, :n],
                               np.asarray(classic.trace)[:n],
                               rtol=1e-9, atol=0)


# ---------------------------------------------------------------------------
# drivers: grid drain, C-order permutation, chunked merge
# ---------------------------------------------------------------------------

def _grid_problem(l=24, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(l, 3))
    y = np.sign(rng.normal(size=l))
    y[y == 0] = 1
    return X, np.stack([y, -y])


def test_solve_grid_drains_lanes_in_caller_order():
    X, Y = _grid_problem()
    gammas = np.array([0.5, 1.0])
    Cs = np.array([2.0, 0.5, 1.0])        # unsorted: exercises the C perm
    cfg = SolverConfig(eps=1e-3, max_iter=300)
    diag = Diagnostics(ring=RingConfig(sample_every=8))
    res = grid_mod.solve_grid(X, Y, Cs, gammas, cfg, diagnostics=diag,
                              **FUSED_KW)
    ref = grid_mod.solve_grid(X, Y, Cs, gammas, cfg, **FUSED_KW)
    assert np.array_equal(np.asarray(res.iterations),
                          np.asarray(ref.iterations))
    assert len(diag.lanes) == 2 * 2 * 3
    it = np.asarray(res.iterations)
    for lane, rec in enumerate(diag.lanes):
        gi, rem = divmod(lane, 2 * 3)
        ci, Ci = divmod(rem, 3)
        assert rec["gamma"] == gammas[gi]
        assert rec["label"] == ci
        assert rec["C"] == Cs[Ci]
        assert rec["iterations"] == int(it[gi, ci, Ci])
        assert rec["n_ratio"] == rec["n_planning"]
    s = diag.summary(top_k=3)
    assert s["n_lanes"] == 12 and s["n_converged"] == 12
    assert len(s["stragglers"]) == 3
    assert s["stragglers"][0]["iterations"] == int(it.max())


def test_chunked_driver_merges_and_rebases_rings():
    X, Y = _grid_problem(l=32, seed=1)
    gammas = np.array([0.5, 1.0])
    Cs = np.array([0.5, 2.0])
    cfg = SolverConfig(eps=1e-3, max_iter=400)
    diag = Diagnostics(ring=RingConfig(sample_every=4, cap=64))
    res = grid_mod.solve_grid_compacted(X, Y, Cs, gammas, cfg, chunk=16,
                                        diagnostics=diag, **FUSED_KW)
    ref = grid_mod.solve_grid_compacted(X, Y, Cs, gammas, cfg, chunk=16,
                                        **FUSED_KW)
    assert np.array_equal(np.asarray(res.iterations),
                          np.asarray(ref.iterations))
    it = np.asarray(res.iterations).reshape(-1)
    assert len(diag.lanes) == it.size
    for lane, rec in enumerate(diag.lanes):
        assert rec["iterations"] == int(it[lane])
        ts = rec["samples"]["t"]
        # chunk-local stamps were rebased to a strictly increasing
        # run-global sequence ending before the lane's final iteration
        assert all(a < b for a, b in zip(ts, ts[1:]))
        assert ts[-1] <= rec["iterations"]
        assert rec["n_ratio"] == rec["n_planning"]
    rounds = [e for e in diag.sink.events
              if e["event"] == "phase" and e.get("name") == "chunk_solve"]
    assert len(rounds) >= 2          # chunk=16 forces multiple rounds
    assert all(e["seconds"] > 0 for e in rounds)


def test_grid_rejects_diagnostics_on_classic_path():
    X, Y = _grid_problem()
    with pytest.raises(ValueError, match="diagnostics"):
        grid_mod.solve_grid(X, Y, np.array([1.0]), np.array([0.5]),
                            SolverConfig(), diagnostics=Diagnostics())


def test_svr_and_oneclass_grid_drains():
    X, _ = _grid_problem(l=28, seed=2)
    rng = np.random.default_rng(2)
    y = np.sin(np.asarray(X)[:, 0]) + 0.1 * rng.normal(size=28)
    cfg = SolverConfig(eps=1e-3, max_iter=400)
    diag = Diagnostics(ring=RingConfig(sample_every=8))
    out = grid_mod.solve_grid_svr(X, y, np.array([0.5, 2.0]),
                                  np.array([0.05, 0.1]),
                                  np.array([0.5, 1.0]), cfg,
                                  diagnostics=diag, **FUSED_KW)
    assert len(diag.lanes) == 8
    it = np.asarray(out.iterations).reshape(-1)
    assert [r["iterations"] for r in diag.lanes] == [int(v) for v in it]
    assert {"gamma", "epsilon", "C"} <= set(diag.lanes[0])

    diag2 = Diagnostics(ring=RingConfig(sample_every=8))
    out2 = grid_mod.solve_grid_oneclass(X, np.array([0.2, 0.5]),
                                        np.array([0.5, 1.0]), cfg,
                                        diagnostics=diag2, **FUSED_KW)
    assert len(diag2.lanes) == 4
    it2 = np.asarray(out2.iterations).reshape(-1)
    assert [r["iterations"] for r in diag2.lanes] == [int(v) for v in it2]
    assert {"gamma", "nu"} <= set(diag2.lanes[0])


# ---------------------------------------------------------------------------
# sharded engine: rings gather back in lane order
# ---------------------------------------------------------------------------

def test_sharded_ring_matches_batched_single_device():
    # 1-device mesh is the degenerate shard_map: ring must be bitwise
    # the batched engine's ring
    from repro.core.sharded_lanes import solve_fused_sharded
    X, Y = _grid_problem()
    cfg = SolverConfig(eps=1e-3, max_iter=300)
    rc = RingConfig(sample_every=8)
    rs, ring_s = solve_fused_sharded(X, jnp.asarray(Y), 1.0, 0.8, cfg,
                                     telemetry=rc, **FUSED_KW)
    rb, ring_b = solve_fused_batched(X, jnp.asarray(Y), 1.0, 0.8, cfg,
                                     telemetry=rc, **FUSED_KW)
    assert np.array_equal(np.asarray(rs.iterations),
                          np.asarray(rb.iterations))
    for a, b in zip(jax.tree.leaves(ring_s), jax.tree.leaves(ring_b)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_sharded_ring_gather_back_multidevice():
    # heterogeneous lanes over 4 forced host devices: the round-robin
    # deal permutes lanes across shards, so ring rows coming back in
    # caller order is exactly the gather-back property under test
    out = run_multidevice(textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        import numpy as np
        from repro.core.sharded_lanes import solve_fused_sharded
        from repro.core.solver_fused import solve_fused_batched
        from repro.core.solver import SolverConfig
        from repro.telemetry import RingConfig

        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.normal(size=(24, 3)))
        y = jnp.asarray(np.where(rng.normal(size=24) >= 0, 1.0, -1.0))
        Y = jnp.stack([y, -y, y, -y])
        gam = jnp.asarray([0.3, 0.6, 1.0, 1.5])
        C = jnp.asarray([8.0, 0.5, 2.0, 1.0])
        cfg = SolverConfig(eps=1e-3, max_iter=500)
        rc = RingConfig(sample_every=8)
        rs, ring_s = solve_fused_sharded(X, Y, C, gam, cfg, impl="jnp",
                                         telemetry=rc)
        rb, ring_b = solve_fused_batched(X, Y, C, gam, cfg, impl="jnp",
                                         telemetry=rc)
        assert np.array_equal(np.asarray(rs.iterations),
                              np.asarray(rb.iterations))
        for a, b in zip(jax.tree.leaves(ring_s), jax.tree.leaves(ring_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-12, atol=0)
        # stamps are integer channels: must be exactly equal
        assert np.array_equal(np.asarray(ring_s.t), np.asarray(ring_b.t))
        assert np.array_equal(np.asarray(ring_s.n_samples),
                              np.asarray(ring_b.n_samples))
        print("GATHER_OK")
    """), n_devices=4)
    assert "GATHER_OK" in out


# ---------------------------------------------------------------------------
# facades
# ---------------------------------------------------------------------------

def test_facades_drain_diagnostics():
    from repro.svm import SVC, SVR, OneClassSVM
    rng = np.random.default_rng(2)
    X = rng.normal(size=(40, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    yr = np.sin(X[:, 0])
    rc = RingConfig(sample_every=8)

    d = Diagnostics(ring=rc)
    clf = SVC(C=2.0, gamma=0.7, impl=FUSED_KW["impl"],
              diagnostics=d).fit(X, y)
    ref = SVC(C=2.0, gamma=0.7, impl=FUSED_KW["impl"]).fit(X, y)
    assert np.array_equal(np.asarray(clf.alpha_), np.asarray(ref.alpha_))
    assert len(d.lanes) == 1
    assert d.lanes[0]["C"] == 2.0 and d.lanes[0]["label"] == 1
    assert any(e["event"] == "phase" and e["name"] == "svc_fit"
               for e in d.sink.events)

    d2 = Diagnostics(ring=rc)
    reg = SVR(C=2.0, epsilon=0.1, gamma=0.7, impl=FUSED_KW["impl"],
              diagnostics=d2).fit(X, yr)
    assert len(d2.lanes) == 1
    assert d2.lanes[0]["epsilon"] == 0.1
    assert d2.lanes[0]["iterations"] == int(reg.fit_result_.iterations)

    d3 = Diagnostics(ring=rc)
    oc = OneClassSVM(nu=0.3, gamma=0.7, impl=FUSED_KW["impl"],
                     diagnostics=d3).fit(X)
    assert len(d3.lanes) == 1
    assert d3.lanes[0]["nu"] == 0.3
    assert d3.lanes[0]["iterations"] == int(oc.fit_result_.iterations)

    # host-only diagnostics on the classic engine: phases, no lanes
    d4 = Diagnostics(ring=None)
    SVC(C=1.0, gamma=0.7, plan_candidates=2, impl=FUSED_KW["impl"],
        diagnostics=d4).fit(X, y)
    assert d4.lanes == []
    assert any(e["event"] == "phase" for e in d4.sink.events)


# ---------------------------------------------------------------------------
# sink, fingerprint, report CLI
# ---------------------------------------------------------------------------

def test_env_fingerprint_and_diff():
    fp = env_fingerprint()
    for key in ("jax_version", "backend", "device_kind", "device_count",
                "cpu_count", "host", "python", "machine"):
        assert key in fp
    assert fp["jax_version"] == jax.__version__
    assert len(fp["host"]) == 12          # hashed, not the raw hostname
    assert fingerprint_diff(fp, fp) == []
    other = dict(fp, backend="tpu", device_count=8)
    lines = fingerprint_diff(fp, other)
    assert any("backend" in ln for ln in lines)
    assert any("device_count" in ln for ln in lines)


def test_env_fingerprint_raises_when_the_backend_cannot_start(monkeypatch):
    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="initialize backend"):
        env_fingerprint()


def test_jsonl_sink_roundtrip(tmp_path):
    path = tmp_path / "events.jsonl"
    with JsonlSink(path) as sink:
        sink.emit("fingerprint", **env_fingerprint())
        with phase_scope("unit_phase", sink, tag=1):
            pass
        sink.emit("lane", lane=0, gap=jnp.asarray(0.5),
                  ts_list=np.arange(3))
    events = read_jsonl(path)
    assert [e["event"] for e in events] == ["fingerprint", "phase", "lane"]
    assert events[1]["name"] == "unit_phase"
    assert events[1]["seconds"] >= 0.0
    assert events[2]["gap"] == 0.5        # jax/numpy coerced to plain
    assert events[2]["ts_list"] == [0, 1, 2]


def test_telemetry_report_cli_golden_smoke(tmp_path, capsys):
    X, Y = _grid_problem()
    path = tmp_path / "run.jsonl"
    diag = Diagnostics(path, ring=RingConfig(sample_every=8))
    grid_mod.solve_grid(X, Y, np.array([0.5, 2.0]), np.array([0.5, 1.0]),
                        SolverConfig(eps=1e-3, max_iter=300),
                        diagnostics=diag, **FUSED_KW)
    summary = diag.finalize()
    assert summary["n_lanes"] == 8

    rc = report_mod.main([str(path), "--trace-lane", "0", "--hist"])
    assert rc == 0
    out = capsys.readouterr().out
    for section in ("## environment", "## host phases", "## convergence",
                    "## stragglers", "## iteration histogram",
                    "## planning trace (Fig. 3), lane 0", "## summary"):
        assert section in out
    assert f"jax_version | {jax.__version__}" in out
    # 8 lanes in the convergence table, keyed by their grid cell
    assert out.count("g=0.5") >= 4 and "C=2" in out
    assert "accepted planning steps" in out
    # straggler math: shares sum to <= 100 and the table is ranked
    assert "% of all iterations" in out


def test_telemetry_report_renders_straggler_warnings():
    events = [
        {"event": "lane", "lane": 0, "iterations": 10, "gamma": 0.5,
         "C": 1.0, "n_samples": 1, "samples": {"t": [0], "gap": [1.0],
                                               "n_active": [4],
                                               "n_unshrink": [0]},
         "ratio": {"t": [], "value": []}, "n_ratio": 0},
        {"event": "straggler_warning", "round": 3, "seconds": 9.5,
         "deadline": 3.0, "lanes": [0, 7], "rows": 128},
    ]
    text = report_mod.render_report(events)
    assert "chunk deadline breached" in text
    assert "round 3" in text and "9.5" in text


def test_report_cli_subprocess_entrypoint(tmp_path):
    # `python -m repro.launch.telemetry_report` is the documented entry
    path = tmp_path / "mini.jsonl"
    with JsonlSink(path) as sink:
        sink.emit("fingerprint", **env_fingerprint())
        sink.emit("lane", lane=0, iterations=5, gamma=1.0, C=1.0,
                  converged=True, kkt_gap=1e-4, n_planning=2,
                  total_unshrink=0, n_samples=1, n_ratio=0,
                  samples={"t": [0], "gap": [0.5], "n_active": [4],
                           "n_unshrink": [0]},
                  ratio={"t": [], "value": []})
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(p for p in (
        os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                     "src")),
        env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.telemetry_report", str(path)],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "## convergence" in proc.stdout


def test_bench_gate_fingerprint_note(capsys):
    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..")))
    try:
        from benchmarks.bench_gate import _fingerprint_note
    finally:
        sys.path.pop(0)
    fp = env_fingerprint()
    _fingerprint_note({"fingerprint": fp}, {"fingerprint": fp})
    assert "matches" in capsys.readouterr().out
    _fingerprint_note({"fingerprint": dict(fp, backend="tpu")},
                      {"fingerprint": fp})
    out = capsys.readouterr().out
    assert "environment differs" in out and "backend" in out
    _fingerprint_note({}, {"fingerprint": fp})
    assert "no environment fingerprint" in capsys.readouterr().out
