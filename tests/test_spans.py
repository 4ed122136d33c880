"""Always-on spans and compile counters (``repro.telemetry.spans``).

Every facade and grid call records a root span with ``fit.gamma`` /
``fit.solve`` children, counts the traces and compiles that fell inside
it, and keeps the engine's counters as unread device arrays; the fused
loop body carries its named scopes whether or not the ring is on.  The
chip benchmark's per-layer readers read the same recorder.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FUSED_KW
from repro import telemetry
from repro.core import grid as grid_mod
from repro.core.solver import SolverConfig
from repro.core.solver_fused import solve_fused_batched_qp
from repro.svm import SVC
from repro.telemetry import (JsonlSink, children, phase_scope, recent,
                             span)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = os.path.join(ROOT, "benchmarks", "chip", "metrics")
READERS = ("driver.fit_host_ms", "driver.gamma_ms", "driver.dispatch_ms",
           "driver.compile_events", "driver.warmup_compile_s",
           "engine.planning_share")


@pytest.fixture(autouse=True)
def empty_recorder():
    telemetry.clear()
    yield
    telemetry.clear()


def _blobs(l=60, d=3, k=3, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, k, l)
    X = rng.normal(size=(l, d)) + 3.0 * y[:, None]
    return X, y


def _fit(X, y, **kw):
    return SVC(C=1.0, gamma="scale", impl=FUSED_KW["impl"], **kw).fit(X, y)


def test_each_fit_records_a_root_with_gamma_and_solve_children():
    X, y = _blobs()
    _fit(X, y)
    _fit(X, y)
    roots = recent(roots=True)
    assert [r.name for r in roots] == ["svc_fit", "svc_fit"]
    for r in roots:
        assert r.root == r.id and r.parent is None
        assert r.attrs == {"engine": "fused", "n_class": 3, "rows": 60}
        kids = children(r)
        assert [c.name for c in kids] == ["fit.gamma", "fit.solve"]
        for c in kids:
            assert c.parent == r.id and c.root == r.id
            assert r.start_ns <= c.start_ns <= c.end_ns <= r.end_ns
    assert roots[0].id != roots[1].id


def test_nested_spans_get_their_parent_and_root():
    with span("outer") as a:
        with span("mid") as b:
            with span("inner") as c:
                pass
        with span("mid2") as d:
            pass
    assert (a.parent, b.parent, c.parent, d.parent) == (None, a.id, b.id,
                                                        a.id)
    assert {a.root, b.root, c.root, d.root} == {a.id}
    # finished spans are listed as they close, oldest first
    assert [s.name for s in recent()] == ["inner", "mid", "mid2", "outer"]
    assert [s.name for s in recent(2)] == ["mid2", "outer"]
    assert recent(roots=True) == [a]
    assert children(a) == [b, d]
    assert all(s.seconds >= 0 for s in (a, b, c, d))


def test_span_as_decorator_opens_a_fresh_span_per_call():
    @span("work", tag=1)
    def work(x):
        return x + 1

    assert work(1) == 2 and work(2) == 3
    a, b = recent(name="work")
    assert a.id != b.id and a.attrs == b.attrs == {"tag": 1}


def test_buffer_keeps_the_newest_spans():
    for i in range(telemetry.spans.CAPACITY + 5):
        with span("s", i=i):
            pass
    kept = recent()
    assert len(kept) == telemetry.spans.CAPACITY
    assert kept[0].attrs["i"] == 5 and kept[-1].attrs["i"] == \
        telemetry.spans.CAPACITY + 4


def test_second_identical_fit_counts_no_trace_and_no_compile():
    X, y = _blobs(seed=1)
    _fit(X, y)
    _fit(X, y)
    X2, y2 = _blobs(l=77, seed=1)
    _fit(X2, y2)
    first, second, new_shape = recent(roots=True)
    assert second.counts["traces"] == 0 and second.counts["compiles"] == 0
    assert new_shape.counts["traces"] > 0
    assert new_shape.counts["compiles"] > 0
    assert new_shape.counts["compile_s"] > 0
    # a root counts what its children counted
    solve = children(new_shape, "fit.solve")[0]
    assert 0 < solve.counts["traces"] <= new_shape.counts["traces"]


def test_engine_counters_stay_unread_until_asked():
    X, y = _blobs(seed=2)
    clf = _fit(X, y)
    root = recent(roots=True)[-1]
    raw = root._held
    assert set(raw) == {"iterations", "n_planning", "converged",
                        "n_unshrink"}
    # references to the engine's own device arrays, not host copies
    assert all(isinstance(v, jax.Array) for v in raw.values())
    assert raw["iterations"] is clf.fit_result_.iterations
    held = root.held()
    assert isinstance(held["iterations"], np.ndarray)
    np.testing.assert_array_equal(held["iterations"],
                                  np.asarray(clf.fit_result_.iterations))
    np.testing.assert_array_equal(held["n_planning"],
                                  np.asarray(clf.fit_result_.n_planning))
    assert held["n_planning"].sum() > 0       # pasmo plans on this problem
    np.testing.assert_array_equal(held["n_unshrink"],
                                  np.asarray(clf.fit_result_.n_unshrink))


def test_phase_scope_is_a_span_plus_the_phase_event():
    sink = JsonlSink()
    with phase_scope("unit_phase", sink, tag=1) as sp:
        sp.attrs.update(late=2)
    (ev,) = sink.events
    assert ev["event"] == "phase" and ev["name"] == "unit_phase"
    assert ev["tag"] == 1 and ev["late"] == 2
    assert ev["seconds"] == sp.seconds
    assert recent() == [sp]


def test_diagnostics_phase_event_covers_the_whole_fit():
    X, y = _blobs(seed=3)
    diag = telemetry.Diagnostics(ring=None)
    _fit(X, y, diagnostics=diag)
    (ev,) = [e for e in diag.sink.events if e["event"] == "phase"]
    root = recent(roots=True)[-1]
    assert ev["name"] == "svc_fit" == root.name
    assert ev["seconds"] == root.seconds
    assert ev["engine"] == "fused" and ev["n_class"] == 3


def test_chunked_driver_records_a_span_per_round():
    rng = np.random.default_rng(4)
    X = rng.normal(size=(32, 3))
    Y = np.sign(rng.normal(size=(2, 32)))
    cfg = SolverConfig(eps=1e-3, max_iter=400)
    grid_mod.solve_grid_compacted(X, Y, np.array([0.5, 2.0]),
                                  np.array([0.5, 1.0]), cfg, chunk=16,
                                  **FUSED_KW)
    (root,) = recent(roots=True)
    assert root.name == "solve_grid_compacted"
    (solve,) = children(root, "fit.solve")
    rounds = [s for s in recent(name="chunk_solve") if s.root == root.id]
    assert len(rounds) >= 2
    assert [s.attrs["round"] for s in rounds] == list(range(len(rounds)))
    assert rounds[0].attrs["lanes"] == 8 and rounds[0].attrs["rows"] == 32
    assert all(s.parent != root.id for s in rounds)   # under fit.solve
    assert sum(s.seconds for s in rounds) <= solve.seconds


def test_export_writes_one_json_line_per_span(tmp_path):
    X, y = _blobs(seed=5)
    _fit(X, y)
    path = tmp_path / "spans.jsonl"
    assert telemetry.export(path) == 3
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["name"] for r in rows] == ["fit.gamma", "fit.solve",
                                         "svc_fit"]
    assert rows[2]["attrs"]["engine"] == "fused"
    assert len(rows[2]["held"]["iterations"]) == 3
    assert set(rows[0]["counts"]) == set(telemetry.spans.COUNTERS)


def test_loop_scopes_are_in_the_engine_without_telemetry():
    rng = np.random.default_rng(6)
    X = jnp.asarray(rng.normal(size=(16, 4)))
    P = jnp.asarray(np.sign(rng.normal(size=(3, 16))))
    L, U = jnp.minimum(0.0, 2.0 * P), jnp.maximum(0.0, 2.0 * P)
    gam = jnp.asarray(rng.uniform(0.3, 1.0, 3))
    cfg = SolverConfig(eps=1e-3, max_iter=50)
    text = jax.jit(
        lambda X, P, L, U, g: solve_fused_batched_qp(
            X, P, L, U, g, cfg, impl="interpret", block_l=128,
            shrinking=True)).lower(X, P, L, U, gam).as_text(debug_info=True)
    for name in ("fused_pass_a", "fused_pass_b", "x_pad", "lane_state",
                 "fused_step", "fused_shrink"):
        assert name in text, name
    assert "telemetry_ring" not in text


def _reader(name):
    """A metric file loaded the way the chip benchmark's ``run.py`` does."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Ctx:
    def __init__(self, n_jobs):
        self.jobs = [{}] * n_jobs


@pytest.mark.parametrize("name", READERS)
def test_span_readers_read_the_recorder(name):
    reader = _reader(name)
    assert reader.read(_Ctx(1)) is None           # empty recorder
    X, y = _blobs(seed=7)
    SVC(C=0.0, gamma="scale", impl=FUSED_KW["impl"]).fit(X, y)   # warm-up
    early = reader.read(_Ctx(1))                  # the warm-up alone
    if name == "driver.warmup_compile_s":
        assert early is None
    clf = _fit(X, y)
    warm, job = recent(roots=True)
    gamma, solve = children(job)
    it = np.asarray(clf.fit_result_.iterations)
    plan = np.asarray(clf.fit_result_.n_planning)
    want = {
        "driver.fit_host_ms": 1e3 * job.seconds,
        "driver.gamma_ms": 1e3 * gamma.seconds,
        "driver.dispatch_ms": 1e3 * solve.seconds,
        "driver.compile_events": 0.0,
        "driver.warmup_compile_s": (warm.counts["trace_s"]
                                    + warm.counts["lower_s"]
                                    + warm.counts["compile_s"]),
        "engine.planning_share": 100.0 * plan.sum() / it.sum(),
    }[name]
    got = reader.read(_Ctx(1))
    assert got == pytest.approx(want) and np.isfinite(got)
    assert reader.read(_Ctx(3)) is None           # fewer fits than jobs
