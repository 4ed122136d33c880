"""The trace reduction: interval arithmetic, and a small trace recorded on
a v5e chip (``record_fixture.py``) reduced end to end."""

import json
import os

import numpy as np
import pytest

import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _brute(iv, lo, hi):
    """Covered length of integer intervals inside [lo, hi), by counting."""
    cover = np.zeros(hi - lo, bool)
    for a, b in iv:
        cover[max(a, lo) - lo:max(min(b, hi) - lo, 0)] = True
    return int(cover.sum())


def test_union_counts_nested_and_overlapping_once():
    s = np.array([0, 2, 3, 10, 20, 20], float)
    e = np.array([5, 4, 8, 12, 21, 25], float)
    assert devtrace.union(s, e, 0, 30) == [(0, 8), (10, 12), (20, 25)]
    assert devtrace.union(s, e, 3, 11) == [(3, 8), (10, 11)]
    assert devtrace.gaps([(3, 8), (10, 11)], 0, 12) == [(0, 3), (8, 10),
                                                         (11, 12)]


def test_interval_arithmetic_against_brute_force():
    rng = np.random.default_rng(0)
    for _ in range(50):
        s = rng.integers(0, 900, 40)
        e = s + rng.integers(0, 60, 40)
        lo, hi = sorted(rng.integers(0, 1000, 2))
        iv = devtrace.union(s.astype(float), e.astype(float), lo, hi)
        assert devtrace.length(iv) == _brute(zip(s, e), lo, hi)
        g = devtrace.gaps(iv, lo, hi)
        assert devtrace.length(iv) + devtrace.length(g) == hi - lo
        s2 = rng.integers(0, 900, 10)
        iv2 = devtrace.union(s2.astype(float), s2 + 80.0, lo, hi)
        both = [(max(a, c), min(b, d)) for a, b in iv for c, d in iv2]
        assert devtrace.intersect(iv, iv2) == sum(
            max(0, b - a) for a, b in both)


def test_classify_by_instruction_name_only():
    hlo = ("%get-tuple-element.9 = f32[16,128] get-tuple-element(("
           "f32[16,128]) %rbf_update_wss_batched_pallas.8), index=0")
    assert devtrace.short_name(hlo) == "get-tuple-element.9"
    assert devtrace.classify(devtrace.short_name(hlo)) == 0
    assert devtrace.classify("rbf_row_wss_batched_pallas.3") == 1
    assert devtrace.classify("rbf_update_wss_batched_pallas.12") == 2
    assert devtrace.classify("fusion.12") == 0


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(DATA, "fixture.json")) as f:
        meta = json.load(f)
    tr = devtrace.load(os.path.join(DATA, "fixture.xplane.pb"))
    return meta, tr, devtrace.reduce(tr)


def test_fixture_launches_equal_the_jobs_iterations(fixture):
    meta, tr, red = fixture
    n = sum(meta["iterations"])
    (dev,) = red["per_device"]
    assert dev["n_pass_a"] == dev["n_pass_b"] == n
    assert red["n_job_starts"] == 2
    assert len(devtrace.job_spans(tr, *devtrace.window(tr))) == 2


def test_fixture_passes_alternate_and_are_named(fixture):
    _, tr, _ = fixture
    (dev,) = tr.devices
    seq = dev.cls[dev.cls > 0]
    assert (seq[0::2] == 1).all() and (seq[1::2] == 2).all()
    assert dev.container.sum() == 2                 # the two jobs' loops
    assert {tr.names[i] for i in dev.name[dev.container]} <= {
        n for n in tr.names if n.startswith("while")}
    for c, key in ((1, devtrace.PASSES["pass_a"]),
                   (2, devtrace.PASSES["pass_b"])):
        names = {tr.names[i] for i in np.unique(dev.name[dev.cls == c])}
        assert names and all(key in n for n in names)


def test_fixture_idle_share_arithmetic(fixture):
    _, tr, red = fixture
    lo, hi = devtrace.window(tr)
    (dev,) = tr.devices
    ns = lambda v: np.round(np.asarray(v)).astype(np.int64)
    busy = _brute(zip(ns(dev.start), ns(dev.end)), int(lo), int(hi))
    assert red["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-6, abs=1e-8)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    idle = sum(d for _, d in red["breakdown"]["idle_gaps"])
    assert 0 < red["busy_s"] < red["window_s"]
    assert idle <= red["window_s"] - red["busy_s"] + 1e-12
    assert red["per_device"][0]["host_idle_in_jobs_s"] >= 0
