"""The per-layer metrics that read the program's own span recorder
(``repro.telemetry``), after a tiny CPU run of the cell's entry: the
warm-up, then one job, as ``run.py`` makes them."""

from types import SimpleNamespace

import numpy as np
import pytest

import run

READERS = ("driver.fit_host_ms", "driver.gamma_ms", "driver.dispatch_ms",
           "driver.compile_events", "driver.warmup_compile_s",
           "engine.planning_share")


@pytest.fixture(scope="module")
def ctx():
    import repro.telemetry as telemetry
    c = run.load_cell("mnist.ovr10")
    traffic = dict(c.traffic, impl="interpret")
    X, y = run.data.make(c.config, 2147483999, l=232)
    entry = run.load_entry(traffic["entry"])
    telemetry.clear()
    entry.warmup(X, y, traffic)
    out = entry.job(X, y, traffic)
    return SimpleNamespace(jobs=[entry.lanes(out)])


@pytest.mark.parametrize("name", READERS)
def test_each_span_metric_reads_a_finite_value(ctx, name):
    v = run.load_metric(name).read(ctx)
    assert v is not None and np.isfinite(v) and v >= 0


def test_no_compile_inside_the_job(ctx):
    assert run.load_metric("driver.compile_events").read(ctx) == 0
    assert run.load_metric("driver.warmup_compile_s").read(ctx) > 0
