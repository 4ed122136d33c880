#!/usr/bin/env python3
"""Record the small chip trace that ``test_devtrace.py`` reads.

    python3 benchmarks/chip/tests/record_fixture.py      # on a TPU host

Two small ``solve_grid`` jobs (one lane, l = 512, d = 22) run back to
back inside one profiler trace, with the benchmark's host markers around
them.  The trace is trimmed to what the reduction reads (the device
planes' ``XLA Ops``/``XLA Modules`` lines and the host thread that holds
the markers) and written to ``data/fixture.xplane.pb``; the iteration
counts the two jobs returned go to ``data/fixture.json``.  Each loop trip
launches pass A and pass B once, so the trace must hold as many launches
of each as the jobs' summed iterations.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
OUT = os.path.join(HERE, "data")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(HERE))


def xplane_pb2():
    """The XSpace protobuf module, loaded from its file alone."""
    spec = importlib.util.find_spec("tensorflow")
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    s = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def trim(src: str, dst: str) -> None:
    import devtrace
    pb = xplane_pb2()
    xs = pb.XSpace()
    with open(src, "rb") as f:
        xs.ParseFromString(f.read())
    out = pb.XSpace()
    for plane in xs.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            keep = [ln for ln in plane.lines
                    if ln.name in (devtrace.OPS_LINE, devtrace.MODULES_LINE)]
        elif plane.name.startswith("/host:"):
            keep = [ln for ln in plane.lines if any(
                plane.event_metadata[e.metadata_id].name.startswith(
                    devtrace.MARK) for e in ln.events)]
        else:
            continue
        p = out.planes.add()
        p.id, p.name = plane.id, plane.name
        used = {e.metadata_id for ln in keep for e in ln.events}
        stat_used = {s.metadata_id for ln in keep for e in ln.events
                     for s in e.stats
                     if not devtrace.DEVICE_PLANE.match(plane.name)}
        for k in used:
            md = p.event_metadata[k]
            md.CopyFrom(plane.event_metadata[k])
            # keep names only: the HLO text and source locations would
            # carry the recording machine's paths
            md.name = devtrace.short_name(md.name)
            if not md.name.startswith(devtrace.MARK):
                md.name = md.name.split(":", 1)[0].rsplit("/", 1)[-1]
            md.display_name = ""
            del md.stats[:]
        for k in stat_used:
            if k in plane.stat_metadata:
                p.stat_metadata[k].CopyFrom(plane.stat_metadata[k])
        for ln in keep:
            q = p.lines.add()
            q.CopyFrom(ln)
            if devtrace.DEVICE_PLANE.match(plane.name):
                for e in q.events:           # per-event stats: not read
                    del e.stats[:]
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


def main() -> int:
    import jax
    jax.config.update("jax_enable_x64", False)
    if jax.devices()[0].platform != "tpu":
        print("record_fixture.py: needs a TPU", file=sys.stderr)
        return 3
    from repro.core import grid
    import data
    cfg = {"l": 512, "d": 22, "generator": "gaussian_blobs",
           "generator_args": {"sep": 2.0}, "data_seed": 0}
    X, y = data.make(cfg, seed=1)
    C, g = np.array([1.0]), np.array([2.0 ** -5])

    def job(c):
        return grid.solve_grid(X, y, C * c, g, impl="auto")

    jax.block_until_ready(job(0.0).alpha)

    def mark(name):
        with jax.profiler.TraceAnnotation(name):
            pass

    tdir = tempfile.mkdtemp(prefix="fixture_trace_")
    its = []
    jax.profiler.start_trace(tdir)
    mark("bench.trace_on")
    for _ in range(2):
        mark("bench.job_start")
        r = job(1.0)
        mark("bench.job_dispatched")
        jax.block_until_ready((r.alpha, r.b))
        mark("bench.job_end")
        its.append(int(np.asarray(r.iterations).max()))
    mark("bench.trace_off")
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                    recursive=True)[0]
    os.makedirs(OUT, exist_ok=True)
    trim(src, os.path.join(OUT, "fixture.xplane.pb"))
    with open(os.path.join(OUT, "fixture.json"), "w") as f:
        json.dump({"iterations": its, "device_kind":
                   jax.devices()[0].device_kind}, f)
    print(json.dumps({"iterations": its, "raw_bytes": os.path.getsize(src),
                      "trimmed_bytes": os.path.getsize(
                          os.path.join(OUT, "fixture.xplane.pb"))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
