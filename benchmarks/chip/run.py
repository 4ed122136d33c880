#!/usr/bin/env python3
"""Chip benchmark of the PA-SMO solver: one run of one cell.

    python3 benchmarks/chip/run.py --workload mnist.ovr10 --seed 7 \
        --seconds 30 --trace 0

A cell of ``BENCHMARK.json`` names a configuration (a data set and its
guarantees, ``configs/<config>.json``), a traffic mix (the job, in
``traffic/<traffic>.json``, run by ``entries/<entry>.py``) and the chips
it needs; ``workloads/<cell>.json`` holds its correctness limits and how
its trace is placed.  Per-layer metrics are readers in
``metrics/<metric>.py``.  Everything is found by name: a new cell, mix,
entry or metric is a new file.

A run:

1. set-up (``setup_s``, from process start): data from the seed, the
   persistent compilation cache at ``<checkout>/.jax_cache``, and a
   warm-up call of the entry at the job's shapes that exits at its first
   check;
2. the window: jobs back to back, each timed from the entry call until
   alpha and b are ready on the host's view; a job that starts before
   ``--seconds`` have passed is finished and counted.  ``solve_s`` is
   the summed job time over the jobs.  With ``--trace 1`` the profiler
   records the end of one job and the start of the next, and the result
   carries the per-layer metrics instead;
3. the check: peak device memory is read, the program's outputs are
   moved to the host and freed, and the plain reference
   (``reference.py``) recomputes every lane's gradient from the data and
   the returned alpha.  ``correct`` holds when each compared number is
   within its limit.

The last stdout line is the result JSON; the compared numbers, each with
its limit, are the last stderr lines and the result's last key.  Without
a TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

import data  # noqa: E402
import devtrace  # noqa: E402
import reference  # noqa: E402
import work  # noqa: E402


class Refused(Exception):
    """The run cannot be made here; exit non-zero, print no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_start() -> float:
    """Wall time at which this process started (Linux), else at import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - up + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> SimpleNamespace:
    """The cell ``name`` of ``BENCHMARK.json`` with every file it names."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; "
                      f"known: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(m):
        return name in m.get("workloads", [name])

    return SimpleNamespace(
        name=name, chips=int(cell["chips"]),
        config=load_json(ROOT, cfg["file"]),
        traffic=load_json(HERE, "traffic", cell["traffic"] + ".json"),
        spec=load_json(HERE, "workloads", name + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)])


def load_entry(name: str):
    return load_module(os.path.join(HERE, "entries", name + ".py"), name)


def load_metric(name: str):
    return load_module(os.path.join(HERE, "metrics", name + ".py"), name)


def import_program() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"the program under test is not here: no {src}/repro")
    sys.path.insert(0, src)


def configure_jax():
    import jax
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def check_devices(jax, chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: the first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, {len(devs)} attached")
    return devs


class CompileCount:
    """Traces, backend compiles and persistent-cache loads, by JAX's own
    monitoring events."""

    def __init__(self, jax):
        from jax._src import dispatch
        self.n = {"traces": 0, "compiles": 0, "cache_loads": 0}
        names = {dispatch.JAXPR_TRACE_EVENT: "traces",
                 dispatch.BACKEND_COMPILE_EVENT: "compiles"}

        def on_duration(event, _secs, **_kw):
            if event in names:
                self.n[names[event]] += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.n["cache_loads"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return dict(self.n)


class Tracer:
    """Places the profiler around one job boundary.

    Job ``k`` (the second) is dispatched untraced; the trace starts
    ``lead_s`` before it is expected to end (its predecessor's time) and
    stops ``tail_s`` after job ``k + 1`` has been dispatched, so the window
    holds steady iterations, the end of one job and the start of the next.
    """

    K = 1

    def __init__(self, jax, lead_s: float, tail_s: float, out_dir: str):
        self.jax, self.lead, self.tail, self.dir = jax, lead_s, tail_s, out_dir

    def mark(self, name: str) -> None:
        with self.jax.profiler.TraceAnnotation(name):
            pass

    def wants_more(self, n_jobs: int) -> bool:
        return n_jobs < self.K + 2

    def before_wait(self, k: int, t0: float, prev_s: float | None) -> None:
        if k == self.K:
            time.sleep(max(0.0, t0 + prev_s - self.lead - time.perf_counter()))
            self.jax.profiler.start_trace(self.dir)
            self.mark("bench.trace_on")
        elif k == self.K + 1:
            time.sleep(self.tail)
            self.mark("bench.trace_off")
            self.jax.profiler.stop_trace()

    def path(self) -> str:
        for d, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(d, f)
        raise FileNotFoundError(f"no .xplane.pb under {self.dir}")


def run_window(jax, entry, X, y, traffic, seconds: float, tracer=None):
    """Jobs back to back; returns [(seconds, program result)]."""
    mark = tracer.mark if tracer is not None else (lambda _n: None)
    jobs = []
    t_start = time.perf_counter()
    while (not jobs or time.perf_counter() - t_start < seconds
           or (tracer is not None and tracer.wants_more(len(jobs)))):
        k = len(jobs)
        mark("bench.job_start")
        t0 = time.perf_counter()
        out = entry.job(X, y, traffic)
        mark("bench.job_dispatched")
        if tracer is not None:
            tracer.before_wait(k, t0, jobs[-1][0] if jobs else None)
        jax.block_until_ready(entry.returned(out))
        t1 = time.perf_counter()
        mark("bench.job_end")
        jobs.append((t1 - t0, out))
    return jobs


def numbers(r: dict) -> dict:
    """The per-lane numbers a check compares, from a reference result."""
    return {"kkt_gap": r["gap"], "bias_err": r["bias_err"], "box": r["box"]}


def compare(X, y, cell, entry, lane_sets) -> dict:
    """The reference's numbers over every job, each with its limit."""
    prob = entry.problems(X, y, cell.traffic)
    seen = {}
    for ln in lane_sets:
        key = ln["alpha"].tobytes() + ln["b"].tobytes()
        if key not in seen:
            seen[key] = reference.check(X, prob["labels"], prob["C"],
                                        prob["gamma"], ln["alpha"], ln["b"])
    rs = list(seen.values())
    log("reference: float32 pass off by at most "
        f"{max(float(r['ref_err'].max()) for r in rs)!r} on the "
        f"{max(int(r['refined'].max()) for r in rs)} rows a lane redone in "
        f"float64; lanes at the refine cap: "
        f"{sum(int(r['capped'].sum()) for r in rs)}")
    out = {}
    for name, limit in cell.spec["limits"].items():
        v = np.concatenate([numbers(r)[name] for r in rs])
        # a NaN anywhere is the worst reading, never hidden by a max
        worst = float(v.max()) if np.isfinite(v).all() else float("nan")
        out[name] = {"value": worst, "limit": float(limit)}
    return out


def device_info(devs, peak: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run(args) -> dict:
    t_proc = process_start()
    phases = {"start": T_IMPORT - t_proc}
    cell = load_cell(args.workload)
    import_program()
    t = time.time()
    jax = configure_jax()
    phases["import_jax"] = time.time() - t
    t = time.time()
    devs = check_devices(jax, cell.chips)
    phases["devices"] = time.time() - t
    counts = CompileCount(jax)
    entry = load_entry(cell.traffic["entry"])
    t = time.time()
    X, y = data.make(cell.config, args.seed)
    phases["data"] = time.time() - t
    t = time.time()
    jax.block_until_ready(entry.returned(entry.warmup(X, y, cell.traffic)))
    phases["warmup"] = time.time() - t
    setup_s = time.time() - t_proc
    before = counts.snapshot()
    log(f"set-up {setup_s:.3f} s; compile events in set-up {before}")
    log("set-up phases (s): "
        + json.dumps({k: round(v, 3) for k, v in phases.items()}))

    tracer = tdir = None
    if args.trace:
        tdir = args.trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        tr = cell.spec["trace"]
        tracer = Tracer(jax, tr["lead_s"], tr["tail_s"], tdir)
    try:
        jobs = run_window(jax, entry, X, y, cell.traffic, args.seconds,
                          tracer)
        after = counts.snapshot()
        in_window = {k: after[k] - before[k] for k in after}
        log(f"window: {len(jobs)} jobs, "
            f"{[round(s, 6) for s, _ in jobs]} s; "
            f"compile events in the window {in_window}")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        lane_sets = [entry.lanes(out) for _, out in jobs]
        times = [s for s, _ in jobs]
        del jobs
        gc.collect()
        reduced = None
        if tracer is not None:
            reduced = devtrace.reduce(devtrace.load(tracer.path()))
    finally:
        if tdir is not None and args.trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)

    checks = compare(X, y, cell, entry, lane_sets)
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    failed = sum(1 for ln in lane_sets if not ln["converged"].all())
    B = lane_sets[0]["alpha"].shape[0]
    result = {"correct": bool(correct), "attempted": len(times),
              "failed": failed, "metrics": {},
              "device": device_info(devs, peak)}
    if not args.trace:
        values = {"solve_s": sum(times) / len(times), "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        ctx = SimpleNamespace(
            jobs=lane_sets, trace=reduced, work=work,
            device_kind=devs[0].device_kind, n_chips=cell.chips,
            l=cell.config["l"], d=cell.config["d"], B=B, H=1)
        for m in cell.per_layer:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
        log("trace per device: " + json.dumps(reduced["per_device"]))
    result["checks"] = checks
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the trace here instead of a temporary "
                         "directory that is removed after reading")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except Refused as e:
        log(f"run.py: {e}")
        return 3
    print(json.dumps(result), flush=True)
    for k, c in result["checks"].items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
