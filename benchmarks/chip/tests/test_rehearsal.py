"""CPU rehearsal of the chip benchmark: every file is found by name, every
entry and the correctness check run end to end at a tiny size (Pallas
kernels in interpret mode), and the command refuses to run without a TPU.
"""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import run
import work

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def tiny(monkeypatch, tmp_path, l=256, impl="interpret"):
    """Shrink every cell to ``l`` rows on the CPU; keep the rest."""
    load = run.load_cell

    def small(name):
        c = load(name)
        c.config = dict(c.config, l=l)
        c.traffic = dict(c.traffic, impl=impl)
        return c

    monkeypatch.setattr(run, "load_cell", small)
    monkeypatch.setattr(run, "check_devices", lambda jax, chips: jax.devices())
    monkeypatch.setattr(run, "CACHE_DIR", str(tmp_path / "jax_cache"))


def test_every_cell_finds_its_files_by_name():
    used = {"traffic": set(), "workloads": set(), "configs": set()}
    for w in BENCH["workloads"]:
        c = run.load_cell(w["name"])
        assert c.chips == w["chips"]
        assert {"kkt_gap", "box"} <= set(c.spec["limits"])
        assert c.spec["limits"]["box"] == 0.0
        entry = run.load_entry(c.traffic["entry"])
        for fn in ("job", "warmup", "returned", "lanes", "problems"):
            assert callable(getattr(entry, fn))
        used["traffic"].add(w["traffic"] + ".json")
        used["workloads"].add(w["name"] + ".json")
    for cfg in BENCH["configs"]:
        assert cfg["file"].startswith(BENCH["paths"][0] + "/")
        c = run.load_json(run.ROOT, cfg["file"])
        assert c["name"] == cfg["name"] and c["reduced"] == cfg["reduced"]
        used["configs"].add(os.path.basename(cfg["file"]))
    for m in BENCH["per_layer"]:
        assert callable(run.load_metric(m["name"]).read)
    for d, names in used.items():
        assert sorted(os.listdir(os.path.join(run.HERE, d))) == sorted(names)


def _ctx(cell_name):
    """A reading context with a trace in which every pass ran."""
    c = run.load_cell(cell_name)
    X, y = run.data.make(c.config, 1, l=64)
    entry = run.load_entry(c.traffic["entry"])
    B = len(entry.problems(X, y, c.traffic)["C"])
    dev = {"busy_s": 1.0, "host_idle_in_jobs_s": 0.01, "n_pass_a": 10,
           "t_pass_a_s": 0.3, "n_pass_b": 10, "t_pass_b_s": 0.3}
    trace = {"window_s": 1.1, "busy_s": 1.0, "n_job_starts": 1,
             "per_device": [dict(dev, device=i) for i in range(c.chips)]}
    jobs = [{"iterations": np.arange(1, B + 1) * 100,
             "converged": np.ones(B, bool)}]
    return SimpleNamespace(jobs=jobs, trace=trace, work=work,
                           device_kind="TPU v5 lite", n_chips=c.chips,
                           l=c.config["l"], d=c.config["d"], B=B, H=1)


def test_metric_workloads_match_the_cells_that_report_them():
    """A cell lists a per-layer metric exactly where the metric finds
    something to read and the cell reports the end-to-end metric it
    moves."""
    for name in CELLS:
        ctx = _ctx(name)
        cell = run.load_cell(name)
        listed = {m["name"] for m in cell.per_layer}
        moves = {m["name"] for m in cell.end_to_end}
        reported = {m["name"] for m in BENCH["per_layer"]
                    if m["moves"] in moves
                    and run.load_metric(m["name"]).read(ctx) is not None}
        assert reported == listed, name


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_entry_and_check_run_end_to_end(cell, monkeypatch, tmp_path):
    tiny(monkeypatch, tmp_path)
    res = run.run(run.parse(["--workload", cell, "--seed", "2147483999",
                             "--seconds", "0.5"]))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {
        m["name"] for m in run.load_cell(cell).end_to_end}
    assert "setup_s" in res["metrics"] and len(res["metrics"]) == 2
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1


def test_same_seed_same_inputs_other_seed_same_rows():
    cfg = run.load_cell("mnist.ovr10").config
    a, b = run.data.make(cfg, 5, l=300), run.data.make(cfg, 5, l=300)
    c = run.data.make(cfg, 6, l=300)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    key = lambda X: np.sort(X[:, 0])
    assert np.array_equal(key(a[0]), key(c[0]))        # same rows, reordered


def _command(cwd):
    return subprocess.run(
        [sys.executable] + BENCH["command"][1:]
        + ["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
           "--trace", "0"], cwd=cwd, env=CPU_ENV, capture_output=True,
        text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _command(run.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_in_a_bare_benchmark_checkout(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(os.path.join(run.ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_new_cell_and_metric_are_only_new_files(monkeypatch, tmp_path):
    """Adding a configuration, a cell, its mix and a metric edits no
    existing file."""
    shutil.copytree(run.HERE, tmp_path / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"] = [dict(c, file=c["file"].replace(BENCH["paths"][0],
                                                        "chip"))
                        for c in bench["configs"]]
    bench["configs"].append({"name": "blobs2", "source": "x",
                             "file": "chip/configs/blobs2.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "blobs2.svc1", "config": "blobs2",
                               "traffic": "svc1", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "chip/configs/blobs2.json").write_text(json.dumps(
        {"name": "blobs2", "l": 60000, "d": 22, "eps": 0.001,
         "generator": "gaussian_blobs", "generator_args": {"sep": 2.0},
         "data_seed": 0, "reduced": []}))
    (tmp_path / "chip/traffic/svc1.json").write_text(json.dumps(
        {"entry": "svc_fit", "impl": "auto", "engine": "auto", "C": 1.0,
         "gamma": "scale"}))
    shutil.copy(tmp_path / "chip/workloads/mnist.ovr10.json",
                tmp_path / "chip/workloads/blobs2.svc1.json")
    (tmp_path / "chip/metrics/engine.lanes.py").write_text(
        "def read(ctx):\n    return float(ctx.B)\n")
    os.symlink(os.path.join(run.ROOT, "src"), tmp_path / "src")
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "HERE", str(tmp_path / "chip"))
    tiny(monkeypatch, tmp_path)
    res = run.run(run.parse(["--workload", "blobs2.svc1", "--seed", "9",
                             "--seconds", "0.2"]))
    assert res["correct"] and res["attempted"] >= 1
    assert run.load_metric("engine.lanes").read(SimpleNamespace(B=4)) == 4.0
