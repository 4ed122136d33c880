#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program on many
seeds, and the control on a few.

    python3 benchmarks/chip/limits.py --workload mnist.ovr10 \
        --seeds 101-112 --control-seeds 101-103 [--control xla_high]

Each seed is one job through the cell's entry at the cell's sizes, checked
by ``reference.py`` as a run checks it; everything runs in one process so
the compiled programs are shared.  A control is the program one precision
step below the configuration's ``HIGHEST`` float32 matmuls:

* ``pallas_default`` (the control): the timed Pallas path with every
  matmul that asks for ``HIGHEST`` planted at ``DEFAULT``, one bf16
  pass.  Mosaic lowers only ``DEFAULT`` and ``HIGHEST``, so this is the
  nearest step below ``HIGHEST`` that the kernels can take.
* ``xla_high``: the program's XLA path (``impl="jnp"``, no Gram bank)
  with those matmuls planted at ``HIGH``, three bf16 passes: the step
  below ``HIGHEST`` where XLA has one.
* ``xla`` is no control: the same XLA path at ``HIGHEST``, to compare
  its time and readings with the Pallas path's at one precision.

``--control`` takes a comma-separated list, run in that order.

One JSON line per job, then a summary: the largest reading of the program
(the lower reading) and the smallest of the control (the upper one).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import data
import reference
import run
from run import log

CONTROLS = {"pallas_default": ({}, "DEFAULT"),
            "xla_high": ({"impl": "jnp", "precompute": False}, "HIGH"),
            # not a control: the XLA path at the configuration's precision
            "xla": ({"impl": "jnp", "precompute": False}, "HIGHEST")}
# every program module that names the precision of its matmuls
PRECISION_MODULES = ("repro.kernels.ref", "repro.kernels.rbf_row_wss",
                     "repro.kernels.rbf_update_wss",
                     "repro.kernels.gram_block", "repro.kernels.row_source",
                     "repro.core.grid", "repro.core.multiclass",
                     "repro.svm.svc", "repro.svm.svr", "repro.svm.oneclass")


def plant_precision(jax, name: str) -> int:
    """Set every program module's ``HIGHEST`` to ``Precision.<name>`` and
    drop the compiled programs; returns how many modules were changed."""
    import importlib
    target = getattr(jax.lax.Precision, name)
    n = 0
    for mod in map(importlib.import_module, PRECISION_MODULES):
        if hasattr(mod, "HIGHEST"):
            mod.HIGHEST = target
            n += 1
    jax.clear_caches()
    return n


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = map(int, part.split("-"))
            out.extend(range(a, b + 1))
        elif part:
            out.append(int(part))
    return out


def reading(jax, cell, entry, traffic, seed: int, kind: str) -> dict:
    """One job of ``cell`` on ``seed``, and the reference's numbers."""
    X, y = data.make(cell.config, seed)
    jax.block_until_ready(entry.returned(entry.warmup(X, y, traffic)))
    t0 = time.perf_counter()
    out = entry.job(X, y, traffic)
    jax.block_until_ready(entry.returned(out))
    secs = time.perf_counter() - t0
    ln = entry.lanes(out)
    del out
    prob = entry.problems(X, y, traffic)
    t0 = time.perf_counter()
    r = reference.check(X, prob["labels"], prob["C"], prob["gamma"],
                        ln["alpha"], ln["b"])
    row = {"kind": kind, "seed": seed, "seconds": secs,
           "reference_s": time.perf_counter() - t0,
           "iterations": ln["iterations"].tolist(),
           "converged": bool(ln["converged"].all())}
    for k, v in run.numbers(r).items():
        row[k] = float(v.max())
    for k in ("gap", "bias_err", "ref_err", "refined", "capped"):
        row[k + "_per_lane"] = np.asarray(r[k]).tolist()
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="xla_high",
                    help="comma-separated, of " + ", ".join(sorted(CONTROLS)))
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.import_program()
    jax = run.configure_jax()
    run.check_devices(jax, cell.chips)
    for name in args.control.split(","):
        if name not in CONTROLS:
            ap.error(f"unknown control {name!r}")
    entry = run.load_entry(cell.traffic["entry"])
    rows = []
    for s in seeds(args.seeds):
        rows.append(reading(jax, cell, entry, cell.traffic, s, "program"))
        print(json.dumps(rows[-1]), flush=True)
    for name in args.control.split(","):
        kw, prec = CONTROLS[name]
        if not args.control_seeds:
            break
        n = plant_precision(jax, prec)
        log(f"control {name}: {n} modules at {prec}")
        traffic = dict(cell.traffic, **kw)
        for s in seeds(args.control_seeds):
            rows.append(reading(jax, cell, entry, traffic, s, name))
            print(json.dumps(rows[-1]), flush=True)
    plant_precision(jax, "HIGHEST")
    summary = {}
    for k in sorted(cell.spec["limits"]):
        prog = [r[k] for r in rows if r["kind"] == "program"]
        summary[k] = {"lower": max(prog) if prog else None}
        for name in args.control.split(","):
            ctrl = [r[k] for r in rows if r["kind"] == name]
            summary[k][name + "_min"] = min(ctrl) if ctrl else None
    print(json.dumps({"summary": summary, "workload": args.workload,
                      "control": args.control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
