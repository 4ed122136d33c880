"""The check catches a broken timed path.

Each test skips the harness's look for a chip and drives the rest of a
run at a tiny size on the CPU, with a fault planted in the program's
fused engine underneath the entry, and sees ``correct`` come out false:

* ``unchanged``: the solve returns its starting state (alpha = 0);
* ``half_batch``: the second half of the lanes comes back unsolved;
* ``alpha_altered`` / ``b_altered``: one lane's answer altered where it
  is produced (alpha scaled by 0.5, b moved by 0.05).
"""

import dataclasses

import pytest

import run
from test_rehearsal import tiny

FAULTS = ("unchanged", "half_batch", "alpha_altered", "b_altered")


def plant(fault: str):
    """Wrap the fused engine so its result carries ``fault``."""
    import jax
    import jax.numpy as jnp
    from repro.core import sharded_lanes, solver_fused
    real = solver_fused.solve_fused_batched_qp

    def broken(*args, **kw):
        r = real(*args, **kw)
        a, b = r.alpha, r.b
        lane = jnp.arange(a.shape[0])[:, None]
        if fault == "unchanged":
            a = jnp.zeros_like(a)
        elif fault == "half_batch":
            a = jnp.where(lane >= a.shape[0] // 2, 0.0, a)
        elif fault == "alpha_altered":
            a = jnp.where(lane == 0, 0.5 * a, a)
        elif fault == "b_altered":
            b = b.at[0].add(0.05)
        return dataclasses.replace(r, alpha=a, b=b)

    solver_fused.solve_fused_batched_qp = broken
    sharded_lanes.solve_fused_batched_qp = broken
    jax.clear_caches()
    return real


def unplant(real):
    import jax
    from repro.core import sharded_lanes, solver_fused
    solver_fused.solve_fused_batched_qp = real
    sharded_lanes.solve_fused_batched_qp = real
    jax.clear_caches()


CELLS = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")
         ["workloads"]]
CASES = [(c, f) for c in CELLS for f in FAULTS]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch, tmp_path):
    tiny(monkeypatch, tmp_path, impl="jnp")
    run.import_program()
    real = plant(fault)
    try:
        res = run.run(run.parse(["--workload", cell, "--seed", "17",
                                 "--seconds", "0.1"]))
    finally:
        unplant(real)
    assert res["correct"] is False, res["checks"]
