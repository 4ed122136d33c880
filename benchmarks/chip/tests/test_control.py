"""The control comes out as not correct (needs a TPU; skips elsewhere).

    python -m pytest benchmarks/chip/tests/test_control.py   # on a TPU host

At each one-chip cell's own size, the program on one seed must read
within the cell's limits, and the control on the same seed must fail one
of them.  The control is ``limits.py``'s ``xla_high``: the program's XLA
path with every ``HIGHEST`` matmul planted at ``HIGH`` (three bf16
passes), the nearest precision below the configuration's.  The readings
on a dozen seeds and three, which set the limits, are made by
``limits.py`` and listed in PERF.md.
"""

import pytest

import limits
import run

SEED = 2_147_483_911
CELLS = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")
         ["workloads"] if w["chips"] == 1]


@pytest.fixture(scope="module")
def jax():
    import jax
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control reads lower matmul precision: needs a TPU")
    run.import_program()
    run.configure_jax()
    yield jax
    limits.plant_precision(jax, "HIGHEST")


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_fails_where_the_program_passes(jax, cell_name):
    cell = run.load_cell(cell_name)
    entry = run.load_entry(cell.traffic["entry"])
    lim = cell.spec["limits"]
    limits.plant_precision(jax, "HIGHEST")
    prog = limits.reading(jax, cell, entry, cell.traffic, SEED, "program")
    assert all(prog[k] <= v for k, v in lim.items()), prog
    kw, prec = limits.CONTROLS["xla_high"]
    limits.plant_precision(jax, prec)
    ctrl = limits.reading(jax, cell, entry, dict(cell.traffic, **kw), SEED,
                          "xla_high")
    assert any(ctrl[k] > v for k, v in lim.items()), ctrl
