"""The control comes out not correct: the plain reference computed in
bfloat16 (the precision below the configuration's float32), put in the
program's place, fails the cell's limits; the float32 reference in the
program's place passes them.  At a tiny size on the CPU; on the chip the
same readings are taken at the cell's own size by bench/readings.py."""
import jax.numpy as jnp
import pytest

from _bench_path import BENCH  # noqa: F401

import harness  # noqa: E402
import run as bench_run  # noqa: E402
from check import Judge, verdict  # noqa: E402

CELLS = [c for c in harness.cell_names() if harness.load_cell(c).chips == 1]


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(cell_name):
    cell = bench_run.tiny(harness.load_cell(cell_name))
    seed = 2147483647 + 40
    inputs = harness.make_inputs(cell, seed)
    model = harness.load_module("configs", cell.config["model"]["reference"])
    judge = Judge(model, cell.config, cell.traffic["dl"], inputs, seed)
    assert verdict(judge.numbers(judge.x_ref), cell.limits)[0]
    ok, shown, _ = verdict(judge.numbers(judge.control(jnp.bfloat16)), cell.limits)
    assert not ok, shown
