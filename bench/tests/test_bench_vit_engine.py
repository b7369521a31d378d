"""ViT-S/4 through the RoundEngine on the CPU, built by the harness as the
benchmark builds it: its rounds against the plain D-PSGD reference, and
``bench/run.py --rehearse`` on its cell."""
import json
import os
import subprocess
import sys

import jax

from _bench_path import BENCH, ROOT

import harness  # noqa: E402
import run as bench_run  # noqa: E402
from check import Judge  # noqa: E402

CELL = "vits4-cifar10-n16.dynamic-full"


def test_engine_rounds_match_the_dpsgd_reference():
    """Two rounds of the RoundEngine with the ViT at N=8, built by the
    harness, against ``bench/reference.Reference`` run from the same seed."""
    cell = bench_run.tiny(harness.load_cell(CELL))
    cell.config = {**cell.config, "n_nodes": 8, "batch_size": 8,
                   "model": {**cell.config["model"], "width": 32}}
    seed = 2147483647 + 21
    inputs = harness.make_inputs(cell, seed)
    eng = harness.build_engine(cell, inputs, seed)
    eng.scheduler.run_span(0, cell.config["chunk_rounds"])
    model = harness.load_module("configs", "vit_ref")
    judge = Judge(model, cell.config, cell.traffic["dl"], inputs, seed)
    numbers = judge.numbers(jax.device_get(eng.params))
    # float32 on both sides: the rounding of the model against its
    # reference (test_bench_vit.py), through two rounds of SGD and mixing,
    # reads ~1e-6
    for k in ("change_gap", "median_gap", "spread_gap", "lead_spread_gap", "loss_gap"):
        assert numbers[k] < 1e-4, (k, numbers[k], numbers["worst_leaf"])
    # and the rounds did move the weights, apart from one another
    assert min(judge.ref_change.values()) > 0 and min(judge.ref_spread.values()) > 0


def test_rehearse_runs_the_cell_on_the_cpu():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL, "--seed", "2147483999",
         "--seconds", "0.2", "--trace", "0", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["device"]["platform"] == "cpu", result
