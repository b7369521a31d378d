"""Readings that set a cell's limits of ``correct`` (not run by the benchmark).

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]

For each seed, in this one process: the program's first chunk through the
window's entry point (as bench/run.py's set-up drives it) and its numbers
against the float32 reference — the lower readings.  For each control
seed, also the numbers of candidates put in the program's place: the
reference computed in bfloat16 (the control), the initial weights (a step
that keeps its state), and the float32 reference with a fault planted
(half of each batch left out; no exchange between nodes) — the upper
readings.  One JSON line per seed; with --out, the
lines are also appended to that file.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402


def readings(name, seed, control, rehearse=False):
    import jax
    import jax.numpy as jnp

    import harness
    from check import Judge

    cell = harness.load_cell(name)
    if rehearse:
        cell = bench_run.tiny(cell)
    inputs = harness.make_inputs(cell, seed)
    t0 = time.perf_counter()
    eng = harness.build_engine(cell, inputs, seed)
    eng.scheduler.run_span(0, cell.config["chunk_rounds"])
    first = jax.device_get(eng.params)
    program_s = time.perf_counter() - t0
    del eng
    gc.collect()
    t0 = time.perf_counter()
    model = harness.load_module("configs", cell.config["model"]["reference"])
    judge = Judge(model, cell.config, cell.traffic["dl"], inputs, seed)
    out = {"workload": name, "seed": seed, "program_s": program_s,
           "reference_s": time.perf_counter() - t0, "skipped": judge.skipped,
           "equal_start": judge.equal_start, "lead": judge.lead, "ref_changes": judge.ref_change,
           "ref_spreads": judge.ref_spread,
           "program": judge.numbers(first)}
    if control:
        out["unchanged_state"] = judge.numbers(judge.x0)
        out["control_bf16"] = judge.numbers(judge.control(jnp.bfloat16))
        for fault in ("half_batch", "no_exchange"):
            out[fault] = judge.numbers(judge.control(jnp.float32, fault))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    bench_run.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds:
        line = json.dumps(readings(args.workload, seed, seed in control, args.rehearse))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
