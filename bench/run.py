"""Chip benchmark of the RoundEngine: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up, in this one process: the cell's inputs from the seed (harness.py),
the program's RoundEngine, and one whole chunk of rounds through the
window's own entry point, ``eng.scheduler.run_span`` — which compiles (or
loads from the persistent cache) the program the window runs, and leaves
the state the check compares.  A node-sharded engine takes one chunk more:
its first call gets the state on one device and compiles a second program
for the sharded state every later call passes.  The window then drives
whole chunks back to back for ``--seconds`` and ends on
``block_until_ready``.  With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result holds the
per-layer metrics the readers in ``bench/metrics/`` take from the trace.
After the window: the peak device memory is read, the program's state is
freed, and the plain reference decides ``correct`` (check.py).

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  ``--rehearse`` runs a tiny version of the cell on
whatever backend is present; its result names that platform.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# A tiny stand-in of any cell, for --rehearse and the tests: the same code
# paths at sizes a CPU runs in seconds.
TINY = {"n_nodes": 16, "chunk_rounds": 2, "model": {"width": 8},
        "data": {"n_train": 1024}}
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def tiny(cell):
    """The cell at TINY sizes."""
    cfg = dict(cell.config)
    for k, v in TINY.items():
        cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
    cell.config = cfg
    return cell


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), keeping every program."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


class Compiles:
    """Counts traces and backend compiles while ``on``."""

    def __init__(self):
        self.on, self.count = False, 0

    def __call__(self, event, duration, **kw):
        if self.on and event in COMPILE_EVENTS:
            self.count += 1


def memory(devices, key):
    vals = [(d.memory_stats() or {}).get(key) for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def peak_bytes(devices):
    """Peak device bytes of the fullest chip: the allocator's peak of
    buffers in use plus its peak of memory reserved for the programs'
    temporaries, which the TPU runtime keeps apart from the buffers (an
    upper bound where the two peaks fall at different times)."""
    vals = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            vals.append(st["peak_bytes_in_use"] + st.get("peak_bytes_reserved", 0))
    return max(vals) if vals else None


class Tamper:
    """Faults a test plants under the timed path; no benchmark run sets one."""

    def __init__(self, loss_wrap=None, engine=None):
        self.loss_wrap, self.engine = loss_wrap, engine


def run(name, seed, seconds, trace, *, require_chip=True, rehearse=False,
        tamper=None, log=sys.stderr):
    """One run of cell ``name``: the result dict, or None (nothing printed
    on stdout) when the chip is missing."""
    import jax

    import harness
    from check import Judge, verdict

    enable_cache()
    devices = jax.devices()
    marks = [("jax", time.time())]
    cell = harness.load_cell(name)
    if rehearse:
        cell = tiny(cell)
    platform = devices[0].platform
    if require_chip and (platform != "tpu" or len(devices) < cell.chips):
        print(f"bench: cell {name} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {platform} device(s)", file=log)
        return None
    cfg, chunk, n = cell.config, cell.config["chunk_rounds"], cell.config["n_nodes"]
    used = devices[:cell.chips]
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    inputs = harness.make_inputs(cell, seed)
    marks.append(("inputs", time.time()))
    tamper = tamper or Tamper()
    eng = harness.build_engine(cell, inputs, seed, loss_wrap=tamper.loss_wrap)
    if tamper.engine is not None:
        tamper.engine(eng)
    # the first chunk: compiles the window's program and yields the
    # state the check compares against the reference
    marks.append(("engine", time.time()))
    eng.scheduler.run_span(0, chunk)
    first = jax.device_get(eng.params)
    marks.append(("first chunk", time.time()))
    n_params = eng.n_params
    if not rehearse and n_params != cfg["model"]["params_per_node"]:
        raise ValueError(f"the model has {n_params} parameters a node, the "
                         f"configuration says {cfg['model']['params_per_node']}")
    rnd = chunk
    if eng.sharded:
        # the first chunk took state on one device; later chunks take it
        # sharded over the mesh, which is another program: warm it too
        eng.scheduler.run_span(rnd, chunk)
        rnd += chunk
    resident = memory(used, "bytes_in_use")

    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    # set-up's garbage is not the window's: collect it once, and keep the
    # objects that outlive set-up out of every later collection
    gc.collect()
    gc.freeze()
    setup_s = time.time() - T_START
    marks.append(("rest", time.time()))
    compiles.on = True
    start = rnd
    chunk_s = []
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.run_span"):
            eng.scheduler.run_span(rnd, chunk)
        chunk_s.append(time.perf_counter() - t)
        rnd += chunk
        if time.perf_counter() - t0 >= seconds:
            break
    with jax.profiler.TraceAnnotation("bench.block"):
        jax.block_until_ready(eng.params)
    window_s = time.perf_counter() - t0
    compiles.on = False
    jax.monitoring.unregister_event_duration_listener(compiles)
    if trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(used)
    rounds = rnd - start
    if compiles.count:
        print(f"bench: {compiles.count} compilation(s) inside the window", file=log)
        raise SystemExit(1)

    del eng
    gc.unfreeze()
    gc.collect()
    print("bench: set-up seconds: " + ", ".join(
        f"{name} {t - before:.3f}" for (name, t), before
        in zip(marks, [T_START] + [t for _, t in marks])), file=log)
    print(f"bench: {len(chunk_s)} chunks, host seconds a chunk: min {min(chunk_s)!r} "
          f"median {sorted(chunk_s)[len(chunk_s) // 2]!r} max {max(chunk_s)!r} "
          f"(chunk {chunk_s.index(max(chunk_s))})", file=log)

    model = harness.load_module("configs", cfg["model"]["reference"])
    judge = Judge(model, cfg, cell.traffic["dl"], inputs, seed)
    numbers = judge.numbers(first)
    correct, shown, lines = verdict(numbers, cell.limits)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": rounds, "failed": 0 if correct else rounds}
    if trace:
        metrics, device_extra, breakdown = per_layer(cell, trace_dir, rounds, resident,
                                                     n_params)
        device.update(device_extra)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        result.update(metrics={
            "node_rounds_per_s": {"value": n * rounds / window_s, "unit": "node-rounds/s"},
            "peak_hbm_gb": {"value": (peak or float("nan")) / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}, device=device)
    result["checks"] = shown
    print(f"bench: worst leaf {numbers['worst_leaf']}; left out (no gradient): "
          f"{judge.skipped}", file=log)
    for line in lines:
        print(line, file=log)
    return result


def per_layer(cell, trace_dir, rounds, resident, n_params):
    """(metrics, device busy/window, breakdown) from the traced window."""
    import harness
    import traces

    tr = traces.load(traces.find_xplane(trace_dir))
    lo, hi = traces.window(tr)
    cfg = cell.config
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        raise KeyError(f"bench/peaks.json has no entry for device kind {kind!r}")
    shards = cell.traffic["dl"].get("shard_devices") or 1
    ctx = {"config": cfg, "rounds": rounds, "window_s": (hi - lo) / 1e9,
           "n_nodes": cfg["n_nodes"], "n_local": cfg["n_nodes"] // shards,
           "params_per_node": n_params, "degree": cfg["degree"],
           "chips": cell.chips, "peaks": peaks[kind], "resident_bytes": resident,
           "flops": harness.load_module("flops", cfg["model"]["flops"])}
    wanted = declared_metrics(cell.name)
    metrics = {}
    for path in sorted((BENCH / "metrics").glob("*.py")):
        if wanted is not None and path.stem not in wanted:
            continue
        value = harness.load_module("metrics", path.stem).read(tr, ctx)
        if value is not None and math.isfinite(value):
            metrics[path.stem] = {"value": value, "unit": wanted.get(path.stem, "")
                                  if wanted else ""}
    device = {"busy_s": traces.busy_seconds(tr), "window_s": (hi - lo) / 1e9}
    breakdown = {"device_ops": traces.top_ops(tr), "idle_gaps": traces.idle_gaps(tr)}
    return metrics, device, breakdown


def declared_metrics(cell_name):
    """{metric: unit} of the per-layer metrics BENCHMARK.json declares for
    this cell, or None where there is no BENCHMARK.json."""
    path = ROOT / "BENCHMARK.json"
    if not path.exists():
        return None
    spec = json.loads(path.read_text())
    return {m["name"]: m["unit"] for m in spec["per_layer"]
            if "workloads" not in m or cell_name in m["workloads"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="a tiny version of the cell on any backend (names its platform)")
    args = ap.parse_args()
    result = run(args.workload, args.seed, args.seconds, args.trace,
                 require_chip=not args.rehearse, rehearse=args.rehearse)
    if result is None:
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
