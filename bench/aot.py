"""Compile a cell's chunk for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <cell>
    (a four-chip cell also needs
     XLA_FLAGS=--xla_force_host_platform_device_count=4)

Builds the cell's RoundEngine at its real sizes on the CPU, then lowers the
scheduler's chunk for one chip (or the 2x2 mesh) of a described v5e, with
the program steered onto its TPU branches (Pallas kernels compiled, not
interpreted), and prints the compiled program's memory analysis per device
and which kernels and collectives it holds.  Nothing runs; what the chip's
compiler refuses here costs no chip time.  Prints one JSON line.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

KERNELS = ("gossip_mix_nodes", "abs_survival_rows", "secure_mask_keyed")
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "reduce-scatter")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax import shard_map
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    import harness

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    inputs = harness.make_inputs(cell, args.seed)
    eng = harness.build_engine(cell, inputs, args.seed)
    sched = eng.scheduler
    chunk = cell.config["chunk_rounds"]
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"   # the program's TPU branches
    try:
        xs = sched._stage_xs(0, chunk)
        state = (eng.params, eng.opt_state, eng.share_state)
        if eng.sharded:
            mesh = Mesh(topo.devices[:cell.chips], ("nodes",))
            eng._mesh = mesh
            specs = tuple(sched._node_pspec(t) for t in state)
            xs_specs = sched._xs_pspec(xs)
            fn = jax.jit(shard_map(
                sched._chunk_fn_sharded, mesh=mesh, in_specs=specs + (xs_specs,),
                out_specs=specs + (P(), P(), {k: P() for k in _stat_keys()}),
                check_vma=False))
            sds = lambda tree, spec: jax.tree_util.tree_map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=NamedSharding(mesh, s)), tree, spec)
            shapes = [sds(t, s) for t, s in zip(state, specs)] + [sds(xs, xs_specs)]
        else:
            one = SingleDeviceSharding(topo.devices[0])
            sds = lambda tree: jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
            fn = sched._chunk_jit
            shapes = [sds(t) for t in state] + [sds(xs)]
        t0 = time.perf_counter()
        compiled = fn.lower(*shapes).compile()
        compile_s = time.perf_counter() - t0
    finally:
        jax.default_backend = real_backend
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        "workload": cell.name, "chips": cell.chips, "compile_s": compile_s,
        "per_device_bytes": {"args": m.argument_size_in_bytes,
                             "out": m.output_size_in_bytes,
                             "temp": m.temp_size_in_bytes,
                             "code": m.generated_code_size_in_bytes},
        "kernels": [k for k in KERNELS if k in text],
        "collectives": {c: text.count(c + "(") + text.count(c + "-start(")
                        for c in COLLECTIVES},
        "staged_xs": sorted(xs)}))
    return 0


def _stat_keys():
    from repro.core import faults

    return faults.STAT_KEYS


if __name__ == "__main__":
    sys.exit(main())
