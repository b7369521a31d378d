"""Engine throughput: legacy per-round dispatch vs scanned chunks, and
sparse neighbor-indexed mixing vs dense W @ X at the paper's 1000+ node
emulation scale.

Part 1 measures rounds/sec of the RoundEngine at chunk sizes 0 (legacy
host-driven per-round dispatch with host-stacked batches), 1, 8, 32 for N
in {64, 256} — the perf regression gate is chunk=32 ≥ 3x chunk=1 at N=256.

Part 2 measures sparse vs dense mixing at N=1024, d=6, chunk=32 on static
d-regular and dynamic (per-round random d-regular) topologies, recording
rounds/s and the peak per-chunk topology staging bytes: the sparse path
stages (R, N, D) neighbor tables (O(N·d)) and keeps full-length chunks,
while the dense path stages (R, N, N) W stacks that hit the 64 MB cap and
silently shrink the chunk exactly where scale matters.  Gate: sparse ≥ 3x
dense rounds/s at N=1024.

Part 3 measures the node-sharded engine (shard_devices=4 by default, a
v5e host's chips; both the 'gather' and the collective_permute 'ppermute'
gossip lowerings) against the single-device engine at N=1024, d=6.  On
CPU-emulated devices that is the emulation cost of multi-device execution
on one box (emulated collectives are host rendezvous; the wire win is a
TPU story); on the CPU backend it runs in a subprocess with XLA_FLAGS set
when the current process has fewer devices.

Part 4 measures payload-form compressed sharing (DLConfig.payload='on':
(N, k) idx/val payloads aggregated in one O(N·d·k) scatter pass) against
the dense-mask oracle ('off': scattered (N, P) masks + two apply_W
passes) at N=1024, d=6, budget=0.01, chunk=32 — the paper's sparsified
1000+-node scenario where the wire format, not the math, decides
throughput.  Gates: payload ≥ 3x dense-mask rounds/s (median), and the
sharing stage's per-round staged message bytes reduced ≥ 10x.

All timed sections record min/median/mean rounds/s over the repeats; the
headline ``rounds_per_s`` (and any CI threshold) is the *median* — this
box's spread under load makes best-of-N misleading.

The workload is a distributed-consensus round — each node pulls its local
batch toward its mean with a quadratic loss, then gossips — deliberately
the cheapest possible per-round device program, so the measurement isolates
the *execution machinery* (per-round dispatch, host batch staging, mixing
FLOPs and topology staging, host<->device metric syncs) rather than model
FLOPs.  Training benchmarks (bench_scalability etc.) cover the model-bound
regime.

    PYTHONPATH=src python benchmarks/bench_engine.py --rounds 64

Results go through benchmarks/common.save_results so the perf trajectory
is recorded (results/bench_engine.json).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.core import DLConfig, RoundEngine
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.optim import make_optimizer

from benchmarks.common import save_results
from repro.utils.compile_cache import enable_compile_cache

SHAPE = (2, 2, 1)  # 4-dim inputs; batch staging stays negligible
P_DISPATCH = 4     # part 1: 4-param state isolates the dispatch machinery
P_MIXING = 256     # part 2: 256-param state so mixing FLOPs are the measured axis
P_PAYLOAD = 1024   # part 4: 1024-param state so the sharing stage dominates
#                    (budget 0.01 -> k=10 payload coords per node)


def _rps_stats(samples):
    """min/median/mean rounds-per-second over the timed repeats.  The box
    is noisy (3.4-16x spread observed under load), so recorded headline
    numbers and CI gates use the *median*, not best-of-N."""
    return {
        "rounds_per_s": statistics.median(samples),
        "rounds_per_s_min": min(samples),
        "rounds_per_s_mean": sum(samples) / len(samples),
    }


def _loss(p, x, y):
    # consensus: pull every 4-wide row of the state toward the local batch
    # mean — the state dim P is free while the dataset stays 4-dim
    t = x.reshape(x.shape[0], -1).mean(0)
    return jnp.mean((p["w"].reshape(-1, t.shape[0]) - t) ** 2)


def _acc(p, x, y):
    return -_loss(p, x, y)  # consensus error, negated so bigger = better


def _engine(n_nodes: int, chunk: int, topology: str = "regular", degree: int = 5,
            mixing: str = "auto", p_dim: int = P_DISPATCH, **dl_kw) -> RoundEngine:
    ds = make_dataset("cifar10", n_train=2048, n_test=64, shape=SHAPE, sigma=2.0)
    parts = sharding_partition(ds.train_y, n_nodes, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=4, seed=0)
    dl_kw = {"local_steps": 1, "eval_every": 10**9, **dl_kw}
    dl = DLConfig(n_nodes=n_nodes, topology=topology, degree=degree,
                  batch_size=4,
                  chunk_rounds=chunk, mixing=mixing, **dl_kw)
    init = lambda key: {"w": jax.random.normal(key, (p_dim,))}
    return RoundEngine(dl, init, _loss, _acc, make_optimizer("sgd", 0.05), batcher)


def run(rounds: int = 64, nodes=(64, 256), chunks=(0, 1, 8, 32), repeats: int = 5,
        log: bool = True, save: bool = True):
    recs = []
    if rounds <= 0:  # CI runs the two sections as separate smoke steps
        return recs
    for n in nodes:
        rps = {}
        for chunk in chunks:
            eng = _engine(n, chunk)
            # warm up with the same round count so every scan length the
            # timed run needs (full chunks + remainder) is already compiled
            eng.run(rounds=rounds, log=False)
            samples = []
            for _ in range(repeats):
                t0 = time.time()
                eng.run(rounds=rounds, log=False)
                samples.append(rounds / (time.time() - t0))
            stats = _rps_stats(samples)
            rps[chunk] = stats["rounds_per_s"]
            name = "legacy" if chunk == 0 else f"chunk{chunk}"
            recs.append({
                "name": f"N{n}-{name}", "n_nodes": n, "chunk": chunk,
                "rounds": rounds, **stats,
            })
            if log:
                print(f"  N={n:4d} {name:8s} {stats['rounds_per_s']:8.1f} rounds/s "
                      f"(min {stats['rounds_per_s_min']:.1f})", flush=True)
        if log and 1 in rps and 32 in rps:
            line = f"  N={n:4d} speedup chunk32/chunk1: {rps[32] / rps[1]:.2f}x"
            if 0 in rps:
                line += f", chunk32/legacy: {rps[32] / rps[0]:.2f}x"
            print(line, flush=True)
    if save:
        save_results("bench_engine", recs)
    return recs


def run_sparse(rounds: int = 32, n: int = 1024, degree: int = 6, chunk: int = 32,
               repeats: int = 3, topologies=("dynamic",), log: bool = True):
    """Sparse-vs-dense mixing at emulation scale (N=1024, d=6, chunk=32).

    The gate case is the *dynamic* per-round d-regular topology — the
    paper's 1000+-node scenario — where the dense path structurally loses
    three ways: O(N²·P) mixing FLOPs, (R, N, N) host W-stack builds +
    transfers, and chunk shrinkage under the 64 MB W-stack cap (visible in
    ``chunk_effective``); sparse ≥ 3x dense holds across box load.  A
    static-graph comparison is e2e-noisy on a CPU box (XLA's serial gather
    vs a multithreaded matmul under throttling), so the static claim is
    covered by the isolated mixing-op micro (``_mix_op_micro``) appended
    to the records; pass topologies=("regular", "dynamic") for the e2e
    static case too.

    Uses a P=256 consensus state (P_MIXING; dataset stays 4-dim so batch
    staging is unchanged) so the mixing term is the measured axis rather
    than rounding error next to the fixed per-round dispatch cost.
    Records rounds/s, the effective chunk length, and peak per-chunk
    topology staging bytes."""
    recs = []
    for topo in topologies:
        engines = {}
        for mixing in ("dense", "sparse"):
            eng = _engine(n, chunk, topology=topo, degree=degree, mixing=mixing,
                          p_dim=P_MIXING)
            eng.run(rounds=rounds, log=False)  # warm-up compiles every scan length
            engines[mixing] = eng
        # interleave timed repeats so box-level CPU throttling hits both
        # paths equally and the ratio stays meaningful
        samples = {"dense": [], "sparse": []}
        for _ in range(repeats):
            for mixing, eng in engines.items():
                t0 = time.time()
                eng.run(rounds=rounds, log=False)
                samples[mixing].append(rounds / (time.time() - t0))
        rps = {}
        for mixing, eng in engines.items():
            stats = _rps_stats(samples[mixing])
            rps[mixing] = stats["rounds_per_s"]
            recs.append({
                "name": f"N{n}-d{degree}-{topo}-{mixing}", "n_nodes": n,
                "degree": degree, "topology": topo, "mixing": mixing,
                "chunk": chunk, "chunk_effective": eng.chunk, "rounds": rounds,
                **stats,
                "topo_stage_peak_bytes": eng.topo_stage_bytes_peak,
            })
            if log:
                print(f"  N={n} d={degree} {topo:8s} {mixing:6s} "
                      f"{rps[mixing]:8.1f} rounds/s  chunk_eff={eng.chunk}"
                      f"  topo_stage={eng.topo_stage_bytes_peak / 1e6:.2f}MB",
                      flush=True)
        if log:
            print(f"  N={n} d={degree} {topo:8s} speedup sparse/dense: "
                  f"{rps['sparse'] / rps['dense']:.2f}x", flush=True)
    recs += _mix_op_micro(n, degree, P_MIXING, log=log)
    return recs


def _mix_op_micro(n: int, degree: int, p: int, iters: int = 100, log: bool = True):
    """Isolated W @ X op: neighbor-indexed gather+contract vs dense matmul
    — the undiluted O(N·d·P) vs O(N²·P) mixing cost, without the round
    program's shared O(N·P) costs (local train, state packing)."""
    from repro.core.mixing import apply_W
    from repro.core.topology import Graph, SparseTopology

    g = Graph.regular_circulant(n, degree)
    st = SparseTopology.from_graph(g)
    ops = {
        "sparse": jax.jit(lambda x, t=jax.tree_util.tree_map(jnp.asarray, st):
                          apply_W(t, x)),
        "dense": jax.jit(lambda x, W=jnp.asarray(g.metropolis_hastings(),
                                                 jnp.float32): apply_W(W, x)),
    }
    X = jax.random.normal(jax.random.key(0), (n, p))
    recs = []
    us = {}
    for mixing, f in ops.items():
        f(X).block_until_ready()
        t0 = time.time()
        for _ in range(iters):
            out = f(X)
        out.block_until_ready()
        us[mixing] = (time.time() - t0) / iters * 1e6
        recs.append({"name": f"N{n}-d{degree}-P{p}-mixop-{mixing}", "n_nodes": n,
                     "degree": degree, "mixing": mixing, "op_us": us[mixing]})
        if log:
            print(f"  N={n} d={degree} P={p} mixop {mixing:6s} {us[mixing]:8.1f} us",
                  flush=True)
    if log:
        print(f"  N={n} d={degree} P={p} mixop speedup sparse/dense: "
              f"{us['dense'] / us['sparse']:.2f}x", flush=True)
    return recs


def run_payload(rounds: int = 16, n: int = 1024, degree: int = 6, chunk: int = 32,
                budget: float = 0.01, repeats: int = 3, log: bool = True):
    """Part 4: payload-form compressed sharing vs the dense-mask oracle at
    the paper's sparsified emulation scale (N=1024, d=6, budget=0.01,
    chunk=32, static d-regular overlay).

    Each case holds *everything but the aggregation form* fixed: payload
    'on' and 'off' engines run the same coordinate selection and produce
    the same trajectories (property-tested in tests/test_sparse_mixing.py);
    the measured axis is O(N·d·k) gather+scatter over (N, k) payloads vs
    two full O(N·d·P) apply_W passes over scattered (N, P) masks, plus the
    sharing stage's staged message bytes (``share_stage_bytes``).

    The *gate* case is randomk with the strided sampler on pure
    consensus-gossip rounds (local_steps=0): selection is O(N), the
    receive is the windowed-scatter fast path, so the round is
    sharing-dominated and the aggregation form is what's measured —
    payload ≥ 3x dense-mask rounds/s and staging ≥ 10x less (median).
    The topk case (selection = a lax.top_k sort over the full (N, P)
    state, shared by both paths and O(N·P·log) on CPU) is recorded
    alongside, un-gated: its e2e ratio is selection-diluted on CPU; the
    histogram-threshold selector (kernels/sparsify.topk_threshold_rows)
    is the TPU answer to that term.  P=1024 (P_PAYLOAD) so the sharing
    stage dominates the fixed dispatch cost, mirroring real models where
    P ≫ N·d.
    """
    recs = []
    if rounds <= 0:
        return recs
    cases = {
        "randomk-strided": dict(sharing="randomk", randk_sampler="strided",
                                local_steps=0),
        "topk": dict(sharing="topk"),
    }
    for case, case_kw in cases.items():
        engines = {}
        for payload in ("off", "on"):
            eng = _engine(n, chunk, topology="regular", degree=degree,
                          p_dim=P_PAYLOAD, budget=budget, payload=payload,
                          **case_kw)
            eng.run(rounds=rounds, log=False)  # warm-up compiles every scan length
            engines[payload] = eng
        # interleave timed repeats so box load hits both paths equally
        samples = {"off": [], "on": []}
        for _ in range(repeats):
            for payload, eng in engines.items():
                t0 = time.time()
                eng.run(rounds=rounds, log=False)
                samples[payload].append(rounds / (time.time() - t0))
        rps = {}
        for payload, eng in engines.items():
            stats = _rps_stats(samples[payload])
            rps[payload] = stats["rounds_per_s"]
            recs.append({
                "name": f"N{n}-d{degree}-{case}-b{budget}-payload-{payload}",
                "n_nodes": n, "degree": degree, "case": case,
                "sharing": case_kw["sharing"], "budget": budget,
                "payload": payload, "chunk": chunk, "rounds": rounds, **stats,
                "wire_dtype": eng.wire_dtype,
                "share_stage_bytes": eng.share_stage_bytes,
            })
            if log:
                print(f"  N={n} d={degree} {case:14s} b={budget} "
                      f"payload={payload:3s} {rps[payload]:8.1f} rounds/s  "
                      f"share_stage={eng.share_stage_bytes / 1e3:.1f}KB",
                      flush=True)
        if log:
            stage_ratio = (engines["off"].share_stage_bytes
                           / max(engines["on"].share_stage_bytes, 1))
            print(f"  N={n} d={degree} {case:14s} speedup payload/dense: "
                  f"{rps['on'] / rps['off']:.2f}x  stage-bytes ratio: "
                  f"{stage_ratio:.0f}x", flush=True)
    return recs


def run_async(rounds: int = 96, n: int = 1024, degree: int = 6, chunk: int = 32,
              base_compute_s: float = 0.05, straggler_factor: float = 10.0,
              straggler_frac: float = 0.1, targets=(0.2, 0.3),
              log: bool = True):
    """Part 5: event-driven async gossip (semantics='async') vs the
    synchronous round barrier at the paper's 1000+-node scale, under a
    10x-straggler compute-time distribution (10% of nodes at 10x the base
    50 ms — ``network.straggler_compute_times``), network='lan'.

    The workload is the *gradient-work-limited* regime the AD-PSGD claim
    lives in: an MLP classification task (benchmarks/common.model_fns)
    where accuracy is bought with local SGD steps over many rounds — not
    the consensus micro-benchmark of parts 1-4, whose loss drops mostly
    through init-variance averaging and would hide the work-rate
    difference.  Sync pays the straggler at every round barrier (round
    time = max over nodes, so every node takes 1 gradient step per ~0.5 s
    of simulated time); async fires event cohorts on the virtual clock,
    so the fast 90% of nodes take ~10x more steps per simulated second,
    gossiping against possibly-stale straggler rows.

    The headline metric is **simulated wall-clock until the mean node
    accuracy reaches a fixed target** (10-class task, random = 0.10;
    targets 0.20 and 0.30).  The *gate* is the 0.30 target: async must
    reach it in <= 0.5x sync's simulated time (observed ~8-9x lower).
    Both trajectories are deterministic functions of the seed, so no
    repeats are needed (the measurement is virtual time, not wall time).
    Async's per-node virtual-clock spread, staleness, and event counts
    are recorded alongside (scheduler extra metrics).
    """
    from repro.data import NodeBatcher, make_dataset, sharding_partition
    from repro.optim import make_optimizer as _mk_opt

    from benchmarks.common import model_fns

    recs = []
    if rounds <= 0:
        return recs
    ds = make_dataset("cifar10", n_train=8 * n, n_test=256, sigma=4.0, seed=7)
    gate_target = max(targets)
    engines = {}
    for sem in ("sync", "async"):
        parts = sharding_partition(ds.train_y, n, 2, seed=0)
        batcher = NodeBatcher(ds.train_x, ds.train_y, parts, 8, seed=0)
        init, loss, acc = model_fns("mlp", width=4)
        dl = DLConfig(n_nodes=n, topology="regular", degree=degree,
                      local_steps=2, batch_size=8, chunk_rounds=chunk,
                      eval_every=8, semantics=sem, network="lan",
                      compute_time_s=base_compute_s,
                      straggler_factor=straggler_factor,
                      straggler_frac=straggler_frac)
        eng = RoundEngine(dl, init, loss, acc, _mk_opt("sgd", 0.05), batcher)
        eng.run(rounds=rounds, log=False)
        engines[sem] = eng

    def time_to(hist, target):
        for rec in hist:
            if rec["acc_mean"] >= target:
                return rec["sim_time_s"]
        return None

    times = {}
    for sem, eng in engines.items():
        tt = {t: time_to(eng.history, t) for t in targets}
        times[sem] = tt
        last = eng.history[-1]
        rec = {
            "name": f"N{n}-d{degree}-{sem}-straggler{straggler_factor:g}x",
            "n_nodes": n, "degree": degree, "semantics": sem,
            "chunk": chunk, "rounds": rounds, "workload": "mlp",
            "compute_time_s": base_compute_s,
            "straggler_factor": straggler_factor,
            "straggler_frac": straggler_frac,
            "sim_time_to_acc_s": {f"{t:g}": v for t, v in tt.items()},
            "sim_time_total_s": eng.sim_time_s,
            "final_acc": last["acc_mean"],
        }
        for k in ("events_total", "events_min", "events_max", "vclock_min_s",
                  "vclock_median_s", "vclock_max_s", "staleness_mean",
                  "staleness_max"):
            if k in last:
                rec[k] = last[k]
        recs.append(rec)
        if log:
            fmt = ", ".join(
                f"acc{t:g} {v:.1f}s" if v is not None else f"acc{t:g} -"
                for t, v in tt.items()
            )
            print(f"  N={n} d={degree} {sem:6s} sim-to-target: {fmt}  "
                  f"(total {eng.sim_time_s:.1f}s, final acc "
                  f"{last['acc_mean']:.4f})", flush=True)
    speedups = {
        t: times["sync"][t] / times["async"][t]
        for t in targets
        if times["sync"].get(t) and times["async"].get(t)
    }
    gate = speedups.get(gate_target)
    recs.append({
        "name": f"N{n}-d{degree}-async-vs-sync-gate",
        "sim_speedup_to_target": {f"{t:g}": s for t, s in speedups.items()},
        "gate_target_acc": gate_target,
        "gate_min_speedup": 2.0,
        "gate_pass": bool(gate is not None and gate >= 2.0),
    })
    if log:
        fmt = ", ".join(f"acc{t:g} {s:.2f}x" for t, s in speedups.items())
        print(f"  N={n} d={degree} async/sync simulated-time speedup to "
              f"fixed accuracy: {fmt} (gate: acc{gate_target:g} >= 2x)",
              flush=True)
    return recs


def run_sharded(rounds: int = 12, n: int = 1024, degree: int = 6, chunk: int = 32,
                repeats: int = 3, devices: int = 4, log: bool = True):
    """Part 3: node-sharded vs single-device RoundEngine at the paper's
    1000+-node scale (N=1024, d=6, chunk=32, static d-regular overlay).

    The sharded engine runs the scanned chunk under shard_map over
    ``devices`` devices (CPU: emulated via
    ``XLA_FLAGS=--xla_force_host_platform_device_count``), in both
    distributed-gossip lowerings: 'gather' (all-gather + local neighbor
    gather) and 'ppermute' (slot-rebalanced per-offset collective_permute
    — the interconnect-native path; on CPU every emulated collective is a
    host rendezvous, so this records honest emulation numbers, not the TPU
    story).  The single-device baseline runs in the *same* process so both
    see the same host contention.

    On the CPU backend, when the current process doesn't have enough
    devices, the section re-executes itself in a subprocess with the XLA
    flag set (device count locks at first jax init), so a plain
    ``python benchmarks/bench_engine.py`` still records the sharded
    entries.  On an accelerator this process already holds the devices, so
    a child could not use them: it fails instead.
    """
    recs = []
    if rounds <= 0:
        return recs
    if jax.device_count() < devices:
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"--sharded-devices {devices} needs that many devices; "
                f"{jax.device_count()} {jax.default_backend()} devices are "
                "visible (pass --sharded-devices <= the visible count)"
            )
        return _run_sharded_subprocess(rounds, n, degree, chunk, repeats, devices, log)
    cases = {
        "single": dict(),
        f"sharded{devices}-gather": dict(shard_devices=devices, shard_backend="gather"),
        f"sharded{devices}-ppermute": dict(shard_devices=devices, shard_backend="ppermute"),
    }
    engines = {}
    for case, kw in cases.items():
        eng = _engine(n, chunk, topology="regular", degree=degree,
                      p_dim=P_MIXING, **kw)
        eng.run(rounds=rounds, log=False)  # warm-up compiles every scan length
        engines[case] = eng
    samples = {case: [] for case in cases}
    for _ in range(repeats):
        for case, eng in engines.items():
            t0 = time.time()
            eng.run(rounds=rounds, log=False)
            samples[case].append(rounds / (time.time() - t0))
    rps = {}
    for case, eng in engines.items():
        stats = _rps_stats(samples[case])
        rps[case] = stats["rounds_per_s"]
        recs.append({
            "name": f"N{n}-d{degree}-{case}", "n_nodes": n, "degree": degree,
            "topology": "regular", "chunk": chunk, "rounds": rounds,
            "n_devices": devices if case != "single" else 1, **stats,
        })
        if log:
            print(f"  N={n} d={degree} {case:18s} {rps[case]:8.1f} rounds/s",
                  flush=True)
    if log:
        for case in rps:
            if case != "single":
                print(f"  N={n} d={degree} speedup {case}/single: "
                      f"{rps[case] / rps['single']:.2f}x", flush=True)
    return recs


def _run_sharded_subprocess(rounds, n, degree, chunk, repeats, devices, log):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"
    ).strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    cmd = [
        sys.executable, os.path.abspath(__file__), "--_sharded-worker",
        "--sharded-rounds", str(rounds), "--sparse-nodes", str(n),
        "--sharded-degree", str(degree), "--sharded-repeats", str(repeats),
        "--sharded-devices", str(devices), "--sharded-chunk", str(chunk),
    ]
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=root, timeout=3600)
    recs = []
    for line in p.stdout.splitlines():
        if line.startswith("SHARDED_JSON:"):
            recs = json.loads(line[len("SHARDED_JSON:"):])
        elif log:
            print(line, flush=True)
    if not recs:
        raise RuntimeError(
            f"sharded bench subprocess produced no records:\n{p.stdout}\n{p.stderr}"
        )
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=64)
    ap.add_argument("--nodes", type=int, nargs="+", default=[64, 256])
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--sparse-rounds", type=int, default=32,
                    help="rounds for the N=1024 sparse-vs-dense section; 0 skips it")
    ap.add_argument("--sparse-nodes", type=int, default=1024)
    ap.add_argument("--sparse-repeats", type=int, default=3)
    ap.add_argument("--payload-rounds", type=int, default=16,
                    help="rounds for the N=1024 payload-vs-dense section; 0 skips it")
    ap.add_argument("--payload-budget", type=float, default=0.01)
    ap.add_argument("--payload-repeats", type=int, default=3)
    ap.add_argument("--async-rounds", type=int, default=96,
                    help="rounds/cohorts for the N=1024 async-vs-sync "
                         "straggler section (sync needs ~50 rounds to cross "
                         "the acc-0.3 gate target); 0 skips it")
    ap.add_argument("--async-straggler-factor", type=float, default=10.0)
    ap.add_argument("--sharded-rounds", type=int, default=12,
                    help="rounds for the N=1024 sharded-vs-single section; 0 skips it")
    ap.add_argument("--sharded-degree", type=int, default=6)
    ap.add_argument("--sharded-repeats", type=int, default=3)
    ap.add_argument("--sharded-devices", type=int, default=4,
                    help="node-axis mesh size (a v5e host has 4 chips)")
    ap.add_argument("--sharded-chunk", type=int, default=32)
    ap.add_argument("--_sharded-worker", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if getattr(args, "_sharded_worker"):
        if jax.device_count() < args.sharded_devices:
            # never re-spawn from the worker: the parent already set the
            # XLA flag; if it didn't take (non-CPU backend), fail loudly
            raise RuntimeError(
                f"sharded worker sees {jax.device_count()} devices, needs "
                f"{args.sharded_devices}; --xla_force_host_platform_device_count "
                "only applies to the CPU backend (set JAX_PLATFORMS=cpu)"
            )
        recs = run_sharded(args.sharded_rounds, n=args.sparse_nodes,
                           degree=args.sharded_degree, chunk=args.sharded_chunk,
                           repeats=args.sharded_repeats,
                           devices=args.sharded_devices)
        print("SHARDED_JSON:" + json.dumps(recs), flush=True)
        return
    recs = run(args.rounds, tuple(args.nodes), repeats=args.repeats, save=False)
    if args.sparse_rounds > 0:
        recs += run_sparse(args.sparse_rounds, n=args.sparse_nodes,
                           repeats=args.sparse_repeats)
    if args.payload_rounds > 0:
        recs += run_payload(args.payload_rounds, n=args.sparse_nodes,
                            budget=args.payload_budget,
                            repeats=args.payload_repeats)
    if args.async_rounds > 0:
        recs += run_async(args.async_rounds, n=args.sparse_nodes,
                          straggler_factor=args.async_straggler_factor)
    if args.sharded_rounds > 0:
        recs += run_sharded(args.sharded_rounds, n=args.sparse_nodes,
                            degree=args.sharded_degree,
                            chunk=args.sharded_chunk,
                            repeats=args.sharded_repeats,
                            devices=args.sharded_devices)
    # one write, after all sections; section-only smokes (--rounds 0, as in
    # CI) record separately so they never clobber the dispatch-gate file
    if args.rounds > 0:
        bench = "bench_engine"
    elif args.sparse_rounds > 0:
        bench = "bench_engine_sparse"
    elif args.payload_rounds > 0:
        bench = "bench_engine_payload"
    elif args.async_rounds > 0:
        bench = "bench_engine_async"
    else:
        bench = "bench_engine_sharded"
    if recs:
        save_results(bench, recs)
    print("\nname,rounds_per_s|op_us|sim_s")
    for r in recs:
        v = r.get("rounds_per_s",
                  r.get("op_us", r.get("sim_time_total_s")))
        if isinstance(v, (int, float)):
            print(f"{r['name']},{v:.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
