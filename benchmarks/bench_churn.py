"""Churn & heterogeneous-time realism (the paper's missing scenario axes):
accuracy / bytes / simulated wall-clock across per-round participation
levels, iid vs machine-correlated failures, straggler compute-time
distributions, and the sync-vs-local execution-semantics split — all
inside the RoundEngine's scanned chunks.

Sections (all recorded to results/bench_churn.json via
benchmarks/common.save_results):

1. *Participation sweep* — iid churn at p in {1.0, 0.9, 0.7, 0.5}: bytes
   drop roughly linearly while accuracy degrades slowly (gossip averaging
   is robust to moderate churn).  A down node does no local step, is cut
   out of the round's mixing operand, and freezes its params/opt/sharing
   state until it rejoins — rejoin-with-stale-model, never reweight-away.
2. *Correlated failures* — ``churn_machines=M`` drops whole machines
   (round-robin node->machine mapping) instead of iid nodes: the same
   expected participation with bursty, spatially-correlated outages.
3. *Stragglers x semantics* — a seeded 10%% of nodes at 10x the base
   compute time (``straggler_factor``/``straggler_frac``): the
   synchronous barrier pays the straggler every round, while
   ``semantics='local'`` (identical trajectories, per-node
   neighborhood-barrier clocks) shows the median node finishing far
   earlier.
4. *Timed gate* — rounds/s of the churned engine vs full participation,
   min/median/mean over interleaved repeats, **gate on the median** like
   bench_engine: the participation-mask machinery rides the compiled scan,
   so churn must cost < 2x throughput (median ratio >= 0.5).

    PYTHONPATH=src:. python benchmarks/bench_churn.py --rounds 40
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import jax.numpy as jnp

from repro.core import DLConfig, RoundEngine
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.optim import make_optimizer

from benchmarks.common import dl_experiment, save_results
from repro.utils.compile_cache import enable_compile_cache


def run(nodes: int = 32, rounds: int = 40, model: str = "mlp", seeds: int = 1,
        log: bool = True):
    """Accuracy/bytes/sim-time sections (1-3): everything through
    RoundEngine's scanned chunks via the shared dl_experiment harness."""
    recs = []
    base = dict(n_nodes=nodes, topology="regular", degree=5, rounds=rounds,
                eval_every=max(rounds // 4, 1), local_steps=2, batch_size=8,
                network="lan")
    # 1. iid participation sweep
    for p in (1.0, 0.9, 0.7, 0.5):
        dl = DLConfig(participation=p, **base)
        recs.append(
            dl_experiment(f"participation-{p:.1f}", dl, model=model, width=8,
                          seeds=seeds, log=log)
        )
    # 2. machine-correlated failures at matched expected participation
    dl = DLConfig(participation=0.7, churn_machines=8, **base)
    recs.append(
        dl_experiment("machine-churn-0.7x8", dl, model=model, width=8,
                      seeds=seeds, log=log)
    )
    # 3. straggler compute distribution, sync barrier vs local clocks
    #    (same trajectories — only the time semantics differ)
    for sem in ("sync", "local"):
        dl = DLConfig(compute_time_s=0.05, straggler_factor=10.0,
                      straggler_frac=0.1, semantics=sem, **base)
        rec = dl_experiment(f"straggler-10x-{sem}", dl, model=model, width=8,
                            seeds=seeds, log=log)
        rec.update({k: v for k, v in rec["history"][-1].items()
                    if k.startswith("vclock")})
        recs.append(rec)
    sync_t = next(r for r in recs if r["name"] == "straggler-10x-sync")["sim_time_s"]
    local = next(r for r in recs if r["name"] == "straggler-10x-local")
    if log:
        print(f"  straggler-10x sim time: sync {sync_t:.1f}s, local max "
              f"{local['sim_time_s']:.1f}s, local median node "
              f"{local.get('vclock_median_s', float('nan')):.1f}s", flush=True)
    return recs


# ---------------------------------------------------------------------------
# timed section: the scan must absorb churn masks ~for free
# ---------------------------------------------------------------------------

def _consensus_engine(n: int, rounds: int, participation: float,
                      chunk: int = 32) -> RoundEngine:
    ds = make_dataset("cifar10", n_train=1024, n_test=64, shape=(2, 2, 1),
                      sigma=2.0)
    parts = sharding_partition(ds.train_y, n, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=4, seed=0)

    def loss(p, x, y):
        t = x.reshape(x.shape[0], -1).mean(0)
        return jnp.mean((p["w"].reshape(-1, t.shape[0]) - t) ** 2)

    dl = DLConfig(n_nodes=n, topology="regular", degree=5, rounds=rounds,
                  eval_every=10**9, local_steps=1, batch_size=4,
                  chunk_rounds=chunk, participation=participation)
    return RoundEngine(dl, lambda k: {"w": jax.random.normal(k, (64,))}, loss,
                       lambda p, x, y: -loss(p, x, y),
                       make_optimizer("sgd", 0.05), batcher)


def run_timed(n: int = 128, rounds: int = 32, repeats: int = 3,
              log: bool = True):
    """Section 4: churned vs full-participation rounds/s (min/median/mean,
    interleaved repeats, gate on the median ratio >= 0.5)."""
    recs = []
    if rounds <= 0:
        return recs
    engines = {
        "full": _consensus_engine(n, rounds, participation=1.0),
        "churn0.5": _consensus_engine(n, rounds, participation=0.5),
    }
    for eng in engines.values():  # warm-up compiles every scan length
        eng.run(rounds=rounds, log=False)
    samples = {case: [] for case in engines}
    for _ in range(repeats):
        for case, eng in engines.items():
            t0 = time.time()
            eng.run(rounds=rounds, log=False)
            samples[case].append(rounds / (time.time() - t0))
    rps = {}
    for case, s in samples.items():
        rps[case] = statistics.median(s)
        recs.append({
            "name": f"N{n}-timed-{case}", "n_nodes": n, "rounds": rounds,
            "rounds_per_s": rps[case], "rounds_per_s_min": min(s),
            "rounds_per_s_mean": sum(s) / len(s),
        })
        if log:
            print(f"  N={n} {case:9s} {rps[case]:8.1f} rounds/s "
                  f"(min {min(s):.1f})", flush=True)
    ratio = rps["churn0.5"] / rps["full"]
    recs.append({
        "name": f"N{n}-churn-throughput-gate", "churn_speed_ratio": ratio,
        "gate_min_ratio": 0.5, "gate_pass": bool(ratio >= 0.5),
    })
    if log:
        print(f"  N={n} churned/full rounds/s (median): {ratio:.2f}x "
              f"(gate: >= 0.5x)", flush=True)
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--timed-nodes", type=int, default=128)
    ap.add_argument("--timed-rounds", type=int, default=32,
                    help="rounds for the churn-throughput gate; 0 skips it")
    ap.add_argument("--timed-repeats", type=int, default=3)
    args = ap.parse_args()
    recs = []
    if args.rounds > 0:
        recs += run(args.nodes, args.rounds, seeds=args.seeds)
    recs += run_timed(args.timed_nodes, args.timed_rounds, args.timed_repeats)
    save_results("bench_churn", recs)
    print("\nname,acc|rounds_per_s,bytes_per_node_MB,sim_time_s")
    for r in recs:
        if "acc_mean" in r:
            print(f"{r['name']},{r['acc_mean']:.4f},"
                  f"{r['bytes_per_node']/1e6:.1f},{r['sim_time_s']:.2f}")
        elif "rounds_per_s" in r:
            print(f"{r['name']},{r['rounds_per_s']:.1f},,")


if __name__ == "__main__":
    enable_compile_cache()
    main()
