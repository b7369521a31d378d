"""Fault-tolerant gossip gate: convergence under message-level fault
injection (core.faults.FaultPlan) must cost bounded *simulated* time.

Protocol (the tentpole acceptance gate):

1. A fault-free consensus run (default N=1024, 6-regular, LAN link model)
   defines the target: the accuracy level at 90%% of the clean run's total
   improvement, and ``T0`` = the simulated time of the first eval at or
   above it.
2. The faulty run — identical config plus ``FaultPlan(msg_loss=0.1)`` —
   gets up to 2x the rounds; ``T1`` is the simulated time of its first
   eval at or above the same target.  Lost messages renormalize the mixing
   operand (rows stay stochastic), so gossip under 10%% loss converges
   slower, not wrong.
3. **Gate**: median ``T1 / T0`` over ``--repeats`` seeds <= 1.5 — i.e.
   10%% message loss costs at most 50%% extra simulated wall-clock to the
   same accuracy.  Per-seed ratios, fault counters (with the
   ``injected == detected + survived`` conservation check), and the gate
   verdict are recorded to results/bench_faults.json.

    PYTHONPATH=src:. python benchmarks/bench_faults.py
    PYTHONPATH=src:. python benchmarks/bench_faults.py --smoke   # CI-sized
"""
from __future__ import annotations

import argparse
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DLConfig, FaultPlan, RoundEngine
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.optim import make_optimizer

from benchmarks.common import save_results
from repro.utils.compile_cache import enable_compile_cache

MSG_LOSS = 0.10
GATE_MAX_SLOWDOWN = 1.5
TARGET_FRAC = 0.9  # target = 90% of the clean run's total improvement


def _consensus_engine(n: int, rounds: int, degree: int, seed: int,
                      faults: FaultPlan | None = None,
                      eval_every: int = 4) -> RoundEngine:
    ds = make_dataset("cifar10", n_train=2048, n_test=64, shape=(2, 2, 1),
                      sigma=2.0)
    parts = sharding_partition(ds.train_y, n, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=4, seed=0)

    def loss(p, x, y):
        t = x.reshape(x.shape[0], -1).mean(0)
        return jnp.mean((p["w"].reshape(-1, t.shape[0]) - t) ** 2)

    dl = DLConfig(n_nodes=n, topology="regular", degree=degree, rounds=rounds,
                  eval_every=eval_every, local_steps=1, batch_size=4,
                  chunk_rounds=min(8, eval_every), network="lan",
                  compute_time_s=0.01, seed=seed, faults=faults)
    return RoundEngine(dl, lambda k: {"w": jax.random.normal(k, (64,))}, loss,
                       lambda p, x, y: -loss(p, x, y),
                       make_optimizer("sgd", 0.05), batcher)


def _time_to_target(history, target):
    """Simulated time of the first eval with acc_mean >= target (None if
    the run never gets there)."""
    for rec in history:
        if rec["acc_mean"] >= target:
            return rec["sim_time_s"]
    return None


def _fault_record(eng):
    t = {k: float(v) for k, v in eng.scheduler._fault_totals.items()}
    conserved = abs(
        t["faults_injected"] - t["faults_detected"] - t["faults_survived"]
    ) < 1e-6
    assert conserved, f"fault counter conservation violated: {t}"
    t["conservation_ok"] = conserved
    return t


def run_gate(n: int, rounds: int, degree: int, repeats: int, log: bool = True):
    recs = []
    ratios = []
    for rep in range(repeats):
        seed = 3 + rep
        clean = _consensus_engine(n, rounds, degree, seed)
        clean.run(log=False)
        accs = [r["acc_mean"] for r in clean.history]
        target = accs[0] + TARGET_FRAC * (accs[-1] - accs[0])
        t0 = _time_to_target(clean.history, target)
        plan = FaultPlan(msg_loss=MSG_LOSS, seed=seed)
        faulty = _consensus_engine(n, 2 * rounds, degree, seed, faults=plan)
        faulty.run(log=False)
        t1 = _time_to_target(faulty.history, target)
        converged = t0 is not None and t1 is not None
        ratio = (t1 / t0) if converged else float("inf")
        ratios.append(ratio)
        fr = _fault_record(faulty)
        recs.append({
            "name": f"N{n}-loss{MSG_LOSS:.2f}-seed{seed}",
            "n_nodes": n, "degree": degree, "rounds": rounds,
            "msg_loss": MSG_LOSS, "target_acc": target,
            "clean_time_to_target_s": t0, "faulty_time_to_target_s": t1,
            "slowdown": ratio, **fr,
        })
        if log:
            print(f"  N={n} seed{seed}: clean {t0 if t0 is None else round(t0, 3)}s "
                  f"-> faulty {t1 if t1 is None else round(t1, 3)}s "
                  f"({ratio:.2f}x), injected {fr['faults_injected']:.0f}",
                  flush=True)
    med = statistics.median(ratios)
    gate_pass = bool(np.isfinite(med) and med <= GATE_MAX_SLOWDOWN)
    recs.append({
        "name": f"N{n}-fault-convergence-gate",
        "median_slowdown": med if np.isfinite(med) else None,
        "gate_max_slowdown": GATE_MAX_SLOWDOWN,
        "gate_pass": gate_pass,
    })
    if log:
        print(f"  N={n} median slowdown under {MSG_LOSS:.0%} loss: "
              f"{med:.2f}x (gate: <= {GATE_MAX_SLOWDOWN}x) "
              f"{'PASS' if gate_pass else 'FAIL'}", flush=True)
    return recs, gate_pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1024)
    ap.add_argument("--degree", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: N=64, one repeat, same gate")
    args = ap.parse_args()
    if args.smoke:
        args.nodes, args.rounds, args.repeats = 64, 24, 1
    recs, ok = run_gate(args.nodes, args.rounds, args.degree, args.repeats)
    path = save_results("bench_faults", recs)
    print(f"\nresults -> {path}")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
