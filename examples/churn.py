"""Churn / straggler / heterogeneous-time realism — the activation-mask
scenario axes, end-to-end on the RoundEngine.

Three sweeps, all inside the engine's compiled scan:

* participation: each round every node is up with probability
  ``participation`` — iid per node, or *machine-correlated* with
  ``--machines M`` (whole machines fail together, round-robin mapping).
  Down nodes skip their local step, are cut out of the mixing operand
  (freed weight back to the surviving diagonals), and freeze their
  params/optimizer/sharing state until they rejoin with that stale model.
* stragglers: ``--straggler-frac``/``--straggler-factor`` mark a seeded
  fraction of nodes with heavier per-node compute times
  (``network.straggler_compute_times``).
* execution semantics: ``--semantics sync|local|async`` selects the
  scheduler layer — the synchronous round barrier, per-node
  neighborhood-barrier clocks (same trajectories, honest per-node time),
  or event-driven AD-PSGD-style gossip on a virtual clock (staleness +
  per-node wall-clock reported).

    PYTHONPATH=src python examples/churn.py --rounds 40
    PYTHONPATH=src python examples/churn.py --rounds 40 --machines 4
    PYTHONPATH=src python examples/churn.py --rounds 60 --semantics async \\
        --straggler-factor 10 --straggler-frac 0.1
"""
import argparse

from repro.core import DLConfig, RoundEngine
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.models.api import cross_entropy
from repro.models.mlp import mlp_apply, mlp_init
from repro.optim import make_optimizer
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--semantics", choices=("sync", "local", "async"),
                    default="sync")
    ap.add_argument("--machines", type=int, default=0,
                    help="churn_machines: >0 drops whole machines together")
    ap.add_argument("--compute-time", type=float, default=0.05,
                    help="base per-node compute seconds in the time model")
    ap.add_argument("--straggler-factor", type=float, default=1.0)
    ap.add_argument("--straggler-frac", type=float, default=0.0)
    args = ap.parse_args()

    ds = make_dataset("cifar10", n_train=8192, n_test=512)
    parts = sharding_partition(ds.train_y, args.nodes, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, 8, seed=0)

    loss_fn = lambda p, x, y: cross_entropy(mlp_apply(p, x), y)
    acc_fn = lambda p, x, y: (mlp_apply(p, x).argmax(-1) == y).mean()

    extra = ""
    if args.semantics != "sync":
        extra = f" {'median node clock':>18s}"
    if args.semantics == "async":
        extra += f" {'staleness':>10s}"
    print(f"{'participation':>14s} {'acc':>8s} {'MB/node':>9s} "
          f"{'sim LAN s':>10s}" + extra)
    for p in (1.0, 0.9, 0.7, 0.5):
        dl = DLConfig(n_nodes=args.nodes, topology="regular", degree=5,
                      rounds=args.rounds, eval_every=args.rounds - 1,
                      local_steps=2 if args.semantics != "async" else 1,
                      participation=p, churn_machines=args.machines,
                      network="lan", semantics=args.semantics,
                      compute_time_s=args.compute_time,
                      straggler_factor=args.straggler_factor,
                      straggler_frac=args.straggler_frac)
        e = RoundEngine(dl, lambda k: mlp_init(k, hidden=128), loss_fn,
                        acc_fn, make_optimizer("sgd", 0.05), batcher)
        hist = e.run(log=False)
        line = (f"{p:14.1f} {hist[-1]['acc_mean']:8.4f} "
                f"{e.bytes_sent / 1e6:9.1f} {e.sim_time_s:10.2f}")
        if args.semantics != "sync":
            line += f" {hist[-1].get('vclock_median_s', float('nan')):18.2f}"
        if args.semantics == "async":
            line += f" {hist[-1].get('staleness_mean', float('nan')):10.2f}"
        print(line)


if __name__ == "__main__":
    enable_compile_cache()
    main()
