"""Topologies + dynamicity (paper §3.2): swap the Graph module between
ring / 5-regular / fully-connected / per-round dynamic 5-regular and
compare accuracy vs communication — the framework makes the swap a
one-line config change (the paper's point).

    PYTHONPATH=src python examples/topologies_dynamic.py --rounds 40
"""
import argparse

from repro.core import DLConfig, DecentralizedRunner
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.models.api import cross_entropy
from repro.models.mlp import mlp_apply, mlp_init
from repro.optim import make_optimizer
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--nodes", type=int, default=16)
    args = ap.parse_args()

    ds = make_dataset("cifar10", n_train=8192, n_test=512)
    parts = sharding_partition(ds.train_y, args.nodes, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, 8, seed=0)

    loss_fn = lambda p, x, y: cross_entropy(mlp_apply(p, x), y)
    acc_fn = lambda p, x, y: (mlp_apply(p, x).argmax(-1) == y).mean()

    print(f"{'topology':20s} {'acc':>8s} {'MB/node':>9s}")
    for topo, degree in [("ring", 2), ("regular", 5), ("fully", 0), ("dynamic", 5)]:
        dl = DLConfig(n_nodes=args.nodes, topology=topo, degree=degree,
                      rounds=args.rounds, eval_every=args.rounds - 1, local_steps=2)
        r = DecentralizedRunner(dl, lambda k: mlp_init(k, hidden=128), loss_fn,
                                acc_fn, make_optimizer("sgd", 0.05), batcher)
        hist = r.run(log=False)
        print(f"{topo:20s} {hist[-1]['acc_mean']:8.4f} {r.bytes_sent / 1e6:9.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
