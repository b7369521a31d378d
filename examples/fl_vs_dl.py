"""FL emulation vs DL (paper Fig. 1: 'to emulate FL, a node can be
modified to coordinate the training, shown as the FL server').

Same dataset, same non-IID partition, same optimizer — one run with the
FederatedRunner (central server, client subset per round) and one with the
DecentralizedRunner (5-regular gossip, no server).

    PYTHONPATH=src python examples/fl_vs_dl.py --rounds 40
"""
import argparse

from repro.core import DLConfig, DecentralizedRunner, FLConfig, FederatedRunner
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

    ds = make_dataset("cifar10", n_train=1024, n_test=512, sigma=4.0)
    parts = sharding_partition(ds.train_y, args.nodes, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, 8, seed=0)
    loss_fn = lambda p, x, y: cross_entropy(mlp_apply(p, x), y)
    acc_fn = lambda p, x, y: (mlp_apply(p, x).argmax(-1) == y).mean()
    init = lambda k: mlp_init(k, hidden=64)

    fl = FLConfig(n_clients=args.nodes, clients_per_round=args.nodes // 2,
                  local_steps=4, rounds=args.rounds, eval_every=args.rounds // 4)
    r_fl = FederatedRunner(fl, init, loss_fn, acc_fn, make_optimizer("sgd", 0.05), batcher)
    h_fl = r_fl.run(log=False)

    dl = DLConfig(n_nodes=args.nodes, topology="regular", degree=5,
                  local_steps=4, rounds=args.rounds, eval_every=args.rounds // 4)
    r_dl = DecentralizedRunner(dl, init, loss_fn, acc_fn, make_optimizer("sgd", 0.05), batcher)
    h_dl = r_dl.run(log=False)

    print(f"{'round':>6s} {'FedAvg':>8s} {'D-PSGD':>8s}")
    fl_by_round = {h['round']: h['acc'] for h in h_fl}
    dl_by_round = {h['round']: h['acc_mean'] for h in h_dl}
    for r in sorted(set(fl_by_round) | set(dl_by_round)):
        print(f"{r:6d} {fl_by_round.get(r, float('nan')):8.4f} "
              f"{dl_by_round.get(r, float('nan')):8.4f}")
    print(f"\nD-PSGD bytes/node: {r_dl.bytes_sent/1e6:.1f} MB "
          f"(FL server would carry {args.nodes//2}x that inbound per round)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
