"""Paper Fig. 5: secure aggregation vs plain D-PSGD on two datasets
(CIFAR-10-like and CelebA-like), 5-regular graph, 48 nodes in the paper
(CLI-tunable here).

Paper claims validated: comparable accuracy (small precision loss) at
~3% extra communication."""
from __future__ import annotations

import argparse

from repro.core import DLConfig

from benchmarks.common import dl_experiment, save_results
from repro.utils.compile_cache import enable_compile_cache


def run(nodes: int = 16, rounds: int = 80, model: str = "mlp", seeds: int = 1,
        log: bool = True):
    recs = []
    for dataset in ("cifar10", "celeba"):
        for name, secure in (("d-psgd", False), ("secure-agg", True)):
            dl = DLConfig(n_nodes=nodes, topology="regular", degree=4, rounds=rounds,
                          eval_every=max(rounds // 6, 1), local_steps=4, batch_size=8,
                          secure=secure)
            recs.append(
                dl_experiment(f"{dataset}/{name}", dl, dataset=dataset, model=model,
                              seeds=seeds, log=log)
            )
    save_results("bench_secure_agg", recs)
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=80)
    ap.add_argument("--model", default="mlp", choices=["mlp", "cnn"])
    ap.add_argument("--seeds", type=int, default=1)
    args = ap.parse_args()
    recs = run(args.nodes, args.rounds, args.model, args.seeds)
    print("\nname,acc,bytes_per_node_MB")
    for r in recs:
        print(f"{r['name']},{r['acc_mean']:.4f},{r['bytes_per_node']/1e6:.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
