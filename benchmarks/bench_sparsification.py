"""Paper Fig. 4: sparsification (random sampling, CHOCO-SGD, TopK) vs full
sharing at a 10% communication budget, 5-regular topology, non-IID.

Paper claim validated: under non-IID at scale, sparsification converges
worse than full sharing for the same number of rounds."""
from __future__ import annotations

import argparse

from repro.core import DLConfig

from benchmarks.common import dl_experiment, save_results
from repro.utils.compile_cache import enable_compile_cache


def run(nodes: int = 32, rounds: int = 120, budget: float = 0.1, model: str = "mlp",
        seeds: int = 1, log: bool = True):
    recs = []
    for name, sharing in [
        ("full-sharing", "full"),
        ("random-sampling", "randomk"),
        ("topk", "topk"),
        ("choco-sgd", "choco"),
    ]:
        dl = DLConfig(n_nodes=nodes, topology="regular", degree=5, rounds=rounds,
                      eval_every=max(rounds // 12, 1), local_steps=4, batch_size=8,
                      sharing=sharing, budget=budget)
        recs.append(dl_experiment(name, dl, model=model, seeds=seeds, log=log))
    save_results("bench_sparsification", recs)
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=120)
    ap.add_argument("--budget", type=float, default=0.1)
    ap.add_argument("--model", default="mlp", choices=["mlp", "cnn"])
    ap.add_argument("--seeds", type=int, default=1)
    args = ap.parse_args()
    recs = run(args.nodes, args.rounds, args.budget, args.model, args.seeds)
    print("\nname,acc,bytes_per_node_MB")
    for r in recs:
        print(f"{r['name']},{r['acc_mean']:.4f},{r['bytes_per_node']/1e6:.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
