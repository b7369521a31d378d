"""Paper Fig. 6 + §3.5: scalability — 256-node vs 1024-node 5-regular
(4x fewer samples per node at 1024), and degree 5 vs degree 9 at the
larger scale.

Paper claims validated: 5-regular@1024 ~ 5-regular@256 despite 4x less
data per node; degree 9 beats degree 5 (paper: +5.8 points)."""
from __future__ import annotations

import argparse

from repro.core import DLConfig

from benchmarks.common import dl_experiment, save_results
from repro.utils.compile_cache import enable_compile_cache


def run(base_nodes: int = 256, rounds: int = 60, model: str = "mlp", seeds: int = 1,
        log: bool = True, n_train: int = 16384):
    recs = []
    for name, nodes, degree in [
        (f"{base_nodes}n-5reg", base_nodes, 5),
        (f"{base_nodes * 4}n-5reg", base_nodes * 4, 5),
        (f"{base_nodes * 4}n-9reg", base_nodes * 4, 9),
    ]:
        dl = DLConfig(n_nodes=nodes, topology="regular", degree=degree, rounds=rounds,
                      eval_every=max(rounds // 6, 1), local_steps=2, batch_size=8)
        recs.append(
            dl_experiment(name, dl, model=model, width=8, n_train=n_train,
                          sigma=4.0, seeds=seeds, log=log)
        )
    save_results("bench_scalability", recs)
    return recs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--base-nodes", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--seeds", type=int, default=1)
    args = ap.parse_args()
    recs = run(args.base_nodes, args.rounds, seeds=args.seeds)
    print("\nname,acc,bytes_per_node_MB")
    for r in recs:
        print(f"{r['name']},{r['acc_mean']:.4f},{r['bytes_per_node']/1e6:.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
