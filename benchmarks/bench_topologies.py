"""Paper Fig. 3: DL across ring / 5-regular / fully-connected / dynamic
5-regular topologies — accuracy per round, wall-clock, cumulative bytes.

Paper claims validated: (a) fully > regular > ring for equal rounds;
(b) dynamic 5-regular ~ fully at a fraction of the bytes (paper: 51x)."""
from __future__ import annotations

import argparse

from repro.core import DLConfig

from benchmarks.common import dl_experiment, save_results
from repro.utils.compile_cache import enable_compile_cache


def run(nodes: int = 32, rounds: int = 120, model: str = "mlp", seeds: int = 1,
        log: bool = True):
    recs = []
    for name, topo, deg in [
        ("ring", "ring", 2),
        ("5-regular", "regular", 5),
        ("fully", "fully", 0),
        ("dynamic-5-regular", "dynamic", 5),
    ]:
        dl = DLConfig(n_nodes=nodes, topology=topo, degree=deg, rounds=rounds,
                      eval_every=max(rounds // 12, 1), local_steps=4, batch_size=8)
        recs.append(dl_experiment(name, dl, model=model, seeds=seeds, log=log))
    save_results("bench_topologies", recs)
    return recs


def simulated_times(recs, nodes: int, rounds: int, model_bytes: float,
                    compute_time_s: float = 0.05):
    """Fig. 3b axis: per-config simulated wall-clock on the paper's
    16-machine LAN testbed (core/network.py)."""
    from repro.core.network import paper_testbed
    from repro.core.topology import Graph

    net = paper_testbed(nodes)
    graphs = {
        "ring": Graph.ring(nodes),
        "5-regular": Graph.regular_circulant(nodes, 5),
        "fully": Graph.fully_connected(nodes),
        "dynamic-5-regular": Graph.regular_circulant(nodes, 5),
    }
    return {
        name: net.experiment_time(g, model_bytes, compute_time_s, rounds)
        for name, g in graphs.items()
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=120)
    ap.add_argument("--model", default="mlp", choices=["mlp", "cnn"])
    ap.add_argument("--seeds", type=int, default=1)
    args = ap.parse_args()
    recs = run(args.nodes, args.rounds, args.model, args.seeds)
    base = next(r for r in recs if r["name"] == "fully")
    model_bytes = base["bytes_per_node"] / args.rounds / max(args.nodes - 1, 1)
    sim = simulated_times(recs, args.nodes, args.rounds, model_bytes)
    print("\nname,acc,bytes_per_node_MB,wall_s,sim_lan_s,bytes_vs_fully")
    for r in recs:
        print(f"{r['name']},{r['acc_mean']:.4f},{r['bytes_per_node']/1e6:.1f},"
              f"{r['wall_s']:.0f},{sim[r['name']]:.1f},"
              f"{base['bytes_per_node']/max(r['bytes_per_node'],1):.1f}x-less")


if __name__ == "__main__":
    enable_compile_cache()
    main()
