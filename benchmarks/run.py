"""Benchmark entry point: one function per paper table/figure.

``python -m benchmarks.run``         — quick CI-scale pass of every bench
``python -m benchmarks.run --full``  — paper-scale settings (slow; the
                                       EXPERIMENTS.md numbers)

Prints ``name,us_per_call,derived`` CSV per bench plus the per-figure
summary lines.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.utils.compile_cache import enable_compile_cache


def _line(name, us, derived):
    print(f"{name},{us:.0f},{derived}", flush=True)


def bench_fig3_topologies(full: bool) -> None:
    from benchmarks.bench_topologies import run

    t0 = time.time()
    recs = run(nodes=32 if full else 12, rounds=150 if full else 12, log=full)
    us = (time.time() - t0) * 1e6 / max(recs[0]["history"][-1]["round"] + 1, 1)
    acc = {r["name"]: r["acc_mean"] for r in recs}
    byt = {r["name"]: r["bytes_per_node"] for r in recs}
    _line(
        "fig3_topologies", us,
        f"acc ring={acc['ring']:.3f} 5reg={acc['5-regular']:.3f} "
        f"fully={acc['fully']:.3f} dyn={acc['dynamic-5-regular']:.3f}; "
        f"bytes fully/dyn={byt['fully'] / max(byt['dynamic-5-regular'], 1):.1f}x",
    )


def bench_fig4_sparsification(full: bool) -> None:
    from benchmarks.bench_sparsification import run

    t0 = time.time()
    recs = run(nodes=32 if full else 12, rounds=150 if full else 12, log=full)
    us = (time.time() - t0) * 1e6 / len(recs)
    acc = {r["name"]: r["acc_mean"] for r in recs}
    _line(
        "fig4_sparsification", us,
        f"acc full={acc['full-sharing']:.3f} randk={acc['random-sampling']:.3f} "
        f"topk={acc['topk']:.3f} choco={acc['choco-sgd']:.3f}",
    )


def bench_fig5_secure_agg(full: bool) -> None:
    from benchmarks.bench_secure_agg import run

    t0 = time.time()
    recs = run(nodes=16 if full else 8, rounds=80 if full else 8, log=full)
    us = (time.time() - t0) * 1e6 / len(recs)
    acc = {r["name"]: r["acc_mean"] for r in recs}
    byt = {r["name"]: r["bytes_per_node"] for r in recs}
    _line(
        "fig5_secure_agg", us,
        f"cifar dpsgd={acc['cifar10/d-psgd']:.3f} sec={acc['cifar10/secure-agg']:.3f}; "
        f"overhead={byt['cifar10/secure-agg'] / byt['cifar10/d-psgd'] - 1:.1%}",
    )


def bench_fig6_scalability(full: bool) -> None:
    from benchmarks.bench_scalability import run

    t0 = time.time()
    recs = run(base_nodes=256 if full else 32, rounds=60 if full else 8,
               n_train=16384 if full else 4096, log=full)
    us = (time.time() - t0) * 1e6 / len(recs)
    accs = [f"{r['name']}={r['acc_mean']:.3f}" for r in recs]
    _line("fig6_scalability", us, " ".join(accs))


def bench_kernels(full: bool) -> None:
    from benchmarks.bench_kernels import run

    for name, us, derived in run():
        _line(f"kernel_{name}", us, derived)


def bench_roofline(full: bool) -> None:
    import glob

    from benchmarks.bench_roofline import load

    rows = load(["results/dryrun_sp", "results/dryrun_mp"])
    ok = [r for r in rows if r.get("status") == "ok"]
    skipped = [r for r in rows if r.get("status") == "skipped"]
    if not rows:
        _line("roofline", 0, "no dry-run results yet (run repro.launch.dryrun --all)")
        return
    doms = {}
    for r in ok:
        doms[r["roofline"]["bottleneck"]] = doms.get(r["roofline"]["bottleneck"], 0) + 1
    _line(
        "roofline", sum(r.get("compile_s", 0) for r in ok) * 1e6 / max(len(ok), 1),
        f"{len(ok)} compiled, {len(skipped)} arch-skips; bottlenecks {doms}",
    )


ALL = [
    bench_fig3_topologies,
    bench_fig4_sparsification,
    bench_fig5_secure_agg,
    bench_fig6_scalability,
    bench_kernels,
    bench_roofline,
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale settings")
    ap.add_argument("--only", default=None, help="substring filter on bench name")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for fn in ALL:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            fn(args.full)
        except Exception as e:  # keep the suite running; report the failure
            _line(fn.__name__, 0, f"ERROR: {type(e).__name__}: {e}")
            raise


if __name__ == "__main__":
    enable_compile_cache()
    main()
