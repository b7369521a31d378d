"""Chaos harness for the elastic process backend: kill/rejoin cycles.

The crash-rejoin gate of the robustness PR: run a real K-process
localhost experiment while the supervisor SIGKILLs workers on a schedule
and relaunches each with ``--rejoin`` (epoch bumped).  Every cycle must
heal — checkpoint/donor catch-up, two-phase JOIN handshake, pristine
edge-weight restoration — and the whole run must end indistinguishable
in structure from a fault-free one:

* all rounds complete (no survivor stalls on a corpse or a rejoiner),
* every killed worker rejoins (``workers_rejoined == cycles``),
* counter conservation holds on every worker
  (``detected == still_dead + rejoined``),
* every rejoiner's final row-block matches a survivor's view of it
  **bitwise** (full sharing: the re-admitted peer fed the last barrier),
* final consensus error <= 2x the fault-free run's.

``round_min_s`` floors the round length so the relaunch (a fresh python
+ jax boot, seconds) lands mid-run instead of after the natural ~50ms
rounds have already finished.

    PYTHONPATH=src:. python benchmarks/bench_chaos.py            # 2 cycles
    PYTHONPATH=src:. python benchmarks/bench_chaos.py --smoke    # CI: 1
"""
from __future__ import annotations

import argparse
import time

from repro.core import DLConfig

from benchmarks.common import save_results
from repro.utils.compile_cache import enable_compile_cache

WL = {"dataset": "cifar10", "model": "mlp", "width": 1,
      "n_train": 256, "n_test": 128, "lr": 0.05}


def run(nodes: int = 16, workers: int = 4, rounds: int = 48, cycles: int = 2,
        round_min_s: float = 0.4, ckpt_every: int = 4, log: bool = True):
    from repro.runtime import ProcessRunner

    base = dict(n_nodes=nodes, topology="regular", degree=5, rounds=rounds,
                eval_every=max(rounds // 4, 1), backend="processes", seed=11)

    # fault-free reference (no round floor needed: the trajectory is
    # round-indexed, so wall-clock pacing does not change consensus)
    if log:
        print(f"[chaos] fault-free reference: N={nodes} K={workers} "
              f"rounds={rounds}", flush=True)
    ref = ProcessRunner(DLConfig(**base), WL, workers=workers,
                        watchdog_s=120.0)
    ref_hist = ref.run(log=False)
    ref_consensus = ref.consensus_error()

    # chaos run: kill+rejoin one worker per cycle, staggered so each
    # relaunch (a full python+jax boot) lands while rounds remain
    victims = [1 + (2 * c) % (workers - 1) for c in range(cycles)]
    plan = [{"worker": victims[c], "kill_at_round": 3 + 9 * c,
             "rejoin": True} for c in range(cycles)]
    if log:
        print(f"[chaos] plan: {plan} round_min_s={round_min_s}", flush=True)
    r = ProcessRunner(
        DLConfig(**base), WL, workers=workers, watchdog_s=120.0,
        chaos_plan=plan, ckpt_every=ckpt_every, round_min_s=round_min_s,
        dump_view=True, keep_run_dir=True,
    )
    t0 = time.time()
    hist = r.run(log=log)
    wall = time.time() - t0
    consensus = r.consensus_error()
    views = r.verify_rejoin_views()

    gates = {
        "all_rounds": bool(hist and hist[-1]["round"] == rounds - 1),
        "all_rejoined": r.workers_rejoined == cycles,
        "conservation": bool(r.conservation["ok"]),
        "bitwise_views": bool(views) and all(views.values()),
        "consensus_2x": consensus <= 2.0 * ref_consensus + 1e-9,
    }
    rec = {
        "name": f"chaos-N{nodes}-K{workers}-{cycles}cycles",
        "nodes": nodes, "workers": workers, "rounds": rounds,
        "cycles": cycles, "round_min_s": round_min_s,
        "chaos_plan": plan,
        "kill_events": r.kill_events,
        "workers_rejoined": r.workers_rejoined,
        "counters": r.counters,
        "conservation": r.conservation,
        "rejoin_views_bitwise": {str(k): bool(v) for k, v in views.items()},
        "catchup": {
            str(w): {"source": res.get("catchup_source"),
                     "start_round": res.get("start_round"),
                     "bytes": res["counters"].get("catchup_bytes", 0)}
            for w, res in r.worker_results.items() if res.get("rejoined")
        },
        "consensus_error": consensus,
        "consensus_error_fault_free": ref_consensus,
        "final_acc": hist[-1]["acc_mean"] if hist else None,
        "final_acc_fault_free": ref_hist[-1]["acc_mean"] if ref_hist else None,
        "wall_s": wall,
        "gates": gates,
        "pass": all(gates.values()),
    }
    if log:
        print(f"[chaos] rejoined {r.workers_rejoined}/{cycles}, consensus "
              f"{consensus:.4f} vs fault-free {ref_consensus:.4f}, "
              f"views {views}, gates {gates}", flush=True)
    for w, res in r.worker_results.items():
        d = r.conservation["per_worker"][str(w)]
        assert d["detected"] == d["still_dead"] + d["rejoined"], (w, d)
    assert rec["pass"], f"chaos gate failed: {gates}"
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=48)
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--round-min-s", type=float, default=0.4)
    ap.add_argument("--smoke", action="store_true",
                    help="CI: one kill+rejoin cycle, fewer rounds")
    args = ap.parse_args(argv)
    if args.smoke:
        rec = run(rounds=30, cycles=1, round_min_s=0.35)
    else:
        rec = run(args.nodes, args.workers, args.rounds, args.cycles,
                  args.round_min_s)
    save_results("bench_chaos", [rec])
    print(f"[chaos] PASS -> results/bench_chaos.json")


if __name__ == "__main__":
    enable_compile_cache()
    main()
