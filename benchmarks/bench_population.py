"""Population-scale async engine benchmark — the cohort gather/scatter
gate (ISSUE 6 acceptance):

1. An N=100k asynchronous run completes at ``cohort_capacity``-bounded
   memory (hot working set O(C·(d+1)·P), independent of N — asserted
   against the scheduler's analytic ``memory_model`` at two population
   sizes and recorded empirically via live device-buffer bytes).
2. Per-active-node event throughput of the cohort path at N=100k is
   within 2x of the dense-oracle cohort rate at N=1024 (recorded median
   over interleaved repeats).

The workload is a small per-node MLP (the paper's model family at toy
scale) trained by per-event local SGD — a fired event pays realistic
gradient FLOPs, so the gate compares end-to-end per-event cost, not just
bookkeeping.  Both runs use homogeneous event times and
``async_slice_s=0`` so every step fires a full cohort: the dense N=1024
baseline fires 1024 events per step over an O(N·(d+1)·P) working set;
the cohort N=100k run fires C events per step over O(C·(d+1)·P) plus
O(N) selection/scatter.

The million-node stage (ISSUE 10 acceptance) adds:

3. N=1,000,000 cohort throughput >= 0.5x the N=100k rate at the same C
   (median over interleaved repeats) — per-step cost stays sublinear in
   N because selection runs through the carried segment-min hierarchy
   and the cold (N, P) population is int8-quantized.
4. Cold-state bytes at N=1M with ``cold_dtype='int8'`` <= 0.3x the fp32
   cold bytes of ``memory_model()``, and the live device-buffer snapshot
   confirms the analytic model within 1.5x.
5. The hierarchical selection is checked bitwise against the flat top_k
   oracle on a small-N run, and the vectorized random-regular builder is
   timed at N=1M (its wall-clock lands in the results record).

Records land in ``results/bench_population.json`` (uploaded by CI); the
shared ``save_results`` appends live-device-bytes + host-RSS capture.
"""
from __future__ import annotations

import argparse
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import memory_snapshot, save_results
from repro.core import DLConfig, RoundEngine
from repro.core.topology import random_regular_neighbors
from repro.data import NodeBatcher
from repro.optim import make_optimizer
from repro.utils.compile_cache import enable_compile_cache

SHAPE = (4, 4, 1)
N_CLASSES = 2


def _make_init(hidden: int):
    feat = int(np.prod(SHAPE))

    def init(k):
        k1, k2 = jax.random.split(k)
        return {
            "w1": jax.random.normal(k1, (feat, hidden)) / np.sqrt(feat),
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, N_CLASSES)) / np.sqrt(hidden),
            "b2": jnp.zeros((N_CLASSES,)),
        }

    return init


def _apply(p, x):
    h = jnp.tanh(x.reshape(x.shape[0], -1) @ p["w1"] + p["b1"])
    return h @ p["w2"] + p["b2"]


def _loss(p, x, y):
    logp = jax.nn.log_softmax(_apply(p, x))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _acc(p, x, y):
    return (_apply(p, x).argmax(-1) == y).mean()


def _engine(n_nodes: int, cohort: int, *, hidden: int, chunk: int,
            batch: int = 4, degree: int = 4, seed: int = 0,
            selection: str = "auto", cold: str = "fp32",
            spread: float = 0.0, slice_s: float = 0.0) -> RoundEngine:
    """Async MLP-per-node engine: each fired event runs one local SGD
    step of a (feat -> hidden -> classes) MLP and a neighborhood gossip,
    with ms-scale event times and no network model.  ``spread`` turns on
    continuous per-node compute heterogeneity (U(1, 1+spread) x base) and
    ``slice_s`` the cohort window — together they put selection in the
    spread-clock regime where the segment hierarchy prunes."""
    rng = np.random.default_rng(seed)
    n_train = max(n_nodes, 256)
    x = rng.normal(size=(n_train, *SHAPE)).astype(np.float32)
    y = rng.integers(0, N_CLASSES, size=(n_train,)).astype(np.int32)
    parts = np.array_split(np.arange(n_train), n_nodes)
    dl = DLConfig(
        n_nodes=n_nodes, topology="regular", degree=degree, sharing="full",
        semantics="async", async_gossip="neighborhood",
        async_slice_s=slice_s, chunk_rounds=chunk, eval_every=10_000,
        batch_size=batch, compute_time_s=1e-3, cohort_capacity=cohort,
        seed=seed, batch_keying="node", selection=selection,
        cold_dtype=cold, compute_spread=spread,
    )
    batcher = NodeBatcher(x, y, parts, dl.batch_size, seed=seed)
    return RoundEngine(dl, _make_init(hidden), _loss, _acc,
                       make_optimizer("sgd", 0.05), batcher)


def _events_per_sec(eng: RoundEngine, steps: int) -> float:
    """Fired events per wall second over ``steps`` scanned event steps
    (post-warmup; the caller interleaves repeats)."""
    sched = eng.scheduler
    start = getattr(eng, "_bench_round", 0)
    before = sched._fired_total
    t0 = time.perf_counter()
    done = 0
    while done < steps:
        r = min(eng.chunk, steps - done)
        sched.run_span(start + done, r)
        done += r
    jax.block_until_ready(eng.params)
    dt = time.perf_counter() - t0
    eng._bench_round = start + done
    return (sched._fired_total - before) / max(dt, 1e-9)


def run_population(dense_nodes: int, pop_nodes: int, cohort: int,
                   hidden: int, steps: int, repeats: int, chunk: int,
                   batch: int):
    recs = []
    print(f"[population] dense N={dense_nodes} oracle vs "
          f"cohort N={pop_nodes} C={cohort} (hidden={hidden}, B={batch}, "
          f"{steps} steps, {repeats} repeats)", flush=True)
    t0 = time.time()
    dense = _engine(dense_nodes, 0, hidden=hidden, chunk=chunk, batch=batch)
    coh = _engine(pop_nodes, cohort, hidden=hidden, chunk=chunk, batch=batch)
    print(f"  engines built in {time.time() - t0:.1f}s", flush=True)
    # warmup: compile both full-length chunk programs (a shorter span
    # would compile a different scan length and leak the timed repeats'
    # first-call compile into the measurement)
    dense.scheduler.run_span(0, chunk)
    coh.scheduler.run_span(0, chunk)
    dense._bench_round = coh._bench_round = chunk
    dense_rates, cohort_rates = [], []
    for r in range(repeats):  # interleaved timed repeats
        dense_rates.append(_events_per_sec(dense, steps))
        cohort_rates.append(_events_per_sec(coh, steps))
        print(f"  repeat {r}: dense {dense_rates[-1]:,.0f} ev/s, "
              f"cohort {cohort_rates[-1]:,.0f} ev/s", flush=True)
    d_med = float(np.median(dense_rates))
    c_med = float(np.median(cohort_rates))
    ratio = d_med / max(c_med, 1e-9)
    mm = coh.scheduler.memory_model()
    m_coh = coh.scheduler.extra_metrics()
    rec = {
        "name": f"population_n{pop_nodes}_c{cohort}",
        "dense_nodes": dense_nodes,
        "pop_nodes": pop_nodes,
        "cohort_capacity": cohort,
        "hidden": hidden,
        "n_params": int(coh.n_params),
        "steps": steps,
        "dense_events_per_s": dense_rates,
        "cohort_events_per_s": cohort_rates,
        "dense_events_per_s_median": d_med,
        "cohort_events_per_s_median": c_med,
        "dense_over_cohort_ratio": ratio,
        "events_total": m_coh["events_total"],
        "cohort_occupancy_mean": m_coh["cohort_occupancy_mean"],
        "cohort_overflow_total": m_coh["cohort_overflow_total"],
        "cohort_overflow_ratio": m_coh["cohort_overflow_ratio"],
        "cohort_selection": m_coh["cohort_selection"],
        "memory_model": mm,
        "memory_after": memory_snapshot(),
    }
    recs.append(rec)
    print(f"  median dense {d_med:,.0f} ev/s vs cohort {c_med:,.0f} ev/s "
          f"-> dense/cohort ratio {ratio:.2f} (gate <= 2.0)", flush=True)
    print(f"  hot set {mm['hot']['total']/1e6:.2f} MB vs cold population "
          f"{mm['cold']['total']/1e6:.1f} MB", flush=True)
    gate_ok = ratio <= 2.0
    rec["throughput_gate_ok"] = bool(gate_ok)
    return recs, gate_ok


def check_memory_independence(cohort: int, hidden: int, n_small: int,
                              n_large: int, chunk: int):
    """Hot-set bytes at fixed C must not depend on N — asserted on the
    analytic model of two engine instances and recorded."""
    small = _engine(n_small, cohort, hidden=hidden, chunk=chunk)
    large = _engine(n_large, cohort, hidden=hidden, chunk=chunk)
    hs, hl = (small.scheduler.memory_model()["hot"],
              large.scheduler.memory_model()["hot"])
    assert hs == hl, (
        f"hot-set bytes depend on N at fixed C={cohort}: {hs} vs {hl}"
    )
    print(f"  hot set at C={cohort}: {hl['total']/1e6:.2f} MB for both "
          f"N={n_small} and N={n_large} (N-independent)", flush=True)
    return {
        "name": f"memory_independence_c{cohort}",
        "n_small": n_small,
        "n_large": n_large,
        "hot_bytes": hl["total"],
        "cold_bytes_small": small.scheduler.memory_model()["cold"]["total"],
        "cold_bytes_large": large.scheduler.memory_model()["cold"]["total"],
    }


# continuous heterogeneity used by the selection-oracle check and the
# million-node stage: per-node compute ~ 1e-3 * U(1, 1 + SPREAD) seconds
SPREAD = 15.0


def _slice_for(n: int, cohort: int, *, fill: float = 0.8) -> float:
    """Cohort window sized so the steady-state occupancy is ~fill*C:
    with per-node rate 1/ct and ct ~ base*U(1, 1+SPREAD), the population
    event rate is N * ln(1+SPREAD) / (base*SPREAD) events/s."""
    rate = n * np.log1p(SPREAD) / (1e-3 * SPREAD)
    return fill * cohort / rate


def check_selection_oracle(chunk: int, hidden: int, *, n: int = 4096,
                           cohort: int = 256, steps: int = 24,
                           batch: int = 4):
    """Hierarchical segment-min selection must pick bitwise the same
    cohorts as the flat top_k oracle: run both paths under a continuous
    heterogeneous clock and compare the full trajectory (params + event
    counters) exactly.  Also asserts the hierarchy actually engaged —
    fallbacks on every step would make the check vacuous."""
    sl = _slice_for(n, cohort)
    flat = _engine(n, cohort, hidden=hidden, chunk=chunk, batch=batch,
                   selection="flat", spread=SPREAD, slice_s=sl)
    hier = _engine(n, cohort, hidden=hidden, chunk=chunk, batch=batch,
                   selection="hier", spread=SPREAD, slice_s=sl)
    for e in (flat, hier):
        done = 0
        while done < steps:
            r = min(e.chunk, steps - done)
            e.scheduler.run_span(done, r)
            done += r
    for a, b in zip(jax.tree_util.tree_leaves(flat.params),
                    jax.tree_util.tree_leaves(hier.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(flat.scheduler._events),
                                  np.asarray(hier.scheduler._events))
    fb = hier.scheduler.extra_metrics()["selection_fallback_total"]
    assert fb < steps, (
        f"hier selection fell back to flat on all {steps} steps — the "
        "oracle check never exercised the segment hierarchy")
    print(f"  selection oracle OK: hier == flat bitwise over {steps} steps "
          f"at N={n} C={cohort} (fallbacks: {fb}/{steps})", flush=True)
    return {
        "name": f"selection_oracle_n{n}_c{cohort}",
        "steps": steps,
        "bitwise_equal": True,
        "selection_fallback_total": fb,
    }


def run_million(base_nodes: int, million_nodes: int, cohort: int,
                hidden: int, steps: int, repeats: int, chunk: int,
                batch: int, cold: str, smoke: bool):
    """The N=1M stage: hierarchical selection + compressed cold rows.

    Full mode interleaves the million-node cohort engine against the
    N=``base_nodes`` cohort engine at the same C and gates the median
    per-event rate at >= 0.5x (which also pins the 10x-N per-step cost
    growth at <= 2x — far below linear).  Smoke mode runs the million
    engine alone (small C, few steps) and checks the memory claims only.
    """
    recs = []
    print(f"[population] million-node stage: N={million_nodes} C={cohort} "
          f"cold_dtype={cold} (vs N={base_nodes} baseline"
          f"{', smoke' if smoke else ''})", flush=True)
    # vectorized random-regular builder at N=1M (the ROADMAP follow-up
    # this stage retires): build once, record wall-clock
    t0 = time.perf_counter()
    nbr = random_regular_neighbors(million_nodes, 6, seed=0)
    rr_s = time.perf_counter() - t0
    assert nbr.shape == (million_nodes, 6) and nbr.dtype == np.int32
    print(f"  random_regular_neighbors(N={million_nodes}, d=6): "
          f"{rr_s:.1f}s", flush=True)
    del nbr
    gc.collect()
    base = None
    if not smoke:
        base = _engine(base_nodes, cohort, hidden=hidden, chunk=chunk,
                       batch=batch, spread=SPREAD,
                       slice_s=_slice_for(base_nodes, cohort))
    t0 = time.time()
    big = _engine(million_nodes, cohort, hidden=hidden, chunk=chunk,
                  batch=batch, selection="hier", cold=cold, spread=SPREAD,
                  slice_s=_slice_for(million_nodes, cohort))
    build_s = time.time() - t0
    print(f"  N={million_nodes} engine built in {build_s:.1f}s "
          f"(selection=hier, cold_dtype={cold})", flush=True)
    def _warm(e, n_nodes):
        # warm to the event clock's steady state: occupancy ramps from the
        # initial-transient fill to ~0.8*C over ~N/C steps (every node has
        # to fire once before the spread clock is stationary); timing the
        # ramp would understate the steady rate.  Chunk-multiple so the
        # jitted span length stays fixed.
        warm = chunk if smoke else max(chunk, (3 * n_nodes) // (2 * cohort))
        warm = -(-warm // chunk) * chunk
        done = 0
        while done < warm:
            e.scheduler.run_span(done, chunk)
            done += chunk
        e._bench_round = done
        return done

    if base is not None:
        _warm(base, base_nodes)
    warm_steps = _warm(big, million_nodes)
    print(f"  warmed N={million_nodes} for {warm_steps} steps "
          f"(occupancy steady state)", flush=True)
    base_rates, big_rates = [], []
    for r in range(repeats):
        if base is not None:
            base_rates.append(_events_per_sec(base, steps))
        big_rates.append(_events_per_sec(big, steps))
        print(f"  repeat {r}: "
              + (f"N={base_nodes} {base_rates[-1]:,.0f} ev/s, "
                 if base_rates else "")
              + f"N={million_nodes} {big_rates[-1]:,.0f} ev/s", flush=True)
    big_med = float(np.median(big_rates))
    base_med = float(np.median(base_rates)) if base_rates else 0.0
    mm = big.scheduler.memory_model()
    m_big = big.scheduler.extra_metrics()
    cold_ratio = mm["cold"]["total"] / max(mm["cold"]["total_fp32"], 1)
    # live-vs-analytic check on the million engine alone: drop the
    # baseline first so its buffers don't pollute the live-bytes sum
    del base
    gc.collect()
    snap = memory_snapshot()
    dataset_bytes = int(
        big._dev_x.nbytes + big._dev_y.nbytes
        + big._dev_lens.nbytes + big._dev_parts_pad.nbytes
    )
    analytic = mm["hot"]["total"] + mm["cold"]["total"] + dataset_bytes
    live_ratio = snap["device_live_bytes"] / max(analytic, 1)
    rec = {
        "name": f"million_n{million_nodes}_c{cohort}_{cold}",
        "base_nodes": base_nodes,
        "million_nodes": million_nodes,
        "cohort_capacity": cohort,
        "cold_dtype": cold,
        "n_params": int(big.n_params),
        "steps": steps,
        "build_s": build_s,
        "random_regular_1m_build_s": rr_s,
        "base_events_per_s": base_rates,
        "million_events_per_s": big_rates,
        "base_events_per_s_median": base_med,
        "million_events_per_s_median": big_med,
        "million_over_base_ratio": big_med / base_med if base_med else None,
        "events_total": m_big["events_total"],
        "cohort_occupancy_mean": m_big["cohort_occupancy_mean"],
        "cohort_overflow_total": m_big["cohort_overflow_total"],
        "cohort_overflow_ratio": m_big["cohort_overflow_ratio"],
        "selection_fallback_total": m_big["selection_fallback_total"],
        "cold_bytes": mm["cold"]["total"],
        "cold_bytes_fp32": mm["cold"]["total_fp32"],
        "cold_over_fp32_ratio": cold_ratio,
        "dataset_bytes": dataset_bytes,
        "analytic_total_bytes": analytic,
        "live_over_analytic_ratio": live_ratio,
        "memory_model": mm,
        "memory_after": snap,
    }
    recs.append(rec)
    print(f"  cold {mm['cold']['total']/1e6:.0f} MB vs fp32 "
          f"{mm['cold']['total_fp32']/1e6:.0f} MB "
          f"(ratio {cold_ratio:.3f}); live/analytic {live_ratio:.2f}",
          flush=True)
    gates_ok = True
    if cold == "int8" and cold_ratio > 0.3:
        print(f"[population] FAIL: int8 cold bytes ratio {cold_ratio:.3f} "
              "> 0.3", flush=True)
        gates_ok = False
    total_steps = warm_steps + repeats * steps
    if m_big["selection_fallback_total"] >= total_steps:
        print(f"[population] FAIL: hier selection fell back to the flat "
              f"oracle on all {total_steps} steps — the segment hierarchy "
              "never engaged", flush=True)
        gates_ok = False
    if not smoke:
        ratio = big_med / max(base_med, 1e-9)
        print(f"  median N={base_nodes} {base_med:,.0f} ev/s vs "
              f"N={million_nodes} {big_med:,.0f} ev/s -> ratio "
              f"{ratio:.2f} (gate >= 0.5, 10x N)", flush=True)
        if ratio < 0.5:
            print("[population] FAIL: million-node throughput below 0.5x "
                  "the 100k rate", flush=True)
            gates_ok = False
        if not (1 / 1.5 <= live_ratio <= 1.5):
            print(f"[population] FAIL: live/analytic memory ratio "
                  f"{live_ratio:.2f} outside [0.67, 1.5]", flush=True)
            gates_ok = False
        rec["throughput_gate_ok"] = bool(big_med >= 0.5 * base_med)
    return recs, gates_ok


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pop-nodes", type=int, default=100_000)
    ap.add_argument("--dense-nodes", type=int, default=1024)
    ap.add_argument("--cohort", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=16,
                    help="MLP hidden width (P = feat*H + H + H*classes + "
                    "classes parameters per node)")
    ap.add_argument("--steps", type=int, default=32,
                    help="timed event steps per repeat")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8,
                    help="per-event local SGD batch size")
    ap.add_argument("--million-nodes", type=int, default=1_000_000,
                    help="population of the million-node stage (0 = skip)")
    ap.add_argument("--million-cohort", type=int, default=0,
                    help="cohort capacity of the million-node stage "
                    "(0 = same as --cohort)")
    ap.add_argument("--cold-dtype", default="int8",
                    choices=["fp32", "bf16", "int8"],
                    help="cold population storage of the million-node stage")
    ap.add_argument("--million-only", action="store_true",
                    help="run only the million-node stage (+ selection "
                    "oracle check) — the CI N=1M smoke entry point")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: small cohort/steps, single repeat, "
                    "assert the hot-set/cold-bytes bounds and the "
                    "selection oracle but skip the (noisy-in-CI) "
                    "throughput gates")
    ap.add_argument("--hot-bound-mb", type=float, default=64.0,
                    help="smoke-mode ceiling on analytic hot-set MB")
    args = ap.parse_args()
    if args.smoke:
        args.cohort = min(args.cohort, 256)
        args.steps = min(args.steps, 8)
        args.repeats = 1
        args.dense_nodes = min(args.dense_nodes, 256)
    million_cohort = args.million_cohort or args.cohort
    recs = [{"name": "_memory_before", **memory_snapshot()}]
    recs.append(check_selection_oracle(args.chunk, args.hidden))
    gate_ok = True
    run_recs = []
    if not args.million_only:
        recs.append(check_memory_independence(
            args.cohort, args.hidden, max(args.pop_nodes // 10, args.cohort),
            args.pop_nodes, args.chunk))
        run_recs, gate_ok = run_population(
            args.dense_nodes, args.pop_nodes, args.cohort, args.hidden,
            args.steps, args.repeats, args.chunk, args.batch)
        recs += run_recs
    million_ok = True
    if args.million_nodes > 0:
        m_recs, million_ok = run_million(
            args.pop_nodes, args.million_nodes, million_cohort, args.hidden,
            args.steps, args.repeats, args.chunk, args.batch,
            args.cold_dtype, args.smoke)
        recs += m_recs
        if args.smoke:
            hot = m_recs[0]["memory_model"]["hot"]["total"]
            assert hot <= args.hot_bound_mb * 1e6, (
                f"million-stage hot set {hot/1e6:.1f} MB exceeds the "
                f"{args.hot_bound_mb} MB smoke bound")
            print(f"[population] million smoke OK: hot set {hot/1e6:.2f} MB "
                  f"<= {args.hot_bound_mb} MB", flush=True)
    path = save_results("bench_population", recs)
    print(f"[population] results -> {path}", flush=True)
    if not million_ok:
        raise SystemExit("[population] FAIL: million-node stage gate "
                         "(see log above)")
    if args.smoke:
        if run_recs:
            hot = run_recs[0]["memory_model"]["hot"]["total"]
            assert hot <= args.hot_bound_mb * 1e6, (
                f"hot set {hot/1e6:.1f} MB exceeds the "
                f"{args.hot_bound_mb} MB smoke bound")
            print(f"[population] smoke OK: hot set {hot/1e6:.2f} MB "
                  f"<= {args.hot_bound_mb} MB", flush=True)
    elif not gate_ok:
        raise SystemExit("[population] FAIL: dense/cohort per-event "
                         "throughput ratio exceeds 2.0")


if __name__ == "__main__":
    enable_compile_cache()
    main()
