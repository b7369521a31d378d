"""Bring-up smoke run of the RoundEngine on a TPU chip.

    python chip_smoke.py               # one chip: three phases, N=256 nodes
    python chip_smoke.py --chips 4     # the node axis sharded over 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

Each phase builds a ``DLConfig`` and a ``RoundEngine`` the way
``examples/quickstart.py`` does — GN-LeNet at full width (``cnn_init``
width 32, 579,594 parameters per node) on the seeded synthetic CIFAR-10
stand-in with 2-shard non-IID partitions — runs two compiled chunks of 8
rounds, then checks one round's real operands of each Pallas kernel the
phase uses against its reference on the same chip, and that the kernel is
compiled into the engine's step (``tpu_custom_call``), not interpreted.

One line per phase reports compile seconds, persistent-cache hits, the
steady rounds/s of the second chunk (a smoke figure, not a benchmark
number), device bytes (in use after the phase, the process peak so far,
and the compiled step's own footprint), loss before/after, the engine's own
evaluation and the checks.  The
last line is ``{"ok": true, "device": {...}}``, printed only when every
phase ran and every check passed.  Without a TPU the script exits non-zero
and prints no result; ``--rehearse`` runs the same code at a tiny size on
whatever backend is present and names that platform.  Everything runs in
this one process: a child could not use a chip its parent holds.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.utils.compile_cache import enable_compile_cache  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Size:
    nodes: int
    width: int       # cnn_init width; 32 is GN-LeNet's full width
    n_train: int


FULL = Size(nodes=256, width=32, n_train=50_000)  # CIFAR-10's train set size
TINY = Size(nodes=16, width=8, n_train=2_048)
CHUNK = 8  # rounds per compiled chunk; two chunks per run

PHASES = {
    "sync-dynamic": dict(topology="dynamic", degree=5, sharing="full"),
    "payload-topk": dict(topology="regular", degree=5, sharing="topk",
                         budget=0.01, payload="on"),
    "secure": dict(topology="regular", degree=5, secure=True),
}
SHARDED = dict(topology="regular", degree=5, sharing="full")

# Sharded vs single-device trajectories: mean test loss after two chunks
# within 1% (relative).  The paths sum the same fp32 terms in different
# orders (fused kernel vs collectives), and GN-LeNet's ReLU/max-pool
# switching amplifies those ulps chaotically: on 4 CPU devices at N=16 the
# final parameters sit 1e-7 apart for 9 rounds, then 2.4e-3 (relative L2)
# by round 16.  So parameter distance is reported, not bounded; the
# sharded gossip operator itself is checked on identical operands instead.
SHARD_LOSS_RTOL = 1e-2


class CacheEvents:
    """Counts JAX persistent compilation-cache requests and hits."""

    def __init__(self):
        self.requests = self.hits = 0

    def __call__(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def since(self, mark):
        return f"{self.hits - mark[0]}/{self.requests - mark[1]}"

    def mark(self):
        return (self.hits, self.requests)


def _workload(size: Size):
    from repro.data import NodeBatcher, make_dataset, sharding_partition
    from repro.models.api import cross_entropy
    from repro.models.cnn import cnn_apply, cnn_init

    ds = make_dataset("cifar10", n_train=size.n_train, n_test=8, seed=0)
    parts = sharding_partition(ds.train_y, size.nodes, shards_per_node=2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=8, seed=0)

    def loss_fn(p, x, y):
        return cross_entropy(cnn_apply(p, x), y)

    def acc_fn(p, x, y):
        return (cnn_apply(p, x).argmax(-1) == y).mean()

    return batcher, loss_fn, acc_fn, lambda k: cnn_init(k, width=size.width)


def _engine(size: Size, workload, **kw):
    from repro.core import DLConfig, RoundEngine
    from repro.optim import make_optimizer

    batcher, loss_fn, acc_fn, init = workload
    dl = DLConfig(n_nodes=size.nodes, rounds=2 * CHUNK, eval_every=2 * CHUNK,
                  chunk_rounds=CHUNK, **kw)
    return RoundEngine(dl, init, loss_fn, acc_fn, make_optimizer("sgd", 0.05),
                       batcher)


def _mem(dev, key):
    """``key`` of the device's memory stats: "bytes_in_use" now, or
    "peak_bytes_in_use", the largest since the process started (JAX has
    no reset, so a later phase reports the peak of every phase so far)."""
    stats = dev.memory_stats()
    return None if stats is None else stats.get(key)


def _drive(eng, cache: CacheEvents):
    """Two chunks through the scheduler's chunk entry point (what
    ``RoundEngine.run`` dispatches), each timed to ``block_until_ready``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine import over_nodes

    tx, ty = (jnp.asarray(a) for a in eng.batcher.test_batch())
    loss = jax.jit(lambda p: jnp.mean(over_nodes(eng.loss_fn, p, tx, ty)))
    loss_before = float(loss(eng.params))
    mark = cache.mark()
    t0 = time.perf_counter()
    eng.scheduler.run_span(0, CHUNK)
    jax.block_until_ready(eng.params)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.scheduler.run_span(CHUNK, CHUNK)
    jax.block_until_ready(eng.params)
    steady = time.perf_counter() - t0
    return {
        "compile_s": first - steady,
        "cache_hits": cache.since(mark),
        "smoke_rounds_per_s": CHUNK / steady,
        "loss_before": loss_before,
        "loss_after": float(loss(eng.params)),
        "acc_after": float(np.mean(eng._eval_jit(eng.params, tx, ty))),
    }


def _step(eng):
    """The engine's scanned chunk, compiled for the shapes run used."""
    sched = eng.scheduler
    xs = sched._stage_xs(0, CHUNK)
    return sched._chunk_jit.lower(eng.params, eng.opt_state, eng.share_state,
                                  xs).compile()


def _step_bytes(step):
    """This phase's own device footprint: the compiled step's arguments,
    outputs and temporaries (its memory analysis; per device when sharded)."""
    m = step.memory_analysis()
    return {"args": m.argument_size_in_bytes, "out": m.output_size_in_bytes,
            "temp": m.temp_size_in_bytes}


def _in_step(text: str, kernel: str) -> bool:
    return any("tpu_custom_call" in line and kernel in line
               for line in text.splitlines())


def _close(name, got, want, bound):
    import numpy as np

    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    return {"check": name, "max_abs_err": err, "bound": bound,
            "ok": bool(err <= bound)}


# -- per-phase checks -------------------------------------------------------


def _check_gossip(eng):
    """One dynamic round's table applied to the real node rows: the engine's
    sparse mix (the fused kernel on TPU) vs kernels.ref.gossip_mix_nodes_ref
    and vs the XLA form it replaces on TPU, with both forms timed.
    Bound: 6 weighted fp32 terms (weights sum to 1) summed in another order
    differ by a few ulps of max|x|; 1e-5 * max|x| is ~80 ulps."""
    import jax
    import jax.numpy as jnp

    from repro.core.mixing import apply_W, gather_mix
    from repro.core.topology import SparseTopology
    from repro.kernels import ref
    from repro.utils.pytree import tree_vector

    X = jax.vmap(tree_vector)(eng.params)
    t = eng.sampler.round_table(2 * CHUNK - 1)
    W = SparseTopology(jnp.asarray(t.nbr), jnp.asarray(t.w), jnp.asarray(t.w_self))
    got = jax.jit(apply_W)(W, X)

    @jax.jit
    def want_fn(W, X):  # 16 receivers at a time: the full stack is GBs
        def rows(r):
            n, nbr, w = r
            xs = jnp.concatenate([X[n][None], jnp.take(X, nbr, axis=0)])
            with jax.default_matmul_precision("highest"):
                return ref.gossip_mix_nodes_ref(xs[None], w[None])[0]

        ws = jnp.concatenate([W.w_self[:, None], W.w], axis=1)
        return jax.lax.map(rows, (jnp.arange(X.shape[0]), W.nbr, ws),
                           batch_size=16)

    want = want_fn(W, X)
    bound = 1e-5 * float(jnp.max(jnp.abs(X)))
    xla = jax.jit(gather_mix)
    out = [_close("gossip_mix_nodes vs gossip_mix_nodes_ref", got, want, bound),
           _close("gossip_mix_nodes vs the XLA gather + einsum", got,
                  xla(W, X), bound)]
    # smoke timings of the two forms on the same operands (median of 5)
    for name, f in (("kernel_ms", jax.jit(apply_W)), ("xla_ms", xla)):
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(W, X))
            times.append(1e3 * (time.perf_counter() - t0))
        out[1][name] = sorted(times)[2]
    return out


def _check_topk(eng):
    """The round's real top-k operand |x - last_shared|: the survival-count
    histogram kernel vs kernels.ref.abs_histogram_rows_ref row by row
    (integer counts, exact), and the threshold it yields vs lax.top_k: at least k
    survivors (t <= the exact k-th largest) and at most 1.35k + 8, the
    quality bound tests/test_kernels.py holds the selector to."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.sparsify import log_edges_rows
    from repro.utils.pytree import tree_vector

    X = jax.vmap(tree_vector)(eng.params)
    a = jnp.abs(X - eng.share_state["last_shared"])
    k = eng.sharing._k(X)
    edges = jax.jit(log_edges_rows)(a)
    got = ops.abs_histogram_rows(a, edges)
    want = jax.jit(lambda a, e: jax.lax.map(
        lambda r: ref.abs_histogram_rows_ref(r[0][None], r[1][None])[0],
        (a, e)))(a, edges)
    hist = {"check": "abs_histogram_rows vs abs_histogram_rows_ref",
            "mismatched_counts": int(np.sum(np.asarray(got) != np.asarray(want)))}
    hist["ok"] = hist["mismatched_counts"] == 0

    t = jax.jit(ops.topk_threshold_rows, static_argnums=1)(a, k)
    nsel = np.asarray(jnp.sum(a >= t[:, None], axis=1))
    kth = np.asarray(jax.jit(lambda a: jax.lax.top_k(a, k)[0][:, -1])(a))
    thr = {"check": "topk_threshold_rows vs lax.top_k", "k": k,
           "min_survivors": int(nsel.min()), "max_survivors": int(nsel.max()),
           "t_le_kth": bool(np.all(np.asarray(t) <= kth))}
    thr["ok"] = bool(thr["t_le_kth"] and nsel.min() >= k
                     and nsel.max() <= int(1.35 * k) + 8)
    return [hist, thr]


def _check_secure(eng):
    """(1) The fused keyed-mask kernel on D slots of 32 real node rows vs
    kernels.ref.secure_mask_apply_pairs_keyed_ref: the masks are the same
    bits mapped the same way, so only the order of the 4 signed terms of
    a message (|term| <= bound = 1) differs — bound 4e-6, about 5 * 5 ulps
    of the sum.  (2) The masked round vs the unmasked Metropolis-Hastings
    round W @ X, at the fp32 tolerance tests/test_secure.py holds it to."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.utils.pytree import tree_vector

    X = jax.vmap(tree_vector)(eng.params)
    rows, D = min(32, X.shape[0]), eng.dl.degree
    Q = D * (D - 1) // 2
    xs = jnp.take(X, (np.arange(rows)[None] + rows * np.arange(D)[:, None]) % X.shape[0],
                  axis=0)                                  # (D, rows, P)
    keys = jax.random.bits(jax.random.key(1), (rows, Q, 2), jnp.uint32)
    signs = jnp.asarray(np.random.default_rng(2).choice([-1.0, 0.0, 1.0], (rows, D, D)),
                        jnp.float32)
    got = ops.secure_mask_apply_pairs_keyed(xs, keys, signs, 1.0)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.secure_mask_apply_pairs_keyed_ref, static_argnums=3)(
            xs, keys, signs, 1.0)
    out = [_close("secure_mask_apply_pairs_keyed vs ref", got, want, 4e-6)]

    W = eng._mix_static
    masked, _, _ = jax.jit(
        lambda X, W: eng.sharing.round(X, W, eng.share_state, jax.random.key(5),
                                       float(D), rnd=3))(X, W)
    Wd = jnp.asarray(eng.graph.metropolis_hastings(), jnp.float32)
    plain = jax.jit(lambda Wd, X: jnp.matmul(Wd, X, precision="highest"))(Wd, X)
    err = np.abs(np.asarray(masked) - np.asarray(plain))
    tol = 5e-5 + 5e-4 * np.abs(np.asarray(plain))
    out.append({"check": "masked round vs unmasked W @ X (rtol 5e-4, atol 5e-5)",
                "max_abs_err": float(err.max()), "ok": bool(np.all(err <= tol))})
    return out


CHECKS = {"sync-dynamic": (_check_gossip, "gossip_mix_nodes"),
          "payload-topk": (_check_topk, "abs_survival_rows"),
          "secure": (_check_secure, "secure_mask_keyed")}


def run_one_chip(size, cache, on_tpu):
    import jax

    workload = _workload(size)
    ok = True
    for name, kw in PHASES.items():
        eng = _engine(size, workload, **kw)
        line = {"phase": name, "n_nodes": size.nodes, "n_params": eng.n_params}
        line.update(_drive(eng, cache))
        if name == "secure":  # the mask kernel's work: with the round rate, its words/s
            line["prf_words_per_round"] = eng.sharing.prf_words_per_round(
                size.nodes, eng.n_params)
        dev = jax.devices()[0]
        line["bytes_in_use"] = _mem(dev, "bytes_in_use")
        line["peak_bytes_in_use_so_far"] = _mem(dev, "peak_bytes_in_use")
        step = _step(eng)
        line["step_bytes"] = _step_bytes(step)
        check, kernel = CHECKS[name]
        line["checks"] = check(eng)
        if on_tpu:
            line["checks"].append({"check": f"{kernel} compiled in the step",
                                   "ok": _in_step(step.as_text(), kernel)})
        ok &= all(c["ok"] for c in line["checks"])
        print(json.dumps(line), flush=True)
        del eng
        gc.collect()
    return ok


def _check_sharded_mix(eng, X):
    """The sharded gossip operator (this engine's backend, under shard_map
    on its mesh) vs the single-device mix (the fused kernel on TPU) on the
    same real rows X; bound as in _check_gossip."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.mixing import apply_W

    rows = P("nodes", None)
    sharded = jax.jit(shard_map(
        lambda Xb: apply_W(eng.scheduler._wrap_mix(None), Xb),
        mesh=eng._mesh, in_specs=rows, out_specs=rows, check_vma=False))
    got = sharded(X)
    want = jax.jit(apply_W)(eng._mix_static, X)
    return _close("sharded gossip vs single-device gossip", got, want,
                  1e-5 * float(jnp.max(jnp.abs(X))))


def run_four_chips(size, cache):
    import jax
    import numpy as np

    from repro.utils.pytree import tree_vector

    workload = _workload(size)
    finals, losses = {}, {}
    cases = {"sharded4-gather": dict(shard_devices=4, shard_backend="gather"),
             "sharded4-ppermute": dict(shard_devices=4, shard_backend="ppermute"),
             "single": {}}
    ok = True
    for name, extra in cases.items():
        eng = _engine(size, workload, **SHARDED, **extra)
        line = {"phase": name, "n_nodes": size.nodes, "n_params": eng.n_params}
        line.update(_drive(eng, cache))
        line["bytes_in_use_per_device"] = [_mem(d, "bytes_in_use")
                                           for d in jax.devices()]
        line["peak_bytes_in_use_so_far_per_device"] = [
            _mem(d, "peak_bytes_in_use") for d in jax.devices()]
        line["step_bytes_per_device"] = _step_bytes(_step(eng))
        X = jax.vmap(tree_vector)(eng.params)
        if eng.sharded:
            line["checks"] = [_check_sharded_mix(eng, X)]
            ok &= line["checks"][0]["ok"]
        finals[name], losses[name] = np.asarray(X), line["loss_after"]
        print(json.dumps(line), flush=True)
        del eng, X
        gc.collect()
    ref = finals["single"]
    for name in ("sharded4-gather", "sharded4-ppermute"):
        rel = float(np.linalg.norm(finals[name] - ref) / np.linalg.norm(ref))
        dloss = abs(losses[name] - losses["single"])
        good = dloss <= SHARD_LOSS_RTOL * abs(losses["single"])
        ok &= good
        print(json.dumps({"check": f"{name} vs single trajectory",
                          "loss_abs_diff": dloss, "loss_rtol": SHARD_LOSS_RTOL,
                          "params_rel_l2": rel, "ok": good}), flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded node axis and its "
                         "single-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend (a CPU rehearsal of the "
                         "chip run); the result names the real platform")
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    import jax

    cache = CacheEvents()
    jax.monitoring.register_event_listener(cache)
    devs = jax.devices()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (backend {jax.default_backend()!r}); "
              "--rehearse runs a tiny version here", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"{len(devs)} visible", file=sys.stderr)
        return 2
    print(json.dumps({"compile_cache_dir": cache_dir,
                      "device_kind": devs[0].device_kind}), flush=True)
    size = TINY if args.rehearse else FULL
    try:
        ok = (run_four_chips(size, cache) if args.chips == 4
              else run_one_chip(size, cache, on_tpu))
    except Exception:
        traceback.print_exc()
        return 1
    if not ok:
        print("chip_smoke: a check failed (see the phase lines)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
