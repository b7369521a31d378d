"""End-to-end training driver (deliverable b's "train a ~100M model for a
few hundred steps"): decentralized LM training of any registry arch at
smoke- or full-scale on the available devices.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \\
        --scale smoke --steps 200 --nodes 4

Uses the node-stacked D-PSGD trainer (vmap local grads + gossip) — the
same code path the dry-run lowers for the production mesh — plus the data
pipeline, checkpointing, and per-round JSON results.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_checkpoint, load_checkpoint, save_checkpoint
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data import make_dataset, sharding_partition
from repro.models.api import init_params
from repro.optim import make_optimizer
from repro.training.trainer import TrainConfig, make_train_step
from repro.utils.compile_cache import enable_compile_cache


def build_lm_batcher(cfg, n_nodes: int, batch: int, seq: int, seed: int = 0):
    """Token-stream batcher: synthetic Markov LM data, 2-sharded non-IID by
    document class, reshaped to (N, B, seq)."""
    ds = make_dataset("lm", n_train=n_nodes * 64, n_test=64, seq_len=seq + 1,
                      vocab=min(cfg.vocab, 512), seed=seed)
    parts = sharding_partition(ds.train_y, n_nodes, 2, seed=seed)

    def batch_fn(step: int):
        xs = []
        for i, part in enumerate(parts):
            rng = np.random.default_rng(seed * 999983 + step * 17 + i)
            take = rng.choice(part, batch, replace=len(part) < batch)
            xs.append(ds.train_x[take])
        arr = np.stack(xs)  # (N, B, seq+1)
        return {"tokens": jnp.asarray(arr[:, :, :-1]),
                "labels": jnp.asarray(arr[:, :, 1:])}

    return batch_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=ARCHS)
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--topology", default="regular",
                    choices=["ring", "regular", "fully"])
    ap.add_argument("--degree", type=int, default=5)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "momentum", "adamw"])
    ap.add_argument("--chunk-steps", type=int, default=8,
                    help="steps compiled into one lax.scan dispatch "
                         "(RoundEngine-style chunking; 1 = per-step dispatch)")
    ap.add_argument("--ckpt-dir", default="results/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch) if args.scale == "full" else get_smoke_config(args.arch)
    if cfg.family == "cnn":
        raise SystemExit("use examples/quickstart.py for the CNN workload")
    cfg = cfg.replace(dtype="float32")  # CPU
    N = args.nodes
    if args.topology == "regular" and N <= args.degree:
        args.topology = "fully"

    print(f"[train] arch={args.arch} scale={args.scale} N={N} "
          f"topology={args.topology} steps={args.steps}")
    keys = jax.random.split(jax.random.key(0), N)
    params = jax.vmap(lambda k: init_params(cfg, k))(keys)
    opt = make_optimizer(args.optimizer, args.lr)
    opt_state = jax.vmap(opt.init)(params)

    tc = TrainConfig(n_nodes=N, topology=args.topology, degree=args.degree,
                     mixing_impl="roll", grad_clip=1.0)
    step_fn = make_train_step(cfg, opt, tc)
    batch_fn = build_lm_batcher(cfg, N, args.batch, args.seq)

    # RoundEngine-style chunking: scan `chunk` steps per dispatch over
    # host-pre-stacked token batches (tokens are tiny; the models are not).
    # Per-step losses are still collected, so the logging cadence is intact.
    def chunk_fn(params, opt_state, batches):
        def body(carry, batch):
            params, opt_state = carry
            params, opt_state, loss = step_fn(params, opt_state, batch)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(body, (params, opt_state), batches)
        return params, opt_state, losses

    chunk_jit = jax.jit(chunk_fn)
    chunk = max(args.chunk_steps, 1)

    start = 0
    if args.resume and latest_checkpoint(args.ckpt_dir) is not None:
        start, trees = load_checkpoint(args.ckpt_dir)
        params = jax.tree_util.tree_map(
            lambda a, b: jnp.asarray(b, a.dtype), params, trees["params"])
        print(f"[train] resumed from step {start}")

    os.makedirs(args.ckpt_dir, exist_ok=True)
    hist = []
    t0 = time.time()
    step = start
    while step < args.steps:
        r = min(chunk, args.steps - step)
        batches = jax.tree_util.tree_map(
            lambda *bs: jnp.stack(bs), *[batch_fn(step + s) for s in range(r)]
        )
        params, opt_state, losses = chunk_jit(params, opt_state, batches)
        losses = np.asarray(losses)
        for s in range(r):
            gstep = step + s
            if gstep % args.log_every == 0 or gstep == args.steps - 1:
                l = float(losses[s])
                hist.append({"step": gstep, "loss": l, "wall_s": time.time() - t0})
                print(f"[train] step {gstep:5d} loss {l:.4f} "
                      f"({(time.time() - t0) / max(gstep - start + 1, 1):.2f}s/step)",
                      flush=True)
        step += r
        if (step // args.ckpt_every) > ((step - r) // args.ckpt_every) and step < args.steps:
            save_checkpoint(args.ckpt_dir, step, params=params)
    save_checkpoint(args.ckpt_dir, args.steps, params=params)
    with open(os.path.join(args.ckpt_dir, "history.json"), "w") as f:
        json.dump(hist, f, indent=1)
    print(f"[train] done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}; "
          f"checkpoint + history in {args.ckpt_dir}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
