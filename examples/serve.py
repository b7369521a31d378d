"""Batched serving demo: load (or init) a smoke-scale model from the arch
registry and serve a batch of requests through the KV-cache decode path.

    PYTHONPATH=src python examples/serve.py --arch smollm-135m --batch 4
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_smoke_config
from repro.models.api import init_params
from repro.serving import ServeConfig, ServingEngine
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=[a for a in ARCHS if a != "gn-lenet"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    if cfg.family in ("encdec",):
        print("serve.py demos decoder-only archs; whisper decode is covered "
              "by tests/test_decode_consistency.py")
        return
    params = init_params(cfg, jax.random.key(0))
    engine = ServingEngine(cfg, ServeConfig(batch=args.batch, max_len=128), params)
    prompts = jax.random.randint(jax.random.key(1), (args.batch, 8), 1, cfg.vocab)
    out = engine.generate(prompts, max_new=args.max_new)
    print(f"arch={args.arch} (smoke config, family={cfg.family})")
    for b in range(args.batch):
        print(f"  request {b}: prompt={list(map(int, prompts[b]))} -> "
              f"generated={list(map(int, out[b]))}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
