"""Kernel microbenchmarks: interpret-mode correctness cost is meaningless
for wall-time, so this bench reports (a) the pure-jnp oracle wall time on
CPU as a stand-in and (b) the kernel's structural roofline: bytes touched,
FLOPs, arithmetic intensity — the numbers that matter on the TPU target."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.utils.compile_cache import enable_compile_cache


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def run():
    rows = []
    # gossip_mix: K=6 neighbors x 4M params
    K, M = 6, 4_000_000
    nb = jax.random.normal(jax.random.key(0), (K, M))
    w = jnp.full((K,), 1.0 / K)
    us = _time(jax.jit(ref.gossip_mix_ref), nb, w)
    byts = (K + 1) * M * 4
    rows.append(("gossip_mix[6x4M]", us, f"bytes={byts/1e6:.0f}MB AI={2*K*M/byts:.2f}"))

    # quantize 4M
    x = jax.random.normal(jax.random.key(1), (64, 65536))
    us = _time(jax.jit(ref.quantize_ref), x)
    rows.append(("quantize_int8[4M]", us, f"bytes={x.size*5/1e6:.0f}MB"))

    # secure mask K=5 x 4M
    bits = jax.random.bits(jax.random.key(2), (5, M), jnp.uint32)
    signs = jnp.ones((5,))
    xv = jax.random.normal(jax.random.key(3), (M,))
    us = _time(jax.jit(ref.secure_mask_apply_ref), xv, bits, signs, 1.0)
    rows.append(("secure_mask[5x4M]", us, f"bytes={(6*M*4)/1e6:.0f}MB"))

    # ssd chunk: G=32 chunks, L=128, H=8, P=64, N=128
    G, L, H, P, N = 32, 128, 8, 64, 128
    xdt = jax.random.normal(jax.random.key(4), (G, L, H, P)) * 0.1
    Bc = jax.random.normal(jax.random.key(5), (G, L, N))
    Cc = jax.random.normal(jax.random.key(6), (G, L, N))
    cum = -jnp.cumsum(jax.random.uniform(jax.random.key(7), (G, L, H)) * 0.1, 1)
    flops = G * H * (2 * L * L * N + 2 * L * L * P + 2 * L * N * P)

    def ssd_all(xdt, Bc, Cc, cum):
        return jax.vmap(ref.ssd_chunk_ref)(xdt, Bc, Cc, cum)

    us = _time(jax.jit(ssd_all), xdt, Bc, Cc, cum)
    rows.append(("ssd_chunk[32x128]", us, f"GFLOP={flops/1e9:.2f}"))

    # swa attention S=4096 W=1024 D=64 BH=8
    BH, S, W, D = 8, 4096, 1024, 64
    q = jax.random.normal(jax.random.key(8), (BH, S, D))
    k = jax.random.normal(jax.random.key(9), (BH, S, D))
    v = jax.random.normal(jax.random.key(10), (BH, S, D))

    def swa_all(q, k, v):
        return jax.vmap(lambda a, b, c: ref.swa_attention_ref(a, b, c, W))(q, k, v)

    us = _time(jax.jit(swa_all), q, k, v, reps=2)
    flops = BH * 4 * S * W * D
    rows.append(("swa_attn[4k,w1k]", us, f"GFLOP={flops/1e9:.2f} (O(S*W) vs O(S^2)={S/W:.0f}x)"))
    return rows


def main():
    for name, us, derived in run():
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
