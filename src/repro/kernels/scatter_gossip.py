"""Pallas TPU kernel: fused payload-indexed gossip merge.

The compressed-sharing hot path: each receiver n holds its own row x[n]
(P,) and K = 1 + degree payload operands (self first, then gathered
neighbor payloads) of k coordinates each — ``idx[n, s]`` (k,) int32 and
``val[n, s]`` (k,) fp32.  DecentralizePy's missing-coordinate rule says a
coordinate not present in a neighbor's payload falls back to the
receiver's own value, which reduces to a sparse correction:

    out[n] = x[n] + sum_s w[n, s] * scatter(idx[n, s], val[n, s] - x[n][idx])

This generalizes ``gossip_mix.gossip_mix_nodes`` (dense (K, N, P) operand
stacks) to indexed payloads: O(N·K·k) work instead of O(N·K·P), reading
x once per P-block.  TPU has no fast VMEM scatter, so the kernel applies
payload contributions with a broadcast-compare accumulate (idx == column
one-hot, a VPU-friendly (BN, K·k) outer comparison per block) — exact for
duplicate indices across operands because contributions sum.  Right for
small K·k (sparsified budgets); for K·k approaching P the dense
``gossip_mix_nodes`` form wins.  Interpret mode on CPU; tested against
``kernels.ref.payload_mix_nodes_ref`` and the dense-mask oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 65536


def _kernel(x_ref, idx_ref, val_ref, w_ref, o_ref, *, block_n: int):
    # x: (1, BN) at column block j; idx/val: (1, K, k); w: (1, K, 1)
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                   # (1, BN)
    idx = idx_ref[...]                                   # (1, K, k)
    val = val_ref[...].astype(jnp.float32)               # (1, K, k)
    w = w_ref[...].astype(jnp.float32)                   # (1, K, 1)
    K, k = idx.shape[1], idx.shape[2]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1) + j * block_n
    flat_idx = idx.reshape(1, K * k)                     # (1, K*k)
    flat_val = val.reshape(1, K * k)
    flat_w = jnp.broadcast_to(w, (1, K, k)).reshape(1, K * k)
    # one-hot scatter: hit[e, c] = payload entry e lands on column c
    hit = (flat_idx[0][:, None] == cols[0][None, :]).astype(jnp.float32)  # (K*k, BN)
    own = jnp.sum(hit * x[0][None, :], axis=1)           # x[idx] for in-block hits
    contrib = flat_w[0] * (flat_val[0] - own)            # (K*k,)
    # entries whose idx falls outside this block contribute nothing: their
    # hit row is all zero, so the (K*k, BN) weighted sum drops them.
    delta = jnp.sum(hit * contrib[:, None], axis=0)      # (BN,)
    o_ref[...] = (x + delta[None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def payload_mix_nodes(x, idx, val, w, *, interpret: bool = False,
                      block_n: int = BLOCK_N):
    """x: (N, P); idx: (N, K, k) int32 in [0, P); val: (N, K, k); w: (N, K)
    -> (N, P).  Grid (N, P/BN); the block adapts down to the (128-aligned)
    row length so small models don't pad to the full 64k block."""
    N, P = x.shape
    _, K, k = idx.shape
    bn = min(block_n, -(-P // 128) * 128)
    pad = (-P) % bn
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    grid = (N, xp.shape[1] // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, block_n=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda b, j: (b, j)),
            pl.BlockSpec((1, K, k), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, K, k), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, K, 1), lambda b, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda b, j: (b, j)),
        out_shape=jax.ShapeDtypeStruct((N, xp.shape[1]), x.dtype),
        interpret=interpret,
    )(xp, idx, val, w[:, :, None])
    return out[:, :P]
