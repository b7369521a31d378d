"""Pallas TPU kernel: fused gossip aggregation.

out[m] = sum_k w_k * neighbors[k, m] — the Metropolis-Hastings weighted
merge of K received neighbor models plus self.  Fusing the K-way weighted
sum reads each operand exactly once from HBM (one pass) instead of K
accumulate passes; the op is purely memory-bound so this is the whole win.

Tiling: flat parameter vector padded to (K, M), blocks (K, BN) in VMEM —
K is small (degree+1 <= ~10), BN = 64k floats -> ~2.5 MB/block fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 65536


def _kernel(w_ref, x_ref, o_ref):
    # x_ref: (K, BN); w_ref: (K, 1) in SMEM-ish VMEM; o_ref: (BN,)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)  # (K, 1)
    o_ref[...] = jnp.sum(x * w, axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def gossip_mix(neighbors, weights, *, interpret: bool = False, block_n: int = BLOCK_N):
    """neighbors: (K, M) any float dtype; weights: (K,) -> (M,)."""
    K, M = neighbors.shape
    pad = (-M) % block_n
    x = jnp.pad(neighbors, ((0, 0), (0, pad)))
    grid = (x.shape[1] // block_n,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((x.shape[1],), neighbors.dtype),
        interpret=interpret,
    )(weights[:, None], x)
    return out[:M]


ROWS = 8          # receivers per block: the second-minor block dim must be a
#                  multiple of 8 (or the whole axis) to compile for TPU
BLOCK_N_NODES = 8192  # (K, ROWS, BN) fp32 block: 1.5 MiB at K = 6, so the
#                  double-buffered operands sit well inside scoped VMEM


def _kernel_nodes(w_ref, x_ref, o_ref):
    # x_ref: (K, R, BN) slot-major operands; w_ref: (R, K); o_ref: (R, BN)
    w = w_ref[...].astype(jnp.float32)
    acc = None
    for s in range(x_ref.shape[0]):
        term = x_ref[s].astype(jnp.float32) * w[:, s:s + 1]
        acc = term if acc is None else acc + term
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def gossip_mix_nodes(slots, weights, *, interpret: bool = False,
                     block_n: int = BLOCK_N_NODES):
    """Node-batched fused gossip merge — ``apply_W``'s sparse form on TPU.

    slots: (K, N, M) — slot s holds, for each of N receivers, its s-th
    operand row (slot 0 = self, then the D = K - 1 neighbors); weights:
    (N, K) -> (N, M), out[n] = sum_s weights[n, s] * slots[s, n].
    Slot-major so the tiled (N, M) dims carry no padding (a node-major
    (N, K, M) stack pads K up to 8 sublanes on TPU).  Grid (N/ROWS, M/BN):
    each program fuses ROWS receivers' K-way weighted sums over one
    parameter block, reading every operand once from HBM.  A dimension
    shorter than its block is taken whole.  Ragged edges are partial
    blocks, not padded copies: the op is columnwise, so the out-of-range
    lanes and rows of an edge block only feed outputs that are dropped.
    """
    K, N, M = slots.shape
    bn, rows = min(block_n, M), min(ROWS, N)
    return pl.pallas_call(
        _kernel_nodes,
        grid=(pl.cdiv(N, rows), pl.cdiv(M, bn)),
        in_specs=[
            pl.BlockSpec((rows, K), lambda b, i: (b, 0)),
            pl.BlockSpec((K, rows, bn), lambda b, i: (0, b, i)),
        ],
        out_specs=pl.BlockSpec((rows, bn), lambda b, i: (b, i)),
        out_shape=jax.ShapeDtypeStruct((N, M), slots.dtype),
        interpret=interpret,
        name="gossip_mix_nodes",
    )(weights, slots)
