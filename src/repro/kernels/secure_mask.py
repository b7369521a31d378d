"""Pallas TPU kernels: fused secure-aggregation mask apply.

A sender adds one cancellable mask per co-neighbor pair before the message
leaves the chip: out = x + sum_k sign_k * U(bits_k), U mapping uint32 PRF
bits to uniform [-b, b).  Fusing the K mask materializations + adds into
one pass avoids K HBM round-trips of the full parameter vector.

Two bit sources:

* ``secure_mask_apply`` / ``secure_mask_apply_nodes`` — bits produced
  outside (threefry) and staged as (…, K, M) uint32 tensors: simple, but
  the caller pays O(B·K·M) HBM for the bit stacks.
* ``secure_mask_apply_nodes_keyed`` — the fused form: the caller passes
  only the (B, K, 2) uint32 *pair keys* and the kernel runs the
  Threefry-2x32 counter expansion in-body per block, bit-identical to
  ``jax.random.bits(key, (M,))`` (asserted against
  ``kernels.ref.counter_bits_ref``).  Peak staging for a secure round
  drops from O(N·d·P) bits to O(N·d) keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 65536


def _kernel(bound_ref, x_ref, bits_ref, signs_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (BN,)
    bits = bits_ref[...]                        # (K, BN) uint32
    signs = signs_ref[...].astype(jnp.float32)  # (K, 1)
    bound = bound_ref[0]
    u01 = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    masks = (u01 * 2.0 - 1.0) * bound
    o_ref[...] = (x + jnp.sum(masks * signs, axis=0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def secure_mask_apply(x, bits, signs, bound: float = 1.0, *,
                      interpret: bool = False, block_n: int = BLOCK_N):
    """x: (M,); bits: (K, M) uint32; signs: (K,) ±1 -> masked x (M,)."""
    K, M = bits.shape
    pad = (-M) % block_n
    xp = jnp.pad(x, (0, pad))
    bp = jnp.pad(bits, ((0, 0), (0, pad)))
    grid = (xp.shape[0] // block_n,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((K, block_n), lambda i: (0, i)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0],), x.dtype),
        interpret=interpret,
    )(jnp.asarray(bound, jnp.float32)[None], xp, bp, signs[:, None])
    return out[:M]


def _kernel_nodes(bound_ref, x_ref, bits_ref, signs_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (1, BN)
    bits = bits_ref[...]                        # (1, K, BN) uint32
    signs = signs_ref[...].astype(jnp.float32)  # (1, K, 1)
    bound = bound_ref[0]
    u01 = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    masks = (u01 * 2.0 - 1.0) * bound * signs     # (1, K, BN)
    # slot-by-slot sum, in the keyed kernel's order, so the two agree
    # bit-for-bit
    tot = masks[:, 0]
    for s in range(1, masks.shape[1]):
        tot = tot + masks[:, s]
    o_ref[...] = (x + tot).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def secure_mask_apply_nodes(x, bits, signs, bound: float = 1.0, *,
                            interpret: bool = False, block_n: int = BLOCK_N):
    """Message-batched fused mask apply — one call masks every message of a
    secure-aggregation round.

    x: (B, M) messages; bits: (B, K, M) uint32 per-pair PRF bits; signs:
    (B, K) in {-1, 0, +1} (0 = inactive pair slot) -> (B, M).  Grid
    (B, M/BN); the block adapts down to the (128-aligned) vector length.
    """
    B, K, M = bits.shape
    bn = min(block_n, -(-M // 128) * 128)
    pad = (-M) % bn
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    bp = jnp.pad(bits, ((0, 0), (0, 0), (0, pad)))
    grid = (B, xp.shape[1] // bn)
    out = pl.pallas_call(
        _kernel_nodes,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, i: (0,)),
            pl.BlockSpec((1, bn), lambda b, i: (b, i)),
            pl.BlockSpec((1, K, bn), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, K, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda b, i: (b, i)),
        out_shape=jax.ShapeDtypeStruct((B, xp.shape[1]), x.dtype),
        interpret=interpret,
    )(jnp.asarray(bound, jnp.float32)[None], xp, bp, signs[:, :, None])
    return out[:, :M]


ROWS = 8               # messages per keyed block: the (8, 128) tiling rule
BLOCK_N_KEYED = 2048   # lanes per keyed block: the cipher's (ROWS, BN) uint32
#                        temporaries stay a few hundred KiB of VMEM


def _threefry2x32(k1, k2, x0, x1):
    """In-kernel Threefry-2x32: uint32 adds/rotates/xors only (VPU ops).
    Must stay bit-identical to kernels.ref.threefry2x32_ref."""
    def rotl(x, d):
        return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))

    ks2 = k1 ^ k2 ^ jnp.uint32(0x1BD11BDA)
    ks = (k1, k2, ks2)
    rots = ((13, 15, 26, 6), (17, 29, 16, 24))
    x0 = x0 + k1
    x1 = x1 + k2
    for i in range(5):
        for r in rots[i % 2]:
            x0 = x0 + x1
            x1 = rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _kernel_nodes_keyed(bound_ref, x_ref, k1_ref, k2_ref, signs_ref, o_ref, *,
                        block_n: int):
    """One (message-rows, param-block) program: expand each pair key's
    counter bits for this block's positions, map to uniform [-b, b), apply
    signed.

    Positional replication of jax's partitionable threefry expansion
    (see ``kernels.ref.counter_bits_ref``): position p of a draw is
    ``y0 ^ y1`` of the cipher on counter (0, p), so a block needs only
    its own positions.  Pair slots run as a static loop over 2-D
    (ROWS, BN) tiles, each slot's key words a (ROWS, 1) column.
    """
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)            # (R, BN)
    k1 = k1_ref[...]                              # (R, K) uint32
    k2 = k2_ref[...]
    signs = signs_ref[...].astype(jnp.float32)    # (R, K)
    bound = bound_ref[...]                        # (1, 1): a vector operand,
    #                                               no scalar load from VMEM
    q = jax.lax.convert_element_type(
        jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + j * block_n,
        jnp.uint32)                               # global positions
    zero = jnp.zeros_like(q)
    tot = None
    for s in range(k1.shape[1]):
        y0, y1 = _threefry2x32(k1[:, s:s + 1], k2[:, s:s + 1], zero, q)
        m = _uniform(y0 ^ y1, bound) * signs[:, s:s + 1]
        tot = m if tot is None else tot + m
    o_ref[...] = (x + tot).astype(o_ref.dtype)


def _uniform(bits, bound):
    """kernels.ref.mask_bits_to_uniform, in-kernel: the top 24 bits fit an
    int32, whose float conversion the TPU vector unit has."""
    u01 = jax.lax.convert_element_type(bits >> jnp.uint32(8), jnp.int32)
    return (u01.astype(jnp.float32) * (1.0 / (1 << 24)) * 2.0 - 1.0) * bound


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def secure_mask_apply_nodes_keyed(x, keys, signs, bound: float = 1.0, *,
                                  interpret: bool = False,
                                  block_n: int = BLOCK_N_KEYED):
    """Fused mask apply with in-kernel bit generation.

    x: (B, M) messages; keys: (B, K, 2) uint32 pair-PRF key words
    (``jax.random.key_data`` of the folded-in pair keys); signs: (B, K) in
    {-1, 0, +1} -> (B, M).  Equivalent to staging
    ``jax.random.bits(key, (M,))`` per pair and calling
    ``secure_mask_apply_nodes`` — without the (B, K, M) bit tensor.  Grid
    (B/ROWS, M/BN); ragged edges are partial blocks (the op is
    elementwise per position, so out-of-range lanes are dropped).
    """
    B, K, _ = keys.shape
    M = x.shape[1]
    bn, rows = min(block_n, M), min(ROWS, B)
    row_spec = pl.BlockSpec((rows, K), lambda b, i: (b, 0))
    return pl.pallas_call(
        functools.partial(_kernel_nodes_keyed, block_n=bn),
        grid=(pl.cdiv(B, rows), pl.cdiv(M, bn)),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i: (0, 0)),
            pl.BlockSpec((rows, bn), lambda b, i: (b, i)),
            row_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((rows, bn), lambda b, i: (b, i)),
        out_shape=jax.ShapeDtypeStruct((B, M), x.dtype),
        interpret=interpret,
        name="secure_mask_keyed",
    )(jnp.asarray(bound, jnp.float32).reshape(1, 1), x, keys[:, :, 0],
      keys[:, :, 1], signs)
