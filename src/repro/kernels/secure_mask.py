"""Pallas TPU kernels: fused secure-aggregation mask apply.

A sender adds one cancellable mask per co-neighbor pair before the message
leaves the chip: out = x + sum_k sign_k * U(bits_k), U mapping uint32 PRF
bits to uniform [-b, b).  Fusing the K mask materializations + adds into
one pass avoids K HBM round-trips of the full parameter vector.

Two bit sources:

* ``secure_mask_apply`` / ``secure_mask_apply_nodes`` — bits produced
  outside (threefry) and staged as (…, K, M) uint32 tensors: simple, but
  the caller pays O(B·K·M) HBM for the bit stacks.
* ``secure_mask_apply_pairs_keyed`` — the fused form: the caller passes
  only the (B, Q, 2) uint32 *pair keys* of each receiver's Q = d(d-1)/2
  co-neighbor pairs, in ``slot_pairs`` order, and the kernel runs the
  Threefry-2x32 counter expansion in-body per block, bit-identical to
  ``jax.random.bits(key, (M,))`` (asserted against
  ``kernels.ref.counter_bits_ref``).  Receiver-major: pair {i, j}'s mask
  at receiver r rides in message i->r with one sign and in j->r with the
  other, so it is expanded once and added to both — N·Q·P cipher words a
  round, not N·d²·P.  Peak staging for a secure round drops from
  O(N·d·P) bits to O(N·Q) keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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


ROWS = 8                # receivers per keyed block: the (8, 128) tiling rule
BLOCK_SUB = 1024        # lanes per inner step of the keyed kernel: the
#                         fastest of 256 to 8192 on a TPU v5e (PERF.md)
BLOCK_BYTES = 1 << 20   # one (D, ROWS, bn) float32 message block of the keyed
#                         kernel; in and out, double-buffered, take 4x in VMEM


def slot_pairs(D: int):
    """The Q = D(D-1)/2 slot pairs s < t of a receiver, in lexicographic
    order — the order of ``secure_mask_apply_pairs_keyed``'s pair keys —
    as two (Q,) index arrays (lo, hi)."""
    return np.triu_indices(D, 1)


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


def _kernel_pairs_keyed(bound_ref, x_ref, k1_ref, k2_ref, clo_ref, chi_ref,
                        o_ref, *, block_n: int, sub: int):
    """One (receiver-rows, param-block) program over all D messages of its
    receivers: expand each slot pair's key once per position, map to
    uniform [-b, b), add it with c_lo to the lower slot's message and with
    c_hi to the higher slot's.

    Positional replication of jax's partitionable threefry expansion
    (see ``kernels.ref.counter_bits_ref``): position p of a draw is
    ``y0 ^ y1`` of the cipher on counter (0, p), so a block needs only
    its own positions.  Pairs run in lexicographic order, so message s
    receives its terms in ascending co-neighbor slot order, each key
    word a (ROWS, 1) column; an inner loop walks the block in (ROWS, sub)
    lane tiles.
    """
    D = x_ref.shape[0]
    pairs = list(zip(*slot_pairs(D)))
    k1 = k1_ref[...]                              # (R, Q) uint32
    k2 = k2_ref[...]
    c_lo = clo_ref[...].astype(jnp.float32)       # (R, Q)
    c_hi = chi_ref[...].astype(jnp.float32)
    bound = bound_ref[...]                        # (1, 1): a vector operand,
    #                                               no scalar load from VMEM
    lanes = jax.lax.broadcasted_iota(jnp.int32, (x_ref.shape[1], sub), 1)
    base = pl.program_id(1) * block_n

    def tile(c, carry):
        off = pl.multiple_of(c * sub, sub)
        q = jax.lax.convert_element_type(
            lanes + (base + off), jnp.uint32)     # global positions
        zero = jnp.zeros_like(q)
        tot = [None] * D
        for p, (s, t) in enumerate(pairs):
            y0, y1 = _threefry2x32(k1[:, p:p + 1], k2[:, p:p + 1], zero, q)
            m = _uniform(y0 ^ y1, bound)
            for slot, coef in ((s, c_lo), (t, c_hi)):
                term = m * coef[:, p:p + 1]
                tot[slot] = term if tot[slot] is None else tot[slot] + term
        for s in range(D):
            x = x_ref[s, :, pl.ds(off, sub)].astype(jnp.float32)
            o_ref[s, :, pl.ds(off, sub)] = (x + tot[s]).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_n // sub, tile, 0)


def _uniform(bits, bound):
    """kernels.ref.mask_bits_to_uniform, in-kernel: the top 24 bits fit an
    int32, whose float conversion the TPU vector unit has."""
    u01 = jax.lax.convert_element_type(bits >> jnp.uint32(8), jnp.int32)
    return (u01.astype(jnp.float32) * (1.0 / (1 << 24)) * 2.0 - 1.0) * bound


@functools.partial(jax.jit, static_argnames=("interpret",))
def secure_mask_apply_pairs_keyed(xs, keys, signs, bound: float = 1.0, *,
                                  interpret: bool = False):
    """Mask every message of a secure-aggregation round, each pair mask
    expanded once.

    xs: (D, B, M) slot-major messages (slot s of receiver b is the message
    its s-th neighbor sends it); keys: (B, Q, 2) uint32 pair-PRF key words
    (``jax.random.key_data``) of the Q = D(D-1)/2 slot pairs in
    ``slot_pairs`` order; signs: (B, D, D), signs[b, s, t] the coefficient
    of pair {s, t}'s mask in message s (the diagonal is not read).  Returns
    (D, B, M) with out[s] = xs[s] + sum over co-slots t (ascending) of
    signs[:, s, t] * U(bits(key)), the bits being ``jax.random.bits(key,
    (M,))``.  Equal, bit for bit, to ``secure_mask_apply_nodes`` fed each
    message's staged bits with a zero-signed own slot
    (``kernels.ref.pairs_to_slots``).  D = 1 has no pairs: xs comes back
    unmasked.  Grid (B/ROWS, M/bn), bn sized from D so a message block
    holds ``BLOCK_BYTES``; ragged edges are partial blocks (the op is
    elementwise per position, so out-of-range lanes are dropped).
    """
    D, B, M = xs.shape
    Q = keys.shape[1]
    if Q != D * (D - 1) // 2:
        raise ValueError(f"{Q} pair keys for {D} slots; expected {D * (D - 1) // 2}")
    if Q == 0:
        return xs
    lo, hi = slot_pairs(D)
    bn = min(max(BLOCK_SUB, BLOCK_BYTES // (4 * ROWS * D) // BLOCK_SUB * BLOCK_SUB), M)
    rows = min(ROWS, B)
    sub = BLOCK_SUB if bn % BLOCK_SUB == 0 else bn
    msg_spec = pl.BlockSpec((D, rows, bn), lambda b, i: (0, b, i))
    row_spec = pl.BlockSpec((rows, Q), lambda b, i: (b, 0))
    return pl.pallas_call(
        functools.partial(_kernel_pairs_keyed, block_n=bn, sub=sub),
        grid=(pl.cdiv(B, rows), pl.cdiv(M, bn)),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i: (0, 0)),
            msg_spec, row_spec, row_spec, row_spec, row_spec,
        ],
        out_specs=msg_spec,
        out_shape=jax.ShapeDtypeStruct(xs.shape, xs.dtype),
        interpret=interpret,
        name="secure_mask_keyed",
    )(jnp.asarray(bound, jnp.float32).reshape(1, 1), xs, keys[:, :, 0],
      keys[:, :, 1], signs[:, lo, hi], signs[:, hi, lo])
