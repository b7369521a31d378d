"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
the shape/dtype sweep tests assert against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.secure_mask import slot_pairs


def gossip_mix_ref(neighbors, weights):
    """neighbors: (K, M) stacked neighbor shards; weights: (K,).
    out[m] = sum_k w_k * neighbors[k, m] (fp32 accumulate)."""
    return jnp.einsum(
        "k,km->m", weights.astype(jnp.float32), neighbors.astype(jnp.float32)
    ).astype(neighbors.dtype)


def abs_histogram_ref(x, edges):
    """Histogram of |x| over bins defined by ``edges`` (ascending, E,).
    Returns (E+1,) int32 counts; bin i = #{|x| in [edges[i-1], edges[i])}."""
    a = jnp.abs(x.astype(jnp.float32)).reshape(-1)
    idx = jnp.searchsorted(edges.astype(jnp.float32), a, side="right")
    return jnp.zeros((edges.shape[0] + 1,), jnp.int32).at[idx].add(1)


def threshold_mask_ref(x, threshold):
    """Values of |x| >= threshold kept, else 0; plus boolean mask."""
    m = jnp.abs(x.astype(jnp.float32)) >= threshold
    return jnp.where(m, x, jnp.zeros((), x.dtype)), m


def quantize_ref(x, noise=None):
    """Per-row symmetric int8; optional stochastic rounding with uniform
    noise in [0,1). x: (R, C) -> (codes int8, scale (R,1) fp32)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0, 1e-12)
    y = xf / scale
    y = jnp.round(y) if noise is None else jnp.floor(y + noise)
    return jnp.clip(y, -127, 127).astype(jnp.int8), scale


def dequantize_ref(codes, scale):
    return codes.astype(jnp.float32) * scale


def mask_bits_to_uniform(bits, bound):
    """uint32 random bits -> uniform float32 in [-bound, bound).
    Mapping: top 24 bits -> [0,1) with 2^-24 quantization (shared by the
    kernel and the oracle so they agree bit-exactly)."""
    u01 = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    return (u01 * 2.0 - 1.0) * bound


def secure_mask_apply_ref(x, bits, signs, bound):
    """x: (K, M) pair-lanes? No — x: (M,), bits: (K, M) one row per pair,
    signs: (K,) ±1. out = x + sum_k signs[k] * uniform(bits[k])."""
    masks = mask_bits_to_uniform(bits, bound)  # (K, M) fp32
    return (x.astype(jnp.float32) + jnp.einsum("k,km->m", signs.astype(jnp.float32), masks)).astype(x.dtype)


def threefry2x32_ref(k1, k2, x0, x1):
    """Elementwise Threefry-2x32 block cipher (the JAX PRNG core), pure jnp.

    k1/k2: uint32 key words (broadcastable against x0/x1); x0/x1: uint32
    counter words.  Returns (y0, y1).  This is the single definition the
    in-kernel bit generation (kernels/secure_mask) and its oracle share —
    it must stay bit-identical to ``jax.random.bits``'s cipher.
    """
    def rotl(x, d):
        return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))

    ks0 = k1
    ks1 = k2
    ks2 = k1 ^ k2 ^ jnp.uint32(0x1BD11BDA)
    ks = [ks0, ks1, ks2]
    rots = ((13, 15, 26, 6), (17, 29, 16, 24))
    x0 = x0 + ks0
    x1 = x1 + ks1
    for i in range(5):
        for r in rots[i % 2]:
            x0 = x0 + x1
            x1 = rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def counter_bits_ref(k1, k2, positions, total: int):
    """uint32 PRF bits at ``positions`` of a ``jax.random.bits(key, (total,))``
    draw, computed positionally (no (total,) materialization).

    Replicates jax's partitionable threefry expansion
    (``jax_threefry_partitionable=True``, the default since jax 0.5): the
    counter of flat position p is the 64-bit word p split into (hi, lo)
    uint32 halves, and the 32-bit output is the xor of the two cipher
    words.  For ``total < 2**32`` the hi word is 0, so position p needs
    only ``threefry(k, 0, p)`` — elementwise in ``positions``, so a kernel
    can generate exactly its block's bits.  Bit-identity is asserted in
    tests/test_kernels.py against jax.random.bits.
    """
    if int(total) >= 1 << 32:
        raise ValueError(f"counter_bits_ref: total={total} needs a hi counter word")
    q = positions.astype(jnp.uint32)
    y0, y1 = threefry2x32_ref(k1, k2, jnp.zeros_like(q), q)
    return y0 ^ y1


def secure_mask_apply_nodes_keyed_ref(x, keys, signs, bound):
    """x: (B, M); keys: (B, K, 2) uint32 pair-PRF keys; signs: (B, K).
    out[b] = x[b] + sum_k signs[b, k] * uniform(bits(keys[b, k])), the bits
    being jax.random.bits(key, (M,)) — generated here via counter_bits_ref
    so the fused kernel and jax.random agree bit-exactly."""
    B, K, _ = keys.shape
    M = x.shape[1]
    pos = jnp.arange(M, dtype=jnp.uint32)[None, None, :]
    bits = counter_bits_ref(keys[:, :, 0:1], keys[:, :, 1:2], pos, M)  # (B, K, M)
    masks = mask_bits_to_uniform(bits, bound)
    return (
        x.astype(jnp.float32)
        + jnp.einsum("bk,bkm->bm", signs.astype(jnp.float32), masks)
    ).astype(x.dtype)


def pairs_to_slots(keys, signs):
    """The pair layout of ``secure_mask_apply_pairs_keyed`` as per-message
    slots: keys (B, Q, 2) of the slot pairs in ``slot_pairs`` order and
    signs (B, D, D) -> keys (D, B, D, 2) and signs (D, B, D), message s's
    slot t holding pair {s, t}'s key and signs[:, s, t], and its own slot
    s a zero-signed placeholder key."""
    D = signs.shape[1]
    lo, hi = slot_pairs(D)
    pair = np.zeros((D, D), np.int32)
    pair[lo, hi] = pair[hi, lo] = np.arange(len(lo))
    own = jnp.asarray(1.0 - np.eye(D), signs.dtype)
    return jnp.moveaxis(keys[:, pair], 1, 0), jnp.moveaxis(signs * own, 1, 0)


def secure_mask_apply_pairs_keyed_ref(xs, keys, signs, bound):
    """xs: (D, B, M); keys: (B, Q, 2); signs: (B, D, D) -> (D, B, M): each
    message masked by ``secure_mask_apply_nodes_keyed_ref`` over its slots
    as ``pairs_to_slots`` lays them out."""
    D = xs.shape[0]
    if D == 1:
        return xs
    k, sg = pairs_to_slots(keys, signs)
    return jnp.stack([secure_mask_apply_nodes_keyed_ref(xs[s], k[s], sg[s], bound)
                      for s in range(D)])


def payload_mix_nodes_ref(x, idx, val, w):
    """Payload-indexed gossip merge oracle (missing-coordinate rule).

    x: (N, P); idx: (N, K, k) int32; val: (N, K, k) fp32; w: (N, K).
    out[n] = x[n] + sum_{K,k} w[n, K] * scatter(idx[n, K], val - x[n][idx])
    — each operand slot contributes only its payload coordinates, missing
    coordinates fall back to the receiver's own value.  fp32 accumulate.
    """
    n, K, k = idx.shape
    xf = x.astype(jnp.float32)
    fid = idx.reshape(n, K * k)
    own = jnp.take_along_axis(xf, fid, axis=1)                    # (N, K*k)
    contrib = (val.astype(jnp.float32).reshape(n, K * k) - own) * jnp.repeat(
        w.astype(jnp.float32), k, axis=1
    )
    delta = jnp.zeros_like(xf).at[jnp.arange(n)[:, None], fid].add(contrib)
    return (xf + delta).astype(x.dtype)


def abs_histogram_rows_ref(x, edges):
    """Row-batched abs_histogram_ref: x (N, P), edges (N, E) per-row
    ascending -> (N, E+1) int32 counts."""
    a = jnp.abs(x.astype(jnp.float32))
    idx = jnp.sum(a[:, :, None] >= edges.astype(jnp.float32)[:, None, :], axis=2)
    E = edges.shape[1]
    onehot = idx[:, :, None] == jnp.arange(E + 1)[None, None, :]
    return jnp.sum(onehot, axis=1).astype(jnp.int32)


def gossip_mix_nodes_ref(neighbors, weights):
    """neighbors: (N, K, M); weights: (N, K).  Per-receiver fused merge:
    out[n, m] = sum_k w[n, k] * neighbors[n, k, m] (fp32 accumulate)."""
    return jnp.einsum(
        "nk,nkm->nm", weights.astype(jnp.float32), neighbors.astype(jnp.float32)
    ).astype(neighbors.dtype)


def secure_mask_apply_nodes_ref(x, bits, signs, bound):
    """x: (B, M); bits: (B, K, M); signs: (B, K) in {-1, 0, +1}.
    out[b] = x[b] + sum_k signs[b, k] * uniform(bits[b, k])."""
    masks = mask_bits_to_uniform(bits, bound)  # (B, K, M) fp32
    return (
        x.astype(jnp.float32)
        + jnp.einsum("bk,bkm->bm", signs.astype(jnp.float32), masks)
    ).astype(x.dtype)


def ssd_chunk_ref(xdt, Bc, Cc, cum):
    """One SSD chunk (single batch element).

    xdt: (L, H, P) fp32 (x * dt), Bc/Cc: (L, N), cum: (L, H) cumsum(dt*A).
    Returns (y_intra (L, H, P), state (H, N, P), decay_out (H,)):
      y_intra[i] = sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) xdt_j
      state      = sum_j exp(cum_L - cum_j) B_j (x) xdt_j
      decay_out  = exp(cum_L)   (total chunk decay for the recurrence)
    """
    L = xdt.shape[0]
    diff = cum[:, None, :] - cum[None, :, :]  # (L, L, H)
    tri = jnp.tril(jnp.ones((L, L), bool))
    Ldec = jnp.where(tri[:, :, None], jnp.exp(diff), 0.0)
    cb = jnp.einsum("in,jn->ij", Cc.astype(jnp.float32), Bc.astype(jnp.float32))
    y = jnp.einsum("ijh,jhp->ihp", cb[:, :, None] * Ldec, xdt.astype(jnp.float32))
    decay_to_end = jnp.exp(cum[-1:, :] - cum)  # (L, H)
    state = jnp.einsum("jn,jhp->hnp", Bc.astype(jnp.float32),
                       xdt.astype(jnp.float32) * decay_to_end[:, :, None])
    return y, state, jnp.exp(cum[-1])


def swa_attention_ref(q, k, v, window: int):
    """Sliding-window causal attention, single head batch-merged.
    q,k,v: (S, D). Query i attends keys (i-window, i]."""
    S = q.shape[0]
    scores = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * (q.shape[-1] ** -0.5)
    qi = jnp.arange(S)[:, None]
    kj = jnp.arange(S)[None, :]
    mask = (kj <= qi) & (kj > qi - window)
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return (w @ v.astype(jnp.float32)).astype(q.dtype)
