"""Jitted public wrappers around the Pallas kernels.

Every wrapper takes ``interpret=None``, which means: compiled on the TPU
backend, and run by the Pallas interpreter (bit-faithful to the TPU
program, for CPU tests) on any other backend.  A caller may force either
mode by passing a bool.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_ops
from repro.kernels.gossip_mix import gossip_mix as _gossip_mix
from repro.kernels.gossip_mix import gossip_mix_nodes as _gossip_mix_nodes
from repro.kernels.quantize import dequantize as _dequantize
from repro.kernels.quantize import quantize as _quantize
from repro.kernels.scatter_gossip import payload_mix_nodes as _payload_mix_nodes
from repro.kernels.secure_mask import secure_mask_apply as _secure_mask_apply
from repro.kernels.secure_mask import secure_mask_apply_nodes as _secure_mask_apply_nodes
from repro.kernels.secure_mask import (
    secure_mask_apply_pairs_keyed as _secure_mask_apply_pairs_keyed,
)
from repro.kernels.sparsify import abs_histogram as _abs_histogram
from repro.kernels.sparsify import abs_histogram_rows as _abs_histogram_rows
from repro.kernels.sparsify import threshold_mask as _threshold_mask
from repro.kernels.sparsify import topk_threshold as _topk_threshold
from repro.kernels.sparsify import topk_threshold_rows as _topk_threshold_rows
from repro.kernels.ssd_chunk import ssd_chunk as _ssd_chunk
from repro.kernels.swa_attention import swa_attention as _swa_attention


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def gossip_mix(neighbors, weights, interpret: bool = None):
    return _gossip_mix(neighbors, weights,
                       interpret=_interpret(interpret))


def quantize(x, noise=None, interpret: bool = None):
    return _quantize(x, noise, interpret=_interpret(interpret))


def dequantize(codes, scale, interpret: bool = None):
    return _dequantize(codes, scale,
                       interpret=_interpret(interpret))


def secure_mask_apply(x, bits, signs, bound: float = 1.0, interpret: bool = None):
    return _secure_mask_apply(x, bits, signs, bound,
                              interpret=_interpret(interpret))


def gossip_mix_nodes(slots, weights, interpret: bool = None):
    return _gossip_mix_nodes(slots, weights,
                             interpret=_interpret(interpret))


def secure_mask_apply_nodes(x, bits, signs, bound: float = 1.0, interpret: bool = None):
    return _secure_mask_apply_nodes(x, bits, signs, bound,
                                    interpret=_interpret(interpret))


def secure_mask_apply_pairs_keyed(xs, keys, signs, bound: float = 1.0,
                                  interpret: bool = None):
    return _secure_mask_apply_pairs_keyed(
        xs, keys, signs, bound,
        interpret=_interpret(interpret))


def payload_mix_nodes(x, idx, val, w, interpret: bool = None):
    return _payload_mix_nodes(x, idx, val, w,
                              interpret=_interpret(interpret))


def abs_histogram(x, edges, interpret: bool = None):
    return _abs_histogram(x, edges,
                          interpret=_interpret(interpret))


def abs_histogram_rows(x, edges, interpret: bool = None):
    return _abs_histogram_rows(x, edges,
                               interpret=_interpret(interpret))


def topk_threshold_rows(x, k: int, interpret: bool = None):
    """Per-row histogram top-k threshold (N,) for x (N, P)."""
    return _topk_threshold_rows(x, k,
                                interpret=_interpret(interpret))


def threshold_mask(x, threshold, interpret: bool = None):
    return _threshold_mask(x, threshold,
                           interpret=_interpret(interpret))


def topk_mask_approx(x, k: int, interpret: bool = None):
    """Histogram-threshold approximate top-k: (values, mask, threshold)."""
    it = _interpret(interpret)
    t, _, _ = _topk_threshold(x, k, interpret=it)
    vals, mask = _threshold_mask(x, t, interpret=it)
    return vals, mask, t


def ssd_chunk(xdt, Bc, Cc, cum, interpret: bool = None):
    return _ssd_chunk(xdt, Bc, Cc, cum,
                      interpret=_interpret(interpret))


def swa_attention(q, k, v, window: int, interpret: bool = None):
    return _swa_attention(q, k, v, window,
                          interpret=_interpret(interpret))


def ssd_scan(xdt, Bc, Cc, cum, interpret: bool = None):
    """Full SSD over chunks using the Pallas intra-chunk kernel + the jnp
    inter-chunk recurrence.  Mirrors ssm.ssm_apply's core.

    xdt: (B, nc, L, H, P); Bc/Cc: (B, nc, L, N); cum: (B, nc, L, H).
    Returns y (B, nc, L, H, P)."""
    B, nc, L, H, P = xdt.shape
    N = Bc.shape[-1]
    g = lambda t: t.reshape(B * nc, *t.shape[2:])
    y_intra, states, dec = ssd_chunk(g(xdt), g(Bc), g(Cc), g(cum), interpret=interpret)
    y_intra = y_intra.reshape(B, nc, L, H, P)
    states = states.reshape(B, nc, H, N, P)
    dec = dec.reshape(B, nc, H)

    def scan_fn(h_prev, inp):
        s_c, d = inp
        return h_prev * d[..., None, None] + s_c, h_prev

    h0 = jnp.zeros((B, H, N, P), jnp.float32)
    _, h_before = jax.lax.scan(
        scan_fn, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(dec, 1, 0))
    )
    h_before = jnp.moveaxis(h_before, 0, 1)  # (B, nc, H, N, P)
    y_inter = jnp.einsum("bcin,bchnp->bcihp", Cc.astype(jnp.float32), h_before) * jnp.exp(
        cum
    )[..., None]
    return y_intra + y_inter
