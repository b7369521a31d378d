"""Pallas TPU kernels for top-k sparsification (sharing-module hot path).

TPU has no fast global sort, so top-k over a multi-million-element
parameter vector is done the TPU-idiomatic way:

  1. ``abs_histogram`` — one HBM pass accumulating a histogram of |x| over
     log-spaced bins (VMEM accumulator, sequential grid);
  2. host/XLA picks the threshold bin so ~k elements survive;
  3. ``threshold_mask`` — one more pass emitting masked values + bool mask.

Both kernels are memory-bound single-pass; the exact-top-k oracle
(lax.top_k) is the test reference: the approximate mask must contain every
element strictly above the chosen bin edge and select k within one bin's
population.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 65536
NBINS = 128


def _hist_kernel(x_ref, edges_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = jnp.abs(x_ref[...].astype(jnp.float32))  # (BLOCK,)
    edges = edges_ref[...].astype(jnp.float32)   # (E,)
    # bucket index = #edges <= a  (same as searchsorted right)
    idx = jnp.sum(a[:, None] >= edges[None, :], axis=1)  # (BLOCK,) in [0, E]
    onehot = idx[:, None] == jnp.arange(edges.shape[0] + 1)[None, :]
    o_ref[...] += jnp.sum(onehot, axis=0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def abs_histogram(x, edges, *, interpret: bool = False, block: int = BLOCK):
    """x: (M,), edges: (E,) ascending -> (E+1,) int32 counts (pad-aware)."""
    M = x.shape[0]
    pad = (-M) % block
    # pad with +inf so padding lands in the last (overflow) bucket; we
    # subtract it afterwards.
    xp = jnp.pad(x.astype(jnp.float32), (0, pad), constant_values=jnp.inf)
    grid = (xp.shape[0] // block,)
    E = edges.shape[0]
    hist = pl.pallas_call(
        _hist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((E,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((E + 1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((E + 1,), jnp.int32),
        interpret=interpret,
    )(xp, edges)
    return hist - jnp.zeros_like(hist).at[E].set(pad)


def _mask_kernel(x_ref, t_ref, v_ref, m_ref):
    x = x_ref[...]
    t = t_ref[0]
    keep = jnp.abs(x.astype(jnp.float32)) >= t
    v_ref[...] = jnp.where(keep, x, jnp.zeros_like(x))
    m_ref[...] = keep


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def threshold_mask(x, threshold, *, interpret: bool = False, block: int = BLOCK):
    """x: (M,) -> (masked values (M,), mask bool (M,))."""
    M = x.shape[0]
    pad = (-M) % block
    xp = jnp.pad(x, (0, pad))
    grid = (xp.shape[0] // block,)
    vals, mask = pl.pallas_call(
        _mask_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xp.shape[0],), x.dtype),
            jax.ShapeDtypeStruct((xp.shape[0],), jnp.bool_),
        ],
        interpret=interpret,
    )(xp, jnp.asarray(threshold, jnp.float32)[None])
    return vals[:M], mask[:M]


ROWS = 8           # rows per block: the (8, 128) tiling rule
BLOCK_ROWS = 2048  # lanes per block: each row's (E, BN) compare is 1 MiB
#                    of VMEM at E = 128


def _survival_rows_kernel(x_ref, e_ref, o_ref, *, block: int, total: int):
    """Accumulate c[r, i] = #{p : |x[r, p]| >= edges[r, i]} over the
    column blocks of a row block.  Edges arrive as (R, E, 1) columns, so
    each row's compare is an (E, BN) tile reduced along lanes; lanes past
    ``total`` (a ragged edge block) are masked below every edge."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = jnp.abs(x_ref[...].astype(jnp.float32))   # (R, BN)
    col = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) + j * block
    a = jnp.where(col < total, a, -1.0)
    for r in range(a.shape[0]):
        ge = a[r:r + 1, :] >= e_ref[r].astype(jnp.float32)   # (E, BN)
        o_ref[r] += jnp.sum(ge.astype(jnp.int32), axis=1, keepdims=True)


def _survival_rows(x, edges, interpret: bool, block: int = BLOCK_ROWS):
    """(N, E) int32 survival counts #{|x[n]| >= edges[n, i]}: one pass
    over x (N, P) with per-row edges (N, E)."""
    N, P = x.shape
    E = edges.shape[1]
    b, rows = min(block, P), min(ROWS, N)
    c = pl.pallas_call(
        functools.partial(_survival_rows_kernel, block=b, total=P),
        grid=(pl.cdiv(N, rows), pl.cdiv(P, b)),
        in_specs=[
            pl.BlockSpec((rows, b), lambda n, j: (n, j)),
            pl.BlockSpec((rows, E, 1), lambda n, j: (n, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, E, 1), lambda n, j: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, E, 1), jnp.int32),
        interpret=interpret,
        name="abs_survival_rows",
    )(x, edges[:, :, None])
    return c[:, :, 0]


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def abs_histogram_rows(x, edges, *, interpret: bool = False,
                       block: int = BLOCK_ROWS):
    """Row-batched |x| histogram: x (N, P), edges (N, E) per-row ascending
    -> (N, E+1) int32 counts, bin i = #{|x| in [edges[i-1], edges[i])}.
    Grid (N/ROWS, P/B): the sharing module's per-node threshold pick is
    one kernel launch instead of N.  Built from the survival counts: with
    ascending edges, bin i holds c[i-1] - c[i]."""
    c = _survival_rows(x, edges, interpret, block)
    P = x.shape[1]
    return jnp.concatenate([P - c[:, :1], c[:, :-1] - c[:, 1:], c[:, -1:]],
                           axis=1)


def _pick_edge_rows(a, k, edges, interpret):
    """Per-row largest edge with #{|x| >= edge} >= k, and the next edge up.
    a: (N, P) magnitudes, edges: (N, E)."""
    nbins = edges.shape[1]
    ok = _survival_rows(a, edges, interpret) >= k               # (N, E)
    any_ok = ok.any(axis=1)
    pos = (jnp.arange(nbins)[None, :] * ok).argmax(axis=1)       # (N,)
    t = jnp.where(
        any_ok, jnp.take_along_axis(edges, pos[:, None], axis=1)[:, 0], 0.0
    )
    hi_pos = jnp.minimum(pos + 1, nbins - 1)
    t_hi = jnp.take_along_axis(edges, hi_pos[:, None], axis=1)[:, 0]
    return t, t_hi


def log_edges_rows(a, nbins: int = NBINS):
    """(N, nbins) log-spaced per-row edges spanning [1e-7, 1] x max|a[n]|:
    the coarse bins of :func:`topk_threshold_rows`.  a: (N, P) magnitudes."""
    hi = jnp.max(a, axis=1)
    lo = jnp.maximum(hi * 1e-7, 1e-30)
    span = jnp.linspace(0.0, 1.0, nbins)[None, :]
    return jnp.exp(
        jnp.log(lo)[:, None] * (1.0 - span) + jnp.log(jnp.maximum(hi, 1e-30))[:, None] * span
    )


def topk_threshold_rows(x, k: int, nbins: int = NBINS, interpret: bool = False):
    """Per-row histogram top-k threshold: x (N, P) -> t (N,) float32 with
    #{|x[n]| >= t[n]} >= k, within one *fine* bin of exactly k.  The
    row-batched form of :func:`topk_threshold` (same coarse-log + linear
    refinement discipline), one pass over x per histogram instead of a
    per-row sort — the sharing module's hot-path selector on TPU."""
    a = jnp.abs(x.astype(jnp.float32))
    span = jnp.linspace(0.0, 1.0, nbins)[None, :]
    t0, t0_hi = _pick_edge_rows(a, k, log_edges_rows(a, nbins), interpret)
    fine = t0[:, None] * (1.0 - span) + jnp.maximum(t0_hi, t0 + 1e-30)[:, None] * span
    t1, _ = _pick_edge_rows(a, k, fine, interpret)
    return jnp.maximum(t0, t1)


def _pick_edge(x, k, edges, interpret):
    """Largest edge with #{|x| >= edge} >= k, and the next edge above it."""
    nbins = edges.shape[0]
    hist = abs_histogram(x, edges, interpret=interpret)
    tail = jnp.cumsum(hist[::-1])[::-1]  # tail[i] = # >= edges[i-1]
    surv = tail[1:]  # surv[i] = #{a >= edges[i]}
    ok = surv >= k
    idx = jnp.where(ok.any(), (jnp.arange(nbins) * ok).argmax(), 0)
    t = jnp.where(ok.any(), edges[idx], 0.0)
    t_hi = edges[jnp.minimum(idx + 1, nbins - 1)]
    return t, t_hi, hist


def topk_threshold(x, k: int, nbins: int = NBINS, interpret: bool = False):
    """Histogram-based threshold t s.t. #{|x| >= t} ~ k (>= k, within one
    *fine* bin).  Two passes: coarse log bins bracket the threshold, then a
    linear re-binning inside the bracketing bin refines it (the log tail is
    too coarse for small k otherwise).  Returns (threshold, hist, edges)."""
    a = jnp.abs(x.astype(jnp.float32))
    hi = jnp.max(a)
    lo = jnp.maximum(hi * 1e-7, 1e-30)
    edges = jnp.exp(jnp.linspace(jnp.log(lo), jnp.log(hi), nbins))
    t0, t0_hi, hist = _pick_edge(x, k, edges, interpret)
    # refinement: linear bins across the bracketing interval [t0, t0_hi]
    fine = jnp.linspace(t0, jnp.maximum(t0_hi, t0 + 1e-30), nbins)
    t1, _, _ = _pick_edge(x, k, fine, interpret)
    t = jnp.maximum(t0, t1)
    return t, hist, edges
