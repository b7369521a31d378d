"""Communication/aggregation strategies — the gossip "wire".

Four interchangeable lowerings of the same math
x_i' = sum_j W_ij x_j  (W = Metropolis-Hastings weights of the overlay):

* ``mix_dense``      — W @ X einsum; W is a *traced* argument, so dynamic
                       per-round topologies never recompile.  Lowers to
                       all-gather + local matmul under GSPMD.  Works for any
                       graph (the paper's ZeroMQ generality); O(N²·P).
* ``mix_sparse``     — neighbor-indexed gather + weighted segment sum over
                       a ``SparseTopology``'s padded (N, D) tables:
                       O(N·D·P) FLOPs, the execution form for sparse graphs
                       (d ≪ N).  On TPU the fused K-way merge runs in
                       the ``kernels/gossip_mix`` Pallas kernel (XLA
                       gather + einsum on other backends).  This is
                       also the neighbor-indexed form multi-host
                       `collective_permute` gossip shards over.
* ``mix_circulant``  — static circulant d-regular graphs; neighbor exchange
                       by index shift.  ``roll`` variant works everywhere
                       (CPU emulation); ``shard_map`` variant lowers each
                       offset to one `collective_permute` on the TPU mesh —
                       the TPU-native analogue of point-to-point sends.
* ``mix_fully``      — fully-connected topology = plain mean (all-reduce).
* ``mix_sparse_shmap`` — node-sharded ``mix_sparse``: the table is
                       slot-rebalanced into permutation columns and each
                       slot becomes rotation-grouped `collective_permute`s
                       (gather fallback otherwise) — the multi-device
                       generalization of ``mix_circulant_shmap`` the
                       sharded RoundEngine builds on (see the
                       ShardedTopology/ShardedDense section below).

All operate on node-stacked pytrees (leading axis N).  ``apply_W`` is the
strategy-facing primitive: one W @ Y that accepts either a dense (N, N)
matrix or a ``SparseTopology`` so every sharing strategy supports both.

``mix_payload`` is the *compressed* wire primitive: sparsified sharing
strategies hand it per-node (idx, val) payloads instead of masked (N, P)
matrices and it applies the missing-coordinate rule in one gather +
scatter-accumulate pass — O(N·d·k) compute and, on the sharded ppermute
backend, O(D·B·k) wire.  ``mix_payload_masked`` is its dense-mask oracle.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.topology import (
    Graph,
    SparseTopology,
    build_permute_schedule,
    circulant_offsets,
    decompose_slot_permutations,
    sample_neighbor_slots,
)

# Mixing products in fp32 on every backend.  TPU's default precision for an
# fp32 contraction rounds its operands to bf16, and bf16 Metropolis-Hastings
# weights no longer sum to 1, so the consensus average would drift.
F32 = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# node-sharded gossip: the distributed backends of mix_sparse / apply_W
# ---------------------------------------------------------------------------
#
# Inside a `shard_map` body the node axis is block-sharded: each device holds
# B = N/ndev consecutive node rows of every node-stacked tensor.  The two
# wrapper types below are what strategy code sees in place of the dense W /
# SparseTopology mixing operand — `apply_W` dispatches on them, so every
# sharing strategy (full, randk, topk, choco, secure) runs distributed
# without code changes:
#
# * ``ShardedTopology`` — local (B, D) neighbor tables plus, when the table
#   decomposes into per-slot permutations (topology.decompose_slot_
#   permutations), a static `PermuteSchedule`: slot s's permutation column is
#   applied as a handful of rotation-grouped `collective_permute`s carrying
#   only the rows that cross devices — O(D·B·P) wire per mix instead of
#   all-gather's O(N·P) (with one node per device this is literally one
#   ppermute per slot, the generalization of mix_circulant_shmap to
#   arbitrary sparse graphs).  Tables that don't decompose (or per-round
#   dynamic tables, whose schedule can't be static) fall back to
#   all-gather + local neighbor gather — bit-identical to the single-device
#   path because each row's arithmetic is unchanged.
# * ``ShardedDense`` — local (B, N) W rows; all-gather + local matmul.


@dataclasses.dataclass(eq=False, frozen=True)
class NodeShard:
    """Static description of the node-axis sharding inside a shard_map body.

    axis: mesh axis name (or tuple of names) forming the node dimension;
    sizes: matching mesh axis sizes; block: rows per device (B = N/ndev).
    """

    axis: object            # str | tuple[str, ...]
    sizes: tuple
    block: int

    @property
    def ndev(self) -> int:
        n = 1
        for s in self.sizes:
            n *= s
        return n

    @property
    def n(self) -> int:
        return self.ndev * self.block

    def dev(self):
        """Linear device index along the node axis (traced)."""
        axes = (self.axis,) if isinstance(self.axis, str) else tuple(self.axis)
        idx = jnp.int32(0)
        for a, s in zip(axes, self.sizes):
            idx = idx * s + jax.lax.axis_index(a)
        return idx

    def rows(self):
        """Global node ids of this device's block, (B,) int32 (traced)."""
        return self.dev() * self.block + jnp.arange(self.block, dtype=jnp.int32)

    def gather(self, x):
        """all-gather the node axis: (B, ...) -> (N, ...)."""
        return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)

    def local(self, x):
        """Slice this device's (B, ...) row block out of a replicated
        (N, ...) array (for closure-captured per-node constants)."""
        return jax.lax.dynamic_slice_in_dim(x, self.dev() * self.block, self.block, 0)

    def psum(self, x):
        return jax.lax.psum(x, self.axis)

    def pmax(self, x):
        return jax.lax.pmax(x, self.axis)


@dataclasses.dataclass(eq=False)
class PermuteSchedule:
    """Static rotation-grouped transfer tables for per-slot permutation
    gossip (see topology.build_permute_schedule).  Identity-hashed: engines
    build one per static topology and reuse it across traces."""

    slots: list  # per slot: {rotation: (send_idx (ndev, K), recv_pos (ndev, K))}

    @staticmethod
    def from_table(nbr_perm, ndev: int) -> "PermuteSchedule":
        return PermuteSchedule(build_permute_schedule(nbr_perm, ndev))


def _permute_block(x, slot_sched, shard: NodeShard):
    """Apply one global node permutation to a block-sharded (B, ...) array:
    out[i] = x_global[src[global_row(i)]], via one `collective_permute` per
    device rotation that actually carries traffic (rotation 0 is a local
    move).  Padded lanes scatter out of range and are dropped."""
    dev = shard.dev()
    ndev, b = shard.ndev, shard.block
    out = jnp.zeros_like(x)
    for r in sorted(slot_sched):
        send_idx, recv_pos = slot_sched[r]
        si = jax.lax.dynamic_index_in_dim(jnp.asarray(send_idx), dev, 0, keepdims=False)
        rp = jax.lax.dynamic_index_in_dim(jnp.asarray(recv_pos), dev, 0, keepdims=False)
        payload = jnp.take(x, si, axis=0)
        if r != 0:
            axes = (shard.axis,) if isinstance(shard.axis, str) else shard.axis
            axis = axes[0] if len(axes) == 1 else tuple(axes)
            pairs = [(d, (d + r) % ndev) for d in range(ndev)]
            payload = jax.lax.ppermute(payload, axis, pairs)
        out = out.at[rp].set(payload, mode="drop")
    return out


@dataclasses.dataclass(eq=False)
class ShardedTopology:
    """Node-sharded view of a SparseTopology inside a shard_map body.

    topo: this device's (B, D) row block of the (rebalanced, when ``sched``
    is set) neighbor/weight tables — traced leaves, so churn reweighting
    updates the weights per round while the communication schedule stays
    static.  Registered as a pytree (shard/sched are static aux data).
    """

    topo: SparseTopology
    shard: NodeShard
    sched: Optional[PermuteSchedule] = None

    @property
    def rows(self):
        return self.shard.rows()

    @property
    def w(self):
        return self.topo.w

    def neighbor_stack(self, Y):
        """(B, D, ...) stack of each local receiver's neighbor rows of the
        node-stacked Y — slot-permutation exchange when the schedule exists,
        all-gather + local gather otherwise."""
        if self.sched is not None:
            return jnp.stack(
                [_permute_block(Y, s, self.shard) for s in self.sched.slots], axis=1
            )
        return jnp.take(self.shard.gather(Y), self.topo.nbr, axis=0)

    def apply(self, Yf):
        """Row-block of W @ Y_global for local rows; Yf: (B, ...) float32."""
        w = self.topo.w.astype(jnp.float32)
        w_self = self.topo.w_self.astype(jnp.float32).reshape(
            (Yf.shape[0],) + (1,) * (Yf.ndim - 1)
        )
        if self.sched is None:
            g = jnp.take(self.shard.gather(Yf), self.topo.nbr, axis=0)
            return w_self * Yf + jnp.einsum("nd,nd...->n...", w, g, precision=F32)
        acc = w_self * Yf
        for s, slot_sched in enumerate(self.sched.slots):
            xs = _permute_block(Yf, slot_sched, self.shard)
            ws = w[:, s].reshape((Yf.shape[0],) + (1,) * (Yf.ndim - 1))
            acc = acc + ws * xs
        return acc


@dataclasses.dataclass(eq=False)
class ShardedDense:
    """Node-sharded dense mixing operand: this device's (B, N) W rows."""

    W: jax.Array
    shard: NodeShard

    @property
    def rows(self):
        return self.shard.rows()

    def apply(self, Yf):
        return jnp.einsum(
            "bn,n...->b...", self.W.astype(jnp.float32), self.shard.gather(Yf),
            precision=F32,
        )


jax.tree_util.register_pytree_node(
    ShardedTopology,
    lambda t: ((t.topo,), (t.shard, t.sched)),
    lambda aux, leaves: ShardedTopology(leaves[0], *aux),
)
jax.tree_util.register_pytree_node(
    ShardedDense,
    lambda t: ((t.W,), (t.shard,)),
    lambda aux, leaves: ShardedDense(leaves[0], *aux),
)


def mix_dense(stacked, W):
    """x_i' = sum_j W_ij x_j per leaf; W (N, N) may be traced."""
    W = W.astype(jnp.float32)

    def f(a):
        return jnp.einsum("ij,j...->i...", W, a.astype(jnp.float32),
                          precision=F32).astype(a.dtype)

    return jax.tree_util.tree_map(f, stacked)


def apply_W(W, Y):
    """Row-stochastic mix Y' = W @ Y, fp32 accumulate, any trailing dims.

    W: dense (N, N) array (possibly traced) *or* a ``SparseTopology``.
    The sparse form gathers each node's D neighbor rows and contracts the
    slot axis — O(N·D·prod(trailing)) instead of O(N²·prod(trailing)) —
    without ever materializing an (N, N) matrix.
    """
    Yf = Y.astype(jnp.float32)
    if isinstance(W, (ShardedTopology, ShardedDense)):
        return W.apply(Yf)  # inside a shard_map body: Y is this device's rows
    if isinstance(W, SparseTopology):
        return _mix_rows(W, Yf)
    return jnp.einsum("ij,j...->i...", W.astype(jnp.float32), Yf, precision=F32)


def _mix_rows(topo: SparseTopology, Yf):
    """w_self_i Y_i + sum_k w[i,k] Y_nbr[i,k] for fp32 Yf (N, ...).

    On TPU the fused (D+1)-way weighted merge runs in the
    ``kernels.gossip_mix`` Pallas kernel (one HBM pass per operand);
    other backends run the XLA gather + einsum.
    """
    if jax.default_backend() != "tpu":
        return gather_mix(topo, Yf)
    from repro.kernels.ops import gossip_mix_nodes

    xs, ws = gossip_operands(topo, Yf.reshape(Yf.shape[0], -1))
    return gossip_mix_nodes(xs, ws).reshape(Yf.shape)


def gather_mix(topo: SparseTopology, Yf):
    """The XLA form of :func:`_mix_rows`: gather (N, D, ...) neighbor rows,
    contract the slot axis."""
    g = jnp.take(Yf, topo.nbr, axis=0)
    mixed = jnp.einsum("nd,nd...->n...", topo.w.astype(jnp.float32), g,
                       precision=F32)
    w_self = topo.w_self.astype(jnp.float32).reshape(
        (Yf.shape[0],) + (1,) * (Yf.ndim - 1)
    )
    return w_self * Yf + mixed


def gossip_operands(topo: SparseTopology, flat):
    """The fused kernel's operands for rows ``flat`` (N, P): the slot-major
    stack (1 + D, N, P) — self, then one gather per neighbor slot — and the
    weights (N, 1 + D).  A single (D, N) gather is assembled by XLA on TPU
    from split pieces with two copies live: 10.1 vs 5.3 GB of temp at
    N=256, GN-LeNet."""
    xs = jnp.stack([flat] + [jnp.take(flat, topo.nbr[:, d], axis=0)
                             for d in range(topo.nbr.shape[1])])
    ws = jnp.concatenate(
        [topo.w_self.astype(jnp.float32)[:, None], topo.w.astype(jnp.float32)],
        axis=1,
    )
    return xs, ws


def mix_sparse(stacked, topo: SparseTopology):
    """Neighbor-indexed gossip over a pytree: x_i' = w_self_i x_i +
    sum_k w[i,k] x_nbr[i,k] per leaf — O(N·D·P); the ``apply_W`` sparse
    form leaf by leaf.
    """
    return jax.tree_util.tree_map(
        lambda a: apply_W(topo, a).astype(a.dtype), stacked
    )


# ---------------------------------------------------------------------------
# payload-indexed aggregation: the compressed-sharing wire primitive
# ---------------------------------------------------------------------------
#
# Sparsified sharing strategies emit compact per-node payloads instead of
# masked (N, P) matrices: ``idx`` (N, k) int32 coordinate indices and
# ``val`` (N, k) wire values (possibly dequantized int8).  ``mix_payload``
# applies DecentralizePy's missing-coordinate rule
#
#     x_i'[c] = x_i[c] + sum_j W_ij * m_j[c] * (v_j[c] - x_i[c])
#
# in one gather + scatter-accumulate pass over neighbor payloads — O(N·d·k)
# compute and wire instead of the dense-mask form's two full apply_W
# passes at O(N·d·P).  The self slot rides along with weight w_self (it
# cancels exactly when val == x[idx], and reproduces the dense rule's
# self-roundtrip when values are quantized).  ``mix_payload_masked`` is the
# dense-mask oracle — identical math through scattered (N, P) masks and
# two apply_W passes — that the payload path is property-tested against
# (and the ``DLConfig.payload="off"`` execution path).


def _payload_operands(W, idx, valf, include_self: bool):
    """(idx_ops, val_ops, w_ops) stacked (rows, S, k)/(rows, S) operand
    payloads for each receiver — the neighbor slots of the mixing operand
    (exchanged via collective permutes when W is a scheduled
    ShardedTopology), preceded by the self slot when ``include_self``.

    The self slot's contribution w_self * (val_i - x_i[idx_i]) is exactly
    zero when payload values are the sender's own coordinates (val == x at
    idx, bit-for-bit), so callers skip it unless the wire codec perturbs
    values (int8 quantization), where the dense rule's self-roundtrip term
    must be reproduced."""
    if isinstance(W, ShardedTopology):
        idx_nbr = W.neighbor_stack(idx)                       # (B, D, k)
        val_nbr = W.neighbor_stack(valf)
        w, w_self = W.topo.w, W.topo.w_self
    else:  # SparseTopology
        idx_nbr = jnp.take(idx, W.nbr, axis=0)                # (N, D, k)
        val_nbr = jnp.take(valf, W.nbr, axis=0)
        w, w_self = W.w, W.w_self
    if not include_self:
        return idx_nbr, val_nbr, w.astype(jnp.float32)
    idx_ops = jnp.concatenate([idx[:, None, :], idx_nbr], axis=1)
    val_ops = jnp.concatenate([valf[:, None, :], val_nbr], axis=1)
    w_ops = jnp.concatenate(
        [w_self.astype(jnp.float32)[:, None], w.astype(jnp.float32)], axis=1
    )
    return idx_ops, val_ops, w_ops


def _payload_scatter(Xf, idx_ops, val_ops, w_ops):
    """out = Xf + sum over operand slots of w * (val - Xf[idx]) scattered
    at idx — the XLA lowering (take_along_axis + at[].add)."""
    n = Xf.shape[0]
    s, k = idx_ops.shape[1], idx_ops.shape[2]
    fid = idx_ops.reshape(n, s * k)
    own = jnp.take_along_axis(Xf, fid, axis=1)
    contrib = (val_ops.reshape(n, s * k) - own) * jnp.repeat(w_ops, k, axis=1)
    delta = jnp.zeros_like(Xf).at[jnp.arange(n)[:, None], fid].add(contrib)
    return Xf + delta


def mix_payload(W, idx, val, X, *, exact_values: bool = True):
    """Payload-indexed sparse aggregation: X' from per-node payloads.

    W: dense (N, N), ``SparseTopology``, or the sharded wrappers
    (``ShardedTopology``/``ShardedDense`` inside a shard_map body — payload
    exchange then rides the same per-slot `collective_permute` schedule as
    plain gossip, carrying (B, k) indices + values: O(D·B·k) wire).
    idx: (N, k) int32; val: (N, k) wire values; X: (N, P).  Returns fp32.

    exact_values: promise that ``val`` is bit-for-bit the sender's own
    coordinates (no lossy wire codec) — the self slot's correction is then
    exactly zero and is skipped; pass False for quantized payloads so the
    dense rule's self-roundtrip term is reproduced.

    Sparse/sharded forms run the XLA gather + scatter-accumulate pass on
    every backend.  The ``kernels.scatter_gossip`` Pallas form is not used:
    its in-VMEM one-hot scatter is (K·k, BN) per block, about 9 GB at
    GN-LeNet width and budget 0.01.  A dense (N, N) W — the all-pairs
    oracle regime — falls back to :func:`mix_payload_masked`.
    """
    Xf = X.astype(jnp.float32)
    valf = val.astype(jnp.float32)
    if isinstance(W, ShardedDense):
        idx_g, val_g = W.shard.gather(idx), W.shard.gather(valf)
        MX = _scatter_rows(idx_g, val_g, (idx_g.shape[0], Xf.shape[1]))
        M = _scatter_rows(idx_g, jnp.ones_like(val_g), MX.shape)
        return Xf + W.apply(MX) - Xf * W.apply(M)
    if isinstance(W, (ShardedTopology, SparseTopology)):
        idx_ops, val_ops, w_ops = _payload_operands(
            W, idx, valf, include_self=not exact_values
        )
        return _payload_scatter(Xf, idx_ops, val_ops, w_ops)
    return mix_payload_masked(W, idx, valf, Xf)


def mix_payload_strided(W, phase, val, X, *, exact_values: bool = True):
    """Strided-payload aggregation — the windowed-scatter fast path for
    ``RandomKSharing(sampler='strided')``.

    The P axis is split into k equal cells of width ``stride`` (the caller
    pads P up to k·stride); sender n's payload is its value at offset
    ``phase[n]`` of *every* cell: idx = i·stride + phase_n.  Because one
    offset addresses a whole k-vector, a receiver applies neighbor s's
    payload as a single k-wide column update of its (k, stride) cell view
    — the scatter indexes N·D rows instead of N·D·k elements, which XLA
    vectorizes (each scattered window is a contiguous k-vector), so the
    receive runs at O(N·d·k) vector speed with no dense (N, P) mask.

    phase: (N,) int32 in [0, stride); val: (N, k); X: (N, k·stride).
    Dense (N, N) W falls back to the masked oracle on reconstructed
    indices.  exact_values as in :func:`mix_payload`.
    """
    Xf = X.astype(jnp.float32)
    valf = val.astype(jnp.float32)
    n, p = Xf.shape
    k = valf.shape[1]
    stride = p // k
    if isinstance(W, ShardedDense) or not isinstance(
        W, (ShardedTopology, SparseTopology)
    ):
        idx = jnp.arange(k, dtype=jnp.int32)[None, :] * stride + phase[:, None]
        if isinstance(W, ShardedDense):
            idx_g, val_g = W.shard.gather(idx), W.shard.gather(valf)
            MX = _scatter_rows(idx_g, val_g, (idx_g.shape[0], p))
            M = _scatter_rows(idx_g, jnp.ones_like(val_g), MX.shape)
            return Xf + W.apply(MX) - Xf * W.apply(M)
        return mix_payload_masked(W, idx, valf, Xf)
    if isinstance(W, ShardedTopology):
        ph_ops = W.neighbor_stack(phase)                   # (B, D)
        val_ops = W.neighbor_stack(valf)                   # (B, D, k)
        w_ops = W.topo.w.astype(jnp.float32)
        w_self = W.topo.w_self
    else:
        ph_ops = jnp.take(phase, W.nbr, axis=0)            # (N, D)
        val_ops = jnp.take(valf, W.nbr, axis=0)            # (N, D, k)
        w_ops = W.w.astype(jnp.float32)
        w_self = W.w_self
    if not exact_values:
        ph_ops = jnp.concatenate([phase[:, None], ph_ops], axis=1)
        val_ops = jnp.concatenate([valf[:, None, :], val_ops], axis=1)
        w_ops = jnp.concatenate(
            [w_self.astype(jnp.float32)[:, None], w_ops], axis=1
        )
    cells_t = jnp.moveaxis(Xf.reshape(n, k, stride), 1, 2)  # (N, stride, k)
    own = jnp.take_along_axis(cells_t, ph_ops[:, :, None], axis=1)  # (N, D, k)
    contrib = w_ops[:, :, None] * (val_ops - own)
    delta_t = jnp.zeros_like(cells_t).at[
        jnp.arange(n)[:, None], ph_ops, :
    ].add(contrib)
    return Xf + jnp.moveaxis(delta_t, 1, 2).reshape(n, p)


def _scatter_rows(idx, val, shape):
    """Dense (N, P) scatter of per-row payloads (payload indices are unique
    per row, so set == add)."""
    return jnp.zeros(shape, jnp.float32).at[
        jnp.arange(shape[0])[:, None], idx
    ].set(val.astype(jnp.float32))


def mix_payload_masked(W, idx, val, X):
    """Dense-mask oracle of :func:`mix_payload`: scatter the payload into
    (N, P) value/mask matrices and apply the missing-coordinate rule as
    X' = X + W@(M*V) - X*(W@M) — two full apply_W passes, O(N·d·P).  With
    val gathered from X this is bit-for-bit the legacy ``sparse_aggregate``
    dense-mask path; it stays as the equivalence oracle and the
    ``payload="off"`` execution mode."""
    Xf = X.astype(jnp.float32)
    MX = _scatter_rows(idx, val, Xf.shape)
    M = _scatter_rows(idx, jnp.ones_like(val, jnp.float32), Xf.shape)
    return Xf + apply_W(W, MX) - Xf * apply_W(W, M)


def gossip_pair_avg(topo: SparseTopology, X, key, *, fire=None, act=None,
                    rows=None):
    """One event-cohort of *pairwise* asynchronous gossip — the AD-PSGD
    update (Lian et al. 2018) in one-sided-read form.  This IS the
    execution path of ``AsyncScheduler`` with ``async_gossip="pairwise"``
    (not just a reference implementation).

    Each node draws one uniformly-random neighbor slot from its
    ``SparseTopology`` table (``topology.sample_neighbor_slots`` — the
    per-event sampling primitive) and averages with that partner's
    current — possibly stale — row:

        x_i' = (x_i + x_{j(i)}) / 2      for fired nodes i (partner up)
        x_i' = x_i                       otherwise

    fire: optional (N,) {0,1} mask of nodes whose event fires this cohort
    (None = everyone).  act: optional (N,) {0,1} churn mask — a sampled
    partner that is down blocks the exchange (the node keeps its local
    step and retries at its next event).  The read is one-sided: partner
    j's row is read but not written, so concurrent events never conflict
    — the write-locked symmetric exchange of the original algorithm is
    modeled in expectation (each direction of an edge fires as its
    endpoint's event).  In expectation over the partner draw the
    fired-row update equals the uniform-neighbor mixing matrix row
    (0.5 self + 0.5/deg per neighbor) — seeded-statistically tested in
    tests/test_scheduler.py.

    Returns (X', partner, ok): partner the (N,) global partner ids (a
    node's own id where no exchange happened), ok the (N,) {0,1} mask of
    exchanges that actually fired — for staleness/comm accounting by the
    caller.
    """
    Xf = X.astype(jnp.float32)
    slot = sample_neighbor_slots(key, topo, rows=rows)
    partner = jnp.take_along_axis(topo.nbr, slot[:, None], axis=1)[:, 0]
    ok = jnp.ones(partner.shape[0], jnp.float32)
    if fire is not None:
        ok = ok * fire
    if act is not None:
        ok = ok * jnp.take(act, partner)
    X2 = 0.5 * (Xf + jnp.take(Xf, partner, axis=0))
    m = ok.reshape((-1,) + (1,) * (Xf.ndim - 1))
    X2 = jnp.where(m > 0, X2, Xf)
    partner = jnp.where(ok > 0, partner, jnp.arange(partner.shape[0]))
    return X2.astype(X.dtype), partner, ok


def mix_fully(stacked):
    """Fully-connected with uniform MH weights == mean over nodes."""

    def f(a):
        return jnp.broadcast_to(
            a.astype(jnp.float32).mean(0, keepdims=True), a.shape
        ).astype(a.dtype)

    return jax.tree_util.tree_map(f, stacked)


def mix_circulant(stacked, n: int, degree: int, weights: Optional[jax.Array] = None):
    """Static circulant d-regular gossip via roll (emulation / GSPMD path).

    weights: optional (1 + n_offsets,) traced [w_self, w_off1, ...];
    defaults to uniform MH 1/(degree+1).
    """
    offs = circulant_offsets(n, degree)
    if weights is None:
        weights = jnp.full((1 + len(offs),), 1.0 / (degree + 1), jnp.float32)

    def f(a):
        acc = weights[0] * a.astype(jnp.float32)
        for k, o in enumerate(offs):
            contrib = jnp.roll(a, -o, 0).astype(jnp.float32)
            if 2 * o % n != 0:  # antipodal offset has a single neighbor
                contrib = contrib + jnp.roll(a, o, 0).astype(jnp.float32)
            acc = acc + weights[1 + k] * contrib
        return acc.astype(a.dtype)

    return jax.tree_util.tree_map(f, stacked)


def mix_circulant_shmap(stacked, mesh, node_axes, degree: int,
                        weights: Optional[jax.Array] = None, pspecs=None):
    """Circulant gossip with explicit `collective_permute` per offset.

    node_axes: mesh axis name(s) forming the node dimension, e.g.
    ('data',) or ('pod', 'data').  Requires N == prod(mesh sizes of axes)
    and every leaf's leading dim == N.

    pspecs: optional PartitionSpec pytree matching ``stacked`` — REQUIRED
    when leaves are tensor-parallel-sharded, otherwise shard_map would
    reshard (replicate) them across the model axis and the wire would pay
    the full unsharded model per send (measured 16x inflation).
    """
    n = 1
    for ax in node_axes:
        n *= mesh.shape[ax]
    offs = circulant_offsets(n, degree)
    if weights is None:
        weights = jnp.full((1 + len(offs),), 1.0 / (degree + 1), jnp.float32)
    axis = tuple(node_axes) if len(node_axes) > 1 else node_axes[0]

    def local(w, *leaves):
        out = []
        for a in leaves:
            # Pin the wire dtype: XLA canonicalizes convert∘permute into
            # permute∘convert, which would ship fp32 (2x bytes) for bf16
            # params.  Permuting the *bitcast integer* view makes that
            # rewrite impossible — the interconnect carries exactly
            # param-dtype bytes.
            int_dt = {2: jnp.uint16, 4: jnp.uint32, 1: jnp.uint8}[a.dtype.itemsize]
            a_wire = jax.lax.bitcast_convert_type(a, int_dt)
            unwire = lambda t: jax.lax.bitcast_convert_type(t, a.dtype).astype(jnp.float32)
            acc = w[0] * a.astype(jnp.float32)
            for k, o in enumerate(offs):
                fwd = [(i, (i + o) % n) for i in range(n)]
                contrib = unwire(jax.lax.ppermute(a_wire, axis, fwd))
                if 2 * o % n != 0:
                    bwd = [(i, (i - o) % n) for i in range(n)]
                    contrib = contrib + unwire(jax.lax.ppermute(a_wire, axis, bwd))
                acc = acc + w[1 + k] * contrib
            out.append(acc.astype(a.dtype))
        return tuple(out)

    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    if pspecs is not None:
        spec_leaves = jax.tree_util.tree_flatten(pspecs)[0]
    else:
        spec_leaves = [P(node_axes, *((None,) * (l.ndim - 1))) for l in leaves]
    in_specs = (P(),) + tuple(spec_leaves)
    out_specs = tuple(spec_leaves)
    fn = shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    mixed = fn(weights, *leaves)
    return jax.tree_util.tree_unflatten(treedef, mixed)


def mix_sparse_shmap(stacked, topo: SparseTopology, mesh, node_axes, *,
                     pspecs=None, backend: str = "auto"):
    """Distributed neighbor-indexed gossip: x_i' = w_self_i x_i +
    sum_k w[i,k] x_nbr[i,k] with the node axis sharded over ``mesh``.

    Generalizes ``mix_circulant_shmap`` from circulant offsets to any
    static ``SparseTopology``: the padded (N, D) table is slot-rebalanced
    into D permutation columns (topology.decompose_slot_permutations), and
    each column lowers to rotation-grouped `collective_permute`s — exactly
    one ppermute per slot when N equals the device count.  Tables that
    don't decompose (or backend="gather") use all-gather + local gather.

    node_axes: mesh axis name(s) forming the node dimension; N must be a
    multiple of the product of their sizes, and every leaf's leading dim N.
    backend: "auto" (ppermute when decomposable) | "ppermute" | "gather".
    """
    if backend not in ("auto", "ppermute", "gather"):
        raise ValueError(f"unknown backend {backend!r} (auto|ppermute|gather)")
    sizes = tuple(mesh.shape[a] for a in node_axes)
    ndev = 1
    for s in sizes:
        ndev *= s
    n = topo.n
    assert n % ndev == 0, f"N={n} must divide over {ndev} devices"
    axis = tuple(node_axes) if len(node_axes) > 1 else node_axes[0]
    shard = NodeShard(axis, sizes, n // ndev)
    table, sched = topo, None
    if backend != "gather":
        dec = decompose_slot_permutations(topo)
        if dec is not None:
            table = dec
            sched = PermuteSchedule.from_table(dec.nbr, ndev)
        elif backend == "ppermute":
            raise ValueError("topology does not decompose into per-slot "
                             "permutations; use backend='gather'")
    tables = jax.tree_util.tree_map(jnp.asarray, table)

    def local(nbr, w, w_self, *leaves):
        st = ShardedTopology(SparseTopology(nbr, w, w_self), shard, sched)
        return tuple(st.apply(a.astype(jnp.float32)).astype(a.dtype) for a in leaves)

    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    if pspecs is not None:
        spec_leaves = jax.tree_util.tree_flatten(pspecs)[0]
    else:
        spec_leaves = [P(node_axes, *((None,) * (l.ndim - 1))) for l in leaves]
    tspecs = (P(node_axes, None), P(node_axes, None), P(node_axes))
    fn = shard_map(
        local, mesh=mesh, in_specs=tspecs + tuple(spec_leaves),
        out_specs=tuple(spec_leaves), check_vma=False,
    )
    mixed = fn(tables.nbr, tables.w, tables.w_self, *leaves)
    return jax.tree_util.tree_unflatten(treedef, mixed)


def mix_compressed_circulant_shmap(
    stacked,
    pspecs,
    mesh,
    node_axes,
    degree: int,
    *,
    budget: float = 0.1,
    mode: str = "sparse",  # 'sparse' | 'quant' | 'sparse+quant'
    weights: Optional[jax.Array] = None,
):
    """Compressed circulant gossip — the paper's sparsification/compression
    modules on the TPU wire, for the tensor-parallel trainer
    (``training/trainer.py`` ``mixing_impl='sparse'/'quant'``).

    Per mesh-shard: select the top-``budget`` fraction of the *local* block
    by magnitude ('sparse'), optionally int8-quantize the values ('quant',
    via ``compression.quantize_int8`` — the same codec every quantized wire
    uses), `collective_permute` only the compressed payload, and
    scatter-merge at the receiver with DecentralizePy's missing-coordinate
    semantics

        x_i' = x_i + sum_nbr w * scatter(idx_nbr, vals_nbr - x_i[idx_nbr]).

    Wire bytes drop from P*dtype to ~budget*P*(4+payload) ('sparse') or
    P*1 ('quant') — visible directly in the dry-run's collective-permute
    operand bytes.  The general engine path does the same thing for
    arbitrary sparse overlays through payload-emitting sharing strategies +
    :func:`mix_payload` (``DLConfig.payload``); this circulant form remains
    only where gossip composes with tensor-parallel model shards (pspecs).
    """
    n = 1
    for ax in node_axes:
        n *= mesh.shape[ax]
    offs = circulant_offsets(n, degree)
    if weights is None:
        w_nbr = 1.0 / (degree + 1)
    axis = tuple(node_axes) if len(node_axes) > 1 else node_axes[0]

    def perms(o, rev=False):
        if rev:
            return [(i, (i - o) % n) for i in range(n)]
        return [(i, (i + o) % n) for i in range(n)]

    ROW = 1 << 20  # top-k row block: keeps indices int32 even for >2^31 leaves

    def _quant(v32):
        from repro.core.compression import quantize_int8

        return quantize_int8(v32)

    def per_leaf(leaf, spec):
        def local(x):
            shape = x.shape
            flat = x.reshape(-1)
            size = flat.size
            R = min(ROW, size)
            pad = (-size) % R
            rows = jnp.pad(flat, (0, pad)).reshape(-1, R)  # (nr, R)
            f32 = rows.astype(jnp.float32)
            if "sparse" in mode:
                k = max(1, int(budget * R))
                _, idx = jax.lax.top_k(jnp.abs(f32), k)       # (nr, k) int32
                vals = jnp.take_along_axis(f32, idx, axis=-1)  # (nr, k)
            else:
                idx, vals = None, f32
            if "quant" in mode:
                payload, scale = _quant(vals)
            else:
                payload, scale = vals, None
            delta = jnp.zeros_like(f32)
            for o in offs:
                dirs = [False] if (2 * o) % n == 0 else [False, True]
                for rev in dirs:
                    pp = lambda t: jax.lax.ppermute(t, axis, perms(o, rev))
                    r_payload = pp(payload)
                    r_scale = pp(scale) if scale is not None else None
                    r_idx = pp(idx) if idx is not None else None
                    r_vals = (r_payload.astype(jnp.float32) * r_scale
                              if r_scale is not None else r_payload)
                    if r_idx is not None:
                        own = jnp.take_along_axis(f32, r_idx, axis=-1)
                        delta = delta.at[
                            jnp.arange(f32.shape[0])[:, None], r_idx
                        ].add(w_nbr * (r_vals - own))
                    else:
                        delta = delta + w_nbr * (r_vals - f32)
            return (f32 + delta).reshape(-1)[:size].reshape(shape).astype(x.dtype)

        fn = shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=spec,
                           check_vma=False)
        return fn(leaf)

    return jax.tree_util.tree_map(per_leaf, stacked, pspecs)


def mixing_bytes_per_node(graph: Graph, n_params: int, bytes_per_param: int = 4) -> float:
    """Average bytes *sent* per node per round under full sharing (the
    paper's cumulative-bytes metric)."""
    return float(graph.degrees().mean()) * n_params * bytes_per_param
