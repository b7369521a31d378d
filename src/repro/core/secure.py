"""Secure aggregation for DL (paper §3.4, after Bonawitz et al. CCS'17 and
the DecentralizePy secure-aggregation node).

Every *receiver* r aggregates the models of its neighbor set N(r) with equal
weights.  Each ordered sender pair (i, j) in N(r), i < j, shares a seed; i
adds +PRF(seed), j adds -PRF(seed) to the copy each sends to r, so the sum
over N(r) is exactly the unmasked sum while every individual message is a
one-time-padded blob.  Receiver r's own model never leaves r.

    y_r = (1 - w·|N(r)|) x_r + w * sum_{i in N(r)} msg_{i->r}
        = MH-weighted aggregate (masks cancel exactly).

The PRF is JAX's threefry counter PRNG keyed by fold_in(round, i, j, r) —
uniform in [-b, b].  Masks are float32, so cancellation is exact in real
arithmetic but the *aggregate* suffers bounded rounding noise — the paper's
reported ~3% accuracy cost on CIFAR-10; we property-test the cancellation
to fp32 tolerance.

Two implementations of the same math:

* ``round``            — vectorized and fully jittable: the ragged neighbor
  sets become a padded ``(N, dmax)`` neighbor table (topology.neighbor_table),
  one batched vmap pass derives the threefry PRF *keys* of every
  receiver's co-neighbor pairs (O(N·d²) key words staged, the bit tensors
  never materialize; round index a *traced* value), and the fused
  ``kernels/secure_mask`` keyed Pallas kernel (compiled on TPU, interpret
  mode on CPU) runs the threefry counter expansion in-body, once per
  unordered pair, maps bits→uniform, and adds each mask with opposite
  signs to the two messages that carry it, in one HBM pass — bit-identical
  to expanding ``jax.random.bits`` per (message, pair).
  So ``secure=True`` runs inside the engine's lax.scan chunk like any other
  sharing strategy; it expands N·d(d-1)/2·P cipher words a round
  (``prf_words_per_round``), without the O(N·d) Python dict of messages.
* ``round_reference``  — the original Python dict-of-messages schedule, kept
  as the oracle the vectorized path is equivalence-tested against.  Both
  paths derive masks from the same threefry bits via the same
  ``kernels.ref.mask_bits_to_uniform`` mapping, so masks are bit-identical
  and only summation order differs.

``W`` may be the dense (N, N) matrix or a neighbor-indexed
``SparseTopology`` — only the per-receiver scalar weight is read from it,
so the sparse engine path threads its (N, D) tables straight through.

Communication: each edge carries the P masked values plus a 24-byte
metadata record (pair seeds + round) — the paper's ≈3% overhead is
metadata+framing; we account 3% to match its cost model.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mixing import ShardedDense, ShardedTopology
from repro.core.topology import SparseTopology, neighbor_table
from repro.kernels import ops as kernel_ops
from repro.kernels.ref import mask_bits_to_uniform
from repro.kernels.secure_mask import slot_pairs

BYTES_VAL = 4
METADATA_OVERHEAD = 0.03  # paper: ~3% extra bytes (seeds, framing)
# one revealed Shamir/seed share on the recovery round: the co-neighbor
# re-sends the (dropped pair, receiver) key-chain material — a 32-byte
# record (pair seed + ids + round), after Bonawitz et al. CCS'17 §5
SEED_SHARE_BYTES = 32


def _pair_key_from(kround, i, j, r):
    """PRF key for ordered pair (i, j) at receiver r, from a key already
    folded with the round — the single definition of the mask PRF chain
    (all indices may be tracers)."""
    k = jax.random.fold_in(kround, i)
    k = jax.random.fold_in(k, j)
    return jax.random.fold_in(k, r)


def _pair_bits_from(kround, i, j, r, shape):
    """Threefry PRF bits for ordered pair (i, j) at receiver r — the
    reference expansion of :func:`_pair_key_from` (the fused kernel
    generates the same bits in-body from the key words alone)."""
    return jax.random.bits(_pair_key_from(kround, i, j, r), shape, jnp.uint32)


def _pair_mask_from(kround, i, j, r, shape, bound: float):
    """PRF mask in [-bound, bound): bits -> uniform via the same mapping the
    Pallas kernel uses (kernels.ref.mask_bits_to_uniform), so the reference
    schedule and the fused kernel agree bit-exactly."""
    return mask_bits_to_uniform(_pair_bits_from(kround, i, j, r, shape), bound)


def _pair_mask(key, rnd, i, j, r, shape, bound: float):
    return _pair_mask_from(jax.random.fold_in(key, rnd), i, j, r, shape, bound)


@dataclasses.dataclass(frozen=True)
class SecureAggregation:
    """Drop-in sharing strategy: masked full sharing over a *static* graph.

    adj: (N, N) bool numpy adjacency (static — the mask schedule, i.e. the
    neighbor table, must be known at trace time; dynamic graphs would
    re-key every round anyway).

    recovery: enable the Bonawitz-style seed-recovery pass so masked
    aggregation stays correct under churn (``DLConfig.secure_recovery``).
    Dropped senders leave their pair masks uncancelled in every live
    co-neighbor's message; surviving co-neighbors re-derive the dropped
    pairs' PRF masks from the shared key chain (``_pair_key_from`` — the
    receiver learns only mask material it could already compute) and the
    receiver subtracts them in a second traced mask pass, then aggregates
    the *live* neighbor set only.  The corrected aggregate equals the
    churn-reweighted plain aggregate exactly (masks over live pairs still
    cancel pairwise; property-tested).  The recovery round's seed-share
    traffic is accounted per (live receiver, live sender, dropped
    co-neighbor) triple at ``SEED_SHARE_BYTES`` each — see
    ``steps.RoundSteps._secure_recovery_bytes``.
    """

    adj: np.ndarray
    mask_bound: float = 1.0
    recovery: bool = False

    def __post_init__(self):
        nbr, valid = neighbor_table(np.asarray(self.adj))
        object.__setattr__(self, "_nbr", nbr)
        object.__setattr__(self, "_valid", valid)

    def init_state(self, X):
        return ()

    @property
    def needs_act(self) -> bool:
        """The step layer passes the participation mask into :meth:`round`
        (``act=``) when recovery is on — the receiver must know which
        senders dropped to run the seed-recovery pass."""
        return self.recovery

    def messages(self, X, key, rnd):
        """Masked message from i to r for every edge (i, r). Returns a dict
        {(i, r): vector} — reference schedule, materialized only for
        emulation-scale N (and for the privacy tests)."""
        N, P = X.shape
        out = {}
        for r in range(N):
            nbrs = [int(i) for i in np.nonzero(self.adj[r])[0]]
            for i in nbrs:
                msg = X[i].astype(jnp.float32)
                for j in nbrs:
                    if j == i:
                        continue
                    a, b = (i, j) if i < j else (j, i)
                    sign = 1.0 if i < j else -1.0
                    msg = msg + sign * _pair_mask(key, rnd, a, b, r, (P,), self.mask_bound)
                out[(i, r)] = msg
        return out

    def round(self, X, W, state, key, degree, rnd=0, act=None):
        """Vectorized, jittable masked aggregation.  W (dense (N, N) or
        SparseTopology) must give equal weight w to all of a receiver's
        neighbors (true for MH on regular graphs); ``degree`` and ``rnd``
        may be traced scalars.  ``act`` is the (N,) participation mask
        (recovery mode only): dropped senders are excised via the
        seed-recovery pass and the live neighbor set is aggregated with
        the churn-reweighted weights W already carries.

        Pipeline: (1) one vmap pass over (receiver, co-neighbor slot pair)
        derives the threefry *pair keys* — O(N·d²) key words, not O(N·d·P)
        bit tensors; keys are built from the *sorted* node pair, so the
        pair's +1 and -1 occurrences are one mask and cancel exactly; (2)
        the fused Pallas kernel (``secure_mask_apply_pairs_keyed``) runs the
        threefry counter expansion in-body per parameter block, once per
        pair, maps bits -> uniform[-b, b), and adds each mask, signed, to
        both messages that carry it — all D·N messages in one HBM pass.
        Finally each receiver sums its valid masked messages with weight w.
        """
        if isinstance(W, (ShardedTopology, ShardedDense)):
            return self._round_sharded(X, W, state, key, degree, rnd, act)
        N, P = X.shape
        Xf = X.astype(jnp.float32)
        nbr = jnp.asarray(self._nbr)                      # (N, D)
        validf = jnp.asarray(self._valid, jnp.float32)
        if isinstance(W, SparseTopology):
            # the secure contract requires equal weights across a receiver's
            # neighbors, so any live slot's weight works: row max skips
            # w=0 padding (and any zeroed slot), where slot 0 alone would not
            wvec = jnp.max(W.w.astype(jnp.float32), axis=1)
        else:
            Wg = jnp.take_along_axis(W.astype(jnp.float32), nbr, axis=1)
            wvec = jnp.max(Wg * validf, axis=1)
        Xnbr = jnp.take(Xf, nbr.T, axis=0)                 # (D, N, P)
        act_nbr = None if act is None else jnp.take(act, nbr, axis=0)
        return self._masked_aggregate(
            Xf, Xnbr, nbr, validf, wvec, jnp.arange(N), key, rnd, degree,
            X.dtype, state, act_nbr,
        )

    def _round_sharded(self, X, W, state, key, degree, rnd, act=None):
        """Node-sharded masked aggregation (inside a shard_map body): X is
        this device's (B, P) row block, W the sharded mixing operand.  The
        co-neighbor messages arrive through ``W.neighbor_stack`` — the same
        per-slot `collective_permute` permutations (or the all-gather
        fallback) the plain gossip path uses — and the pair-PRF bits are
        keyed by *global* node ids, so every mask pair still cancels
        exactly as in the single-device schedule.  Recovery mode
        (``act`` given) uses the *canonical* neighbor table gathered at
        this device's rows: the rebalanced table's churn-zeroed weights
        can't be told apart from static padding, and recovery must see
        exactly the schedule the masks were keyed over."""
        B, P = X.shape
        Xf = X.astype(jnp.float32)
        act_g = None if act is None else W.shard.gather(act)
        if isinstance(W, ShardedTopology) and act is None:
            nbr = W.topo.nbr                               # (B, D), rebalanced order
            validf = (W.topo.w > 0).astype(jnp.float32)
            # equal-weight assumption (regular graphs): row max skips the
            # w=0 padding slots the rebalanced table interleaves
            wvec = jnp.max(W.topo.w.astype(jnp.float32), axis=1)
            Xnbr = jnp.moveaxis(W.neighbor_stack(Xf), 1, 0)  # (D, B, P)
        else:
            rows = W.rows
            nbr = jnp.take(jnp.asarray(self._nbr), rows, axis=0)
            validf = jnp.take(jnp.asarray(self._valid, jnp.float32), rows, axis=0)
            if isinstance(W, ShardedTopology):
                wvec = jnp.max(W.topo.w.astype(jnp.float32), axis=1)
            else:
                Wg = jnp.take_along_axis(W.W.astype(jnp.float32), nbr, axis=1)
                wvec = jnp.max(Wg * validf, axis=1)
            Xnbr = jnp.take(W.shard.gather(Xf), nbr.T, axis=0)
        act_nbr = None if act_g is None else jnp.take(act_g, nbr, axis=0)
        return self._masked_aggregate(
            Xf, Xnbr, nbr, validf, wvec, W.rows, key, rnd, degree, X.dtype,
            state, act_nbr,
        )

    def _masked_aggregate(self, Xf, Xnbr, nbr, validf, wvec, rows, key, rnd,
                          degree, dtype, state, act_nbr=None):
        """Shared core of the vectorized path: pair PRF keys + fused
        mask apply + weighted receiver sum.  ``rows`` are the global node
        ids of the local receiver rows (arange unsharded).  ``Xnbr`` is
        the slot-major (D, N, P) neighbor stack: on TPU a node-major
        (N, D, P) stack pads D up to 8 sublanes, 1.6x the bytes at D = 5.

        Recovery (``act_nbr`` — the neighbor slots' participation, (N, D)):
        pass 1 applies exactly the masks the senders transmitted (senders
        don't know who dropped, so they mask against *every* valid
        co-neighbor); pass 2 re-derives the (live sender, dropped
        co-neighbor) pair masks from the same key chain and subtracts
        them.  The surviving mask set then cancels pairwise over live
        pairs, and the receiver aggregates the live slots only — equal to
        the churn-reweighted plain aggregate."""
        P = Xf.shape[1]
        kr = jax.random.fold_in(key, rnd)
        i_mat = nbr[:, :, None]                            # sender node
        j_mat = nbr[:, None, :]                            # co-neighbor node
        signs = jnp.where(i_mat < j_mat, 1.0, -1.0) * validf[:, None, :]  # (N, D, D)
        lo, hi = slot_pairs(nbr.shape[1])                  # slot pairs s < t

        def receiver_keys(r, nbr_r):
            def pair(i, j):
                a, b = jnp.minimum(i, j), jnp.maximum(i, j)
                return jax.random.key_data(_pair_key_from(kr, a, b, r))

            return jax.vmap(pair)(nbr_r[lo], nbr_r[hi])    # (Q, 2)

        keys = jax.vmap(receiver_keys)(rows, nbr)          # (N, Q, 2) uint32

        msgs = kernel_ops.secure_mask_apply_pairs_keyed(
            Xnbr, keys, signs, self.mask_bound)            # (D, N, P)
        validf_live = validf
        if act_nbr is not None:
            down = validf * (1.0 - act_nbr)                # dropped co-nbrs
            msgs = kernel_ops.secure_mask_apply_pairs_keyed(
                msgs, keys, -signs * down[:, None, :], self.mask_bound)
            validf_live = validf * act_nbr
        deg_r = validf_live.sum(1)
        acc = (1.0 - wvec * deg_r)[:, None] * Xf + wvec[:, None] * jnp.sum(
            msgs * validf_live.T[:, :, None], axis=0
        )
        X2 = jnp.where((deg_r > 0)[:, None], acc, Xf)
        item = jnp.dtype(dtype).itemsize
        bytes_sent = degree * P * item * (1.0 + METADATA_OVERHEAD)
        return X2.astype(dtype), state, bytes_sent

    def wire_dtype(self, x_dtype):
        return np.dtype(x_dtype)

    def stage_bytes_per_round(self, n: int, p: int) -> int:
        # recovery stages a second full mask pass over the neighbor stack
        return n * p * 4 * (2 if self.recovery else 1)

    def prf_words_per_round(self, n: int, p: int) -> int:
        """Threefry words a round expands: one per position of each
        receiver's D(D-1)/2 co-neighbor pair masks, each mask shared by the
        two messages that carry it; recovery repeats the pass."""
        d = self._nbr.shape[1]
        return n * (d * (d - 1) // 2) * p * (2 if self.recovery else 1)

    def round_reference(self, X, W, state, key, degree: float, rnd: int = 0):
        """Python-scheduled reference: aggregate the dict of masked
        messages.  Oracle for the vectorized ``round``."""
        N, P = X.shape
        Xf = X.astype(jnp.float32)
        msgs = self.messages(Xf, key, rnd)
        rows = []
        Wn = np.asarray(W)
        for r in range(N):
            nbrs = [int(i) for i in np.nonzero(self.adj[r])[0]]
            w = float(Wn[r, nbrs[0]]) if nbrs else 0.0
            acc = (1.0 - w * len(nbrs)) * Xf[r]
            for i in nbrs:
                acc = acc + w * msgs[(i, r)]
            rows.append(acc)
        X2 = jnp.stack(rows).astype(X.dtype)
        bytes_sent = degree * P * jnp.dtype(X.dtype).itemsize * (1.0 + METADATA_OVERHEAD)
        return X2, state, bytes_sent
