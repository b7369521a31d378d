"""Step layer — the pure, jittable per-round functions of the engine.

The execution model splits into two layers (see ``core/scheduler.py`` for
the other half):

* **steps** (this module): what one node-stacked round *does* — the local
  SGD step (``RoundSteps.local_train``), the share/mix step through the
  configured sharing strategy (``RoundSteps.train_and_mix``), and the
  simulated per-node round time (``RoundSteps.round_time``).  Every
  function is pure in its traced arguments and runs identically inside a
  ``lax.scan`` body, a legacy per-round jit, or a ``shard_map`` block
  (``shard`` carries the node-axis sharding when present).
* **scheduler**: when those steps fire and what time means — the
  synchronous round barrier, per-node local clocks, or event-driven
  cohorts on a virtual clock.

``RoundSteps`` is a plain container of the static experiment pieces
(loss/optimizer/sharing, per-node compute times, link matrices); it holds
no mutable state — params/opt/sharing state are threaded by the caller.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import faults as faults_lib
from repro.core.mixing import ShardedDense, ShardedTopology
from repro.core.network import gathered_round_times, node_round_times
from repro.core.sharing import (
    edge_reweight,
    edge_reweight_sparse,
    participation_reweight,
    participation_reweight_sparse,
)
from repro.core.topology import SparseTopology
from repro.optim.optimizers import apply_updates
from repro.utils.pytree import tree_unvector, tree_vector


def node_scale(tree, scale):
    """Multiply every node-stacked leaf by a per-node (N,) factor."""

    def f(a):
        return a * scale.reshape((scale.shape[0],) + (1,) * (a.ndim - 1))

    return jax.tree_util.tree_map(f, tree)


def node_where(mask, new, old):
    """Per-node select between two node-stacked pytrees."""

    def f(n, o):
        m = mask.reshape((mask.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m > 0, n, o)

    return jax.tree_util.tree_map(f, new, old)


@dataclasses.dataclass(eq=False)
class RoundSteps:
    """The traced per-round step functions, shared by every scheduler.

    compute_node: (N,) float32 per-node local compute seconds (the
    heterogeneous-time axis — a straggler is simply a large entry).
    lat/goodput: (N, N) link matrices of the simulated network, or None.
    """

    loss_fn: Callable
    opt: Any
    sharing: Any
    template: Any
    base_key: jax.Array
    mean_degree: float
    compute_node: jnp.ndarray
    parallel_sends: bool
    lr_scales: Optional[jnp.ndarray] = None
    lat: Optional[jnp.ndarray] = None
    goodput: Optional[jnp.ndarray] = None
    # fault injection (core/faults.py): the declarative plan plus its PRF
    # root key — None disables every fault branch statically
    faults: Optional[Any] = None
    fault_key: Optional[jax.Array] = None

    # ------------------------------------------------------------------
    def local_train(self, params, opt_state, bx, by, active, shard=None,
                    rows=None):
        """``rows`` (traced global node ids) marks a gathered row subset —
        the cohort path's (C, ...) hot set — and redirects the per-node
        static vectors (lr_scales) through the same gather; every other
        operand is already row-stacked by the caller."""
        def node_grad(p, x, y):
            return jax.grad(self.loss_fn)(p, x, y)

        if self.lr_scales is not None:
            if rows is not None:
                lrs = jnp.take(self.lr_scales, rows)
            elif shard is not None:
                # sharded: this device's block of the per-node multipliers
                lrs = shard.local(self.lr_scales)
            else:
                lrs = self.lr_scales
        # local_steps is small and static: unroll instead of nesting a scan
        for s in range(bx.shape[0]):
            grads = jax.vmap(node_grad)(params, bx[s], by[s])
            updates, new_opt = jax.vmap(self.opt.update)(grads, opt_state, params)
            if self.lr_scales is not None:
                updates = node_scale(updates, lrs)
            if active is not None:
                # down nodes do no local work: zero update, frozen opt state
                updates = node_scale(updates, active)
                new_opt = node_where(active, new_opt, opt_state)
            params, opt_state = apply_updates(params, updates), new_opt
        return params, opt_state

    # ------------------------------------------------------------------
    def round_time(self, Wm, active, nbytes, deg_eff, shard=None, *,
                   reduce: str = "max", lat_mult=None):
        """Simulated round wall-clock, traced — the same compute+comm
        formula as ``NetworkModel.round_time`` (both call
        ``network.node_round_times``; an equivalence test pins them
        together).  For a SparseTopology the per-edge latency/goodput are
        gathered through the neighbor table — O(N·D) — instead of masking
        (N, N) matrices.  Sharded: rows are this device's block (global ids
        index the replicated latency/goodput matrices) and the synchronous
        round max is a pmax over the node axis.

        reduce: 'max' — the synchronous-barrier scalar (every node waits
        for the slowest); 'none' — the per-node (N,) time vector, for
        schedulers that own their own clock semantics (local / async).
        """
        per_edge = jnp.where(deg_eff > 0, nbytes / jnp.maximum(deg_eff, 1e-9), 0.0)
        if isinstance(Wm, ShardedTopology):
            topo, rows = Wm.topo, Wm.rows[:, None]
            A = (topo.w > 0).astype(jnp.float32)
            lat = self.lat[rows, topo.nbr]
            gp = self.goodput[rows, topo.nbr]
        elif isinstance(Wm, ShardedDense):
            rows = Wm.rows
            offdiag = (jnp.arange(Wm.W.shape[1])[None, :] != rows[:, None]).astype(
                jnp.float32
            )
            A = (Wm.W * offdiag > 0).astype(jnp.float32)
            lat = jnp.take(self.lat, rows, axis=0)
            gp = jnp.take(self.goodput, rows, axis=0)
        elif isinstance(Wm, SparseTopology):
            rows = jnp.arange(Wm.nbr.shape[0])[:, None]
            A = (Wm.w > 0).astype(jnp.float32)  # live edge slots post-reweight
            lat = self.lat[rows, Wm.nbr]
            gp = self.goodput[rows, Wm.nbr]
        else:
            n = Wm.shape[0]
            offdiag = 1.0 - jnp.eye(n, dtype=jnp.float32)
            A = (Wm * offdiag > 0).astype(jnp.float32)
            lat, gp = self.lat, self.goodput
        if lat_mult is not None:
            # per-edge latency surges (fault injection): lat_mult is
            # aligned with A's edge layout (neighbor slots or dense)
            lat = lat * lat_mult
        ct = shard.local(self.compute_node) if shard is not None else self.compute_node
        node_t = node_round_times(A, lat, gp, per_edge, ct, self.parallel_sends)
        if active is not None:
            node_t = active * node_t
        if reduce == "none":
            return node_t
        t = jnp.max(node_t)
        return shard.pmax(t) if shard is not None else t

    # ------------------------------------------------------------------
    def cohort_comm_time(self, rows, nbr, live, nbytes, deg_eff):
        """Per-event comm seconds for a *gathered cohort* — the (C,)-row
        slice of ``round_time(..., reduce='none') - compute_node`` that the
        dense async path computes over all N rows, replicated expression
        for expression (per-edge bytes, the (ct + comm) - ct roundtrip) so
        the cohort trajectory matches the dense oracle bitwise.

        rows: (C,) global node ids; nbr: their (C, D) global neighbor ids;
        live: (C, D) {0,1} live-edge mask (post churn reweight).
        """
        per_edge = jnp.where(deg_eff > 0, nbytes / jnp.maximum(deg_eff, 1e-9), 0.0)
        ct = jnp.take(self.compute_node, rows)
        node_t = gathered_round_times(
            self.lat, self.goodput, rows, nbr, live, per_edge, ct,
            self.parallel_sends,
        )
        return node_t - ct  # caller adds compute back, like the dense path

    # ------------------------------------------------------------------
    def _secure_recovery_bytes(self, active, shard=None):
        """Wire bytes of the Bonawitz seed-recovery pass under churn: one
        revealed seed share per (live receiver, live sender, dropped
        co-neighbor) triple of the secure-aggregation neighbor table —
        the surviving co-neighbors re-send the dropped pair's key-chain
        material so the receiver can subtract its PRF masks.  Sharded:
        counted over this device's receiver rows, psum'd to the global
        scalar every device returns."""
        from repro.core.secure import SEED_SHARE_BYTES

        nbr = jnp.asarray(self.sharing._nbr)
        valid = jnp.asarray(self.sharing._valid, jnp.float32)
        if shard is not None:
            nbr, valid = shard.local(nbr), shard.local(valid)
            act_g = shard.gather(active)
        else:
            act_g = active
        a = jnp.take(act_g.astype(jnp.float32), nbr, axis=0)   # (B, D)
        live, dead = valid * a, valid * (1.0 - a)
        pairs = jnp.sum(active * live.sum(1) * dead.sum(1))
        if shard is not None:
            pairs = shard.psum(pairs)
        return pairs * SEED_SHARE_BYTES

    # ------------------------------------------------------------------
    def train_and_mix(self, params, opt_state, share_state, bx, by, W, active,
                      rnd, shard=None, *, time_reduce: str = "max"):
        """One round: local step, then the share/mix step through the
        configured sharing strategy.  ``active`` is None for full
        participation (statically skips masking/reweighting: W flows
        through untouched and the degree stays a Python float, exactly
        like per-round dispatch did).  ``shard`` is the node-axis sharding
        inside a shard_map body (all node-stacked operands are then this
        device's row blocks).  ``time_reduce`` is forwarded to
        :meth:`round_time` — 'max' for the synchronous barrier scalar,
        'none' for the per-node vector.

        With ``self.faults`` set (a ``core.faults.FaultPlan``), the round
        additionally injects message-level faults: per-edge message loss
        renormalizes the mixing operand (``edge_reweight``) while wire
        bytes and link time are still spent (the sender does not know);
        latency spikes multiply the affected edges' latency in the traced
        round time; payload corruption hits post-mix rows and the
        self-healing guard rolls detected (non-finite) rows back to the
        start-of-round snapshot.  Returns a 6-tuple ``(params, opt_state,
        share_state, nbytes, sim_t, fstats)`` where ``fstats`` is the
        static-schema fault-counter dict (``faults.STAT_KEYS``)."""
        plan = self.faults
        fstats = faults_lib.zero_stats()
        guard = plan is not None and plan.corrupt_prob > 0
        if guard:
            snap = (params, opt_state, share_state)  # last-good snapshot
        key = jax.random.fold_in(self.base_key, rnd)
        # named scopes mark the round's layers in the compiled program's
        # op metadata, where a profile attributes device time to them
        with jax.named_scope("local_step"):
            params, opt_state = self.local_train(
                params, opt_state, bx, by, active, shard
            )
        if active is not None:
            if isinstance(W, ShardedTopology):
                t2, deg_eff = participation_reweight_sparse(
                    W.topo, active, shard=W.shard
                )
                Wm = ShardedTopology(t2, W.shard, W.sched)
            elif isinstance(W, ShardedDense):
                W2, deg_eff = participation_reweight(W.W, active, shard=W.shard)
                Wm = ShardedDense(W2, W.shard)
            elif isinstance(W, SparseTopology):
                Wm, deg_eff = participation_reweight_sparse(W, active)
            else:
                Wm, deg_eff = participation_reweight(W, active)
        else:
            Wm, deg_eff = W, self.mean_degree
        # --- message-level edge faults (single-host; validated) ------------
        # the *mixing* operand drops lost edges (renormalized), but wire
        # bytes and simulated link time are charged on the churn-level
        # operand Wm: the sender transmitted, the network just lost it
        Wm_mix, lat_mult = Wm, None
        if plan is not None and plan.edge_faults:
            if isinstance(Wm, SparseTopology):
                n_rows, d = Wm.nbr.shape
                live, spike = faults_lib.edge_draws(
                    self.fault_key, rnd, jnp.arange(n_rows), d, plan
                )
                sent = (Wm.w > 0).astype(jnp.float32)
                Wm_mix = edge_reweight_sparse(Wm, live)
            else:
                n = Wm.shape[0]
                live, spike = faults_lib.edge_draws(
                    self.fault_key, rnd, jnp.arange(n), n, plan
                )
                sent = (
                    Wm * (1.0 - jnp.eye(n, dtype=jnp.float32)) > 0
                ).astype(jnp.float32)
                Wm_mix = edge_reweight(Wm, live)
            dropped = jnp.sum(sent * (1.0 - live))
            spiked = jnp.sum(sent * spike)
            if plan.latency_spike_prob > 0:
                lat_mult = 1.0 + spike * (plan.latency_spike_factor - 1.0)
            # drops are absorbed by renormalization, spikes by late
            # delivery: survived by design, never silently lost
            fstats["faults_injected"] += dropped + spiked
            fstats["faults_survived"] += dropped + spiked
        with jax.named_scope("flatten"):
            X = jax.vmap(tree_vector)(params)
        share_kw = {}
        if getattr(self.sharing, "needs_act", False) and active is not None:
            share_kw["act"] = active
        with jax.named_scope("share_mix"):
            X2, new_share, nbytes = self.sharing.round(
                X, Wm_mix, share_state, key, degree=deg_eff, rnd=rnd, **share_kw
            )
        if share_kw:
            rec = self._secure_recovery_bytes(active, shard)
            nbytes = nbytes + rec
            fstats["recovery_bytes"] += rec
        # --- payload corruption (post-mix, in flight) ----------------------
        if guard:
            cmask = faults_lib.corruption_mask(
                self.fault_key, rnd, jnp.arange(X2.shape[0]), plan
            )
            if active is not None:
                cmask = cmask * active  # a down node received nothing
            X2 = faults_lib.corrupt_rows(X2, cmask, plan.corrupt_mode)
            fstats["faults_injected"] += jnp.sum(cmask)
        if active is not None:
            # a down node transmitted nothing: its sharing bookkeeping
            # (TopK last_shared, CHOCO xhat — node-stacked leaves) must not
            # record this round's payload as sent
            share_state = node_where(active, new_share, share_state)
        else:
            share_state = new_share
        with jax.named_scope("unflatten"):
            new_params = jax.vmap(lambda v: tree_unvector(v, self.template))(X2)
        if active is not None:
            # don't trust each strategy's W-row-identity property for down
            # nodes (e.g. QuantizedSharing would hand them the int8
            # roundtrip of their own params): freeze them explicitly
            params = node_where(active, new_params, params)
        else:
            params = new_params
        # --- self-healing step guard: roll back non-finite rows ------------
        if guard:
            bad = faults_lib.nonfinite_rows(X2)
            if active is not None:
                bad = bad * active
            good = 1.0 - bad
            p0, o0, s0 = snap
            params = node_where(good, params, p0)
            opt_state = node_where(good, opt_state, o0)
            share_state = node_where(good, share_state, s0)
            nbad = jnp.sum(bad)
            fstats["faults_detected"] += nbad
            fstats["faults_recovered"] += nbad
        nbytes = jnp.asarray(nbytes, jnp.float32)
        if self.lat is not None:
            sim_t = self.round_time(Wm, active, nbytes, deg_eff, shard,
                                    reduce=time_reduce, lat_mult=lat_mult)
        elif time_reduce == "none":
            # no network model: comm is free but per-node compute time still
            # drives the virtual clocks (matching the async scheduler, whose
            # event cadence is compute-only without a network)
            node_t = self.compute_node
            if active is not None:
                node_t = active * node_t
            sim_t = node_t
        else:
            sim_t = jnp.float32(0.0)
        return params, opt_state, share_state, nbytes, sim_t, fstats
