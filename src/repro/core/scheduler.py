"""Scheduler layer — who fires when, and what time means.

The engine's execution model is split in two (``core/steps.py`` holds the
other half): the **step layer** defines what one round does (local SGD,
share/mix, per-node round time) as pure jittable functions, and this
**scheduler layer** owns time and activation semantics.  Three schedulers
implement ``DLConfig.semantics``:

* ``sync`` (:class:`SyncScheduler`) — the synchronous round barrier:
  every node mixes in lockstep, the simulated round time is the max over
  nodes (stragglers bind the whole network).  This is bit-for-bit the
  pre-split engine — the equivalence oracle the other semantics are
  tested against — including the legacy per-round dispatch
  (``chunk_rounds=0``) and the node-sharded ``shard_map`` chunk.
* ``local`` (:class:`LocalScheduler`) — same lockstep *trajectories* (the
  mixing math is identical, property-tested), but time is a per-node
  virtual clock with a **neighborhood barrier**: node i starts round r
  when it and its live neighbors have finished round r-1, so non-adjacent
  stragglers no longer bind each other.  Simulated experiment time is the
  max final clock — a lower bound pairing with sync's global barrier.
* ``async`` (:class:`AsyncScheduler`) — event-driven gossip on a virtual
  clock (the AD-PSGD family, Lian et al. 2018).  Each node's next event
  completes at ``t_next[i]``; every scanned step executes one event
  *cohort* (all nodes whose events land in the earliest time slice).  A
  fired node takes a local step, then gossip-averages against
  possibly-stale neighbor params — pairwise (one sampled partner,
  ``mixing.gossip_pair_avg``) or neighborhood (its whole W row through
  the sharing strategy) — and reschedules at
  ``t_next[i] += compute_time[i] + comm_time[i]``.  Staleness
  (event-count gap of the rows read), per-node virtual wall-clock, and
  event counts are traced scan outputs surfaced via
  :meth:`extra_metrics` into ``history`` / ``results.json``.

Activation masks are also owned here: iid per-node participation (the
original churn axis), **machine-correlated failures** (all nodes mapped
to a down machine drop together, ``DLConfig.churn_machines``), and the
rejoin-with-stale-model rule — a down node freezes its params/optimizer/
sharing state and re-enters with them (no silent reweight-away); under
``async`` its pending events burn their time slots while it is down.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import compression as compression_lib
from repro.core import faults as faults_lib
from repro.core.mixing import F32, ShardedDense, ShardedTopology, gossip_pair_avg
from repro.data.loader import node_batch_indices
from repro.core.sharing import (
    edge_reweight,
    edge_reweight_sparse,
    participation_deg_eff,
    participation_reweight,
    participation_reweight_rows,
    participation_reweight_sparse,
)
from repro.core.steps import node_where
from repro.core.topology import SparseTopology, gather_rows, sample_neighbor_slots
from repro.utils.pytree import tree_unvector, tree_vector
from repro.utils.spans import HostSpans

# cap on the pre-gathered (R, L, N, B, ...) batch stack; above it the scan
# falls back to gathering each round's batch inside the loop body.
_BATCH_STACK_BYTES_CAP = 256 * 1024 * 1024

# virtual-clock rebase threshold (cohort-path fp32 hygiene): once every
# pending event time exceeds this, the async scheduler subtracts a common
# fp32 shift from t_next/vclock on device and carries it in a float64 host
# offset.  fp32 *running maxima* over the clock are exact (max never
# rounds), but the clock itself advances by running sums — at t ~ 2^16 s
# the fp32 ulp is ~2^-7 s, so millisecond-scale event durations start to
# be absorbed; rebasing keeps the accumulating magnitudes small.  The
# threshold is far above any existing test horizon, so trajectories below
# it are untouched bitwise.
_REBASE_T_S = 65536.0

# selection='auto' switches the cohort path from the flat O(N) min+top_k
# selection to the hierarchical segment-minimum selection above this node
# count: below it the flat scan over t_next is already cheap next to the
# O(C·(d+1)·P) gossip, above it the O(N) selection layer starts to bind
# (the million-node regime the hierarchy exists for).
_HIER_AUTO_MIN_N = 1 << 18


@jax.jit
def stage_batches(x, y, idx):
    """A chunk's pre-gathered batches ``(x[idx], y[idx])`` as one program
    (``jit_stage_batches`` in a profile)."""
    return jnp.take(x, idx, axis=0), jnp.take(y, idx, axis=0)


def _live_edges(W, act):
    """Live off-diagonal edges of a mixing operand, pruned by a churn mask.

    Returns ``(live, gather)``: ``live`` is the {True} edge mask — (N, D)
    over neighbor slots for a ``SparseTopology``, (N, N) for a dense W —
    and ``gather(v)`` aligns a per-node (N,) vector with it (neighbor
    gather / row broadcast).  One derivation of edge liveness shared by
    the local scheduler's neighborhood barrier and the async scheduler's
    staleness accounting."""
    if isinstance(W, SparseTopology):
        live = W.w > 0
        if act is not None:
            live = live & (act[:, None] > 0) & (jnp.take(act, W.nbr, axis=0) > 0)
        return live, lambda v: jnp.take(v, W.nbr, axis=0)
    n = W.shape[0]
    live = W * (1.0 - jnp.eye(n, dtype=W.dtype)) > 0
    if act is not None:
        live = live & (act[:, None] > 0) & (act[None, :] > 0)
    return live, lambda v: jnp.broadcast_to(v[None, :], (n, n))


class Scheduler:
    """Base: host-side chunk staging + activation-mask machinery shared by
    every semantics.  ``eng`` is the owning RoundEngine — the scheduler
    reads its static resources (batcher, topology operands, steps) and
    writes its running metrics (bytes_sent, sim_time_s)."""

    semantics = "sync"

    def __init__(self, eng):
        self.eng = eng
        # 'node' batch keying: indices are a device-side pure function of
        # (seed, round, global id) — no host staging, no (R, L, N, B) stack
        self._node_keying = eng.dl.batch_keying == "node"
        # host-side float64 fault-counter totals (every scanned step emits
        # the static fstats schema; zeros when no fault axis is active)
        self._fault_totals = {k: 0.0 for k in faults_lib.STAT_KEYS}
        self._track_faults = eng.dl.faults is not None or (
            eng.dl.secure and eng.dl.secure_recovery
        )
        # host seconds by span: run_span > stage (> stage.batches,
        # stage.graphs), dispatch, sync; counts["run_span"] is the chunks
        self.host_s = HostSpans()

    # ------------------------------------------------------------------
    # activation masks (churn)
    # ------------------------------------------------------------------
    def participation_mask(self, start: int, n_rounds: int) -> np.ndarray:
        """(R, N) {0,1} activity masks for rounds [start, start+n_rounds).

        One batched counter-based draw (splitmix64 hash over (seed,
        absolute round, unit)) — each round's randomness is a pure function
        of its absolute index, so masks are chunk-boundary invariant, with
        no per-round ``default_rng`` host loop.  The draw unit is the node
        (iid churn) or, with ``churn_machines=M`` set, the *machine*: all
        nodes round-robin-mapped to a down machine drop together —
        correlated machine-level failures.  The final column holds each
        round's fallback draw: if every unit sampled down, one (uniform
        via that draw) is kept alive.
        """
        dl = self.eng.dl
        n = dl.n_nodes
        if dl.participation >= 1.0:
            return np.ones((n_rounds, n), np.float32)
        m_units = dl.churn_machines if dl.churn_machines > 0 else n
        with np.errstate(over="ignore"):  # uint64 wraparound is the point
            x = (
                np.uint64(dl.seed * 1_000_003 + 7_919)
                * np.uint64(0x9E3779B97F4A7C15)
                + np.arange(start, start + n_rounds, dtype=np.uint64)[:, None]
                * np.uint64(0xBF58476D1CE4E5B9)
                + np.arange(m_units + 1, dtype=np.uint64)[None, :]
                * np.uint64(0x94D049BB133111EB)
            )
            x ^= x >> np.uint64(30)
            x *= np.uint64(0xBF58476D1CE4E5B9)
            x ^= x >> np.uint64(27)
            x *= np.uint64(0x94D049BB133111EB)
            x ^= x >> np.uint64(31)
        u = (x >> np.uint64(11)).astype(np.float64) * (2.0 ** -53)
        up = u[:, :m_units] < dl.participation
        dead = ~up.any(1)
        if dead.any():  # keep at least one unit alive per round
            up[dead, (u[dead, m_units] * m_units).astype(np.int64)] = True
        if dl.churn_machines > 0:
            # broadcast machine up/down to its round-robin node set
            up = up[:, np.arange(n) % dl.churn_machines]
        return up.astype(np.float32)

    # ------------------------------------------------------------------
    # host-side chunk staging (shared)
    # ------------------------------------------------------------------
    def _stage_xs(self, start: int, n_rounds: int) -> Dict:
        """Per-round scan inputs for rounds [start, start+n_rounds): always
        ``rnd`` (R,) int32 and the chunk's batches — pre-gathered ``bx``/
        ``by`` under the byte cap, raw ``idx`` above it; plus ``mix`` for
        dynamic topologies ((R,N,N) W stack in dense mode, (R,N,D)
        SparseTopology stack in sparse mode) and ``act`` (R,N) with
        churn.  Runs as the ``stage`` span, whose ``bytes`` stat is what
        it copies from host to device."""
        eng = self.eng
        dl = eng.dl
        spans = self.host_s
        with spans.span("stage") as stage:
            rnd = np.arange(start, start + n_rounds, dtype=np.int32)
            staged = rnd.nbytes
            xs = {"rnd": jnp.asarray(rnd)}
            if not self._node_keying:
                with spans.span("stage.batches"):
                    idx = eng.batcher.chunk_indices(start, n_rounds, dl.local_steps)
                    staged += idx.nbytes
                    item_bytes = eng._dev_x.nbytes // max(eng._dev_x.shape[0], 1)
                    if idx.size * item_bytes <= _BATCH_STACK_BYTES_CAP:
                        # pre-stack the whole chunk's batches on device: one
                        # gather per chunk instead of one per scanned round
                        xs["bx"], xs["by"] = stage_batches(
                            eng._dev_x, eng._dev_y, jnp.asarray(idx)
                        )  # (R, L, N, B, ...)
                    else:
                        xs["idx"] = jnp.asarray(idx)
            # ('node' keying stages nothing: each scan step derives its rows'
            # indices from (rnd, id) in-body — see _node_indices)
            if eng.sampler is not None:
                with spans.span("stage.graphs"):
                    if eng.mix_mode == "sparse":
                        st = eng.sampler.sparse_stack(start, n_rounds)  # (R, N, D)
                        xs["mix"] = SparseTopology(
                            jnp.asarray(st.nbr), jnp.asarray(st.w),
                            jnp.asarray(st.w_self),
                        )
                        graph_bytes = st.stage_bytes()
                    else:
                        Wst = eng.sampler.weights_stack(start, n_rounds)  # (R, N, N)
                        xs["mix"] = jnp.asarray(Wst)
                        graph_bytes = int(Wst.nbytes)
                staged += graph_bytes
                eng.topo_stage_bytes_peak = max(eng.topo_stage_bytes_peak, graph_bytes)
            plan = dl.faults
            crashes = plan is not None and bool(plan.crashes)
            if dl.participation < 1.0 or crashes:
                m = self.participation_mask(start, n_rounds)
                if crashes:
                    # declarative crash/restart windows AND into the churn
                    # draw: a crashed node is exactly a churn-down node, but
                    # deterministic (both masks are pure functions of the
                    # absolute round, so chunking stays invariant)
                    cm = faults_lib.crash_mask(plan, dl.n_nodes, start, n_rounds)
                    m = m * cm
                    # crash downtime counts as injected faults absorbed by the
                    # participation machinery (frozen state, reweighted mixing)
                    down = float((1.0 - cm).sum())
                    self._fault_totals["faults_injected"] += down
                    self._fault_totals["faults_survived"] += down
                staged += m.nbytes
                xs["act"] = jnp.asarray(m)
            stage.set_metadata(bytes=staged)
        return xs

    def _node_indices(self, rnd, ids):
        """(L, |ids|, B) sample indices for the given global node ids under
        'node' keying — a traced pure function of (round, id), so a
        gathered cohort samples bitwise what the dense oracle samples."""
        eng = self.eng
        return node_batch_indices(
            eng._batch_key, rnd, ids, eng._dev_lens, eng._dev_parts_pad,
            eng.dl.local_steps, eng.dl.batch_size,
        )

    def _round_batch(self, xs_r):
        """One round's (L, N, B, ...) batches inside a scan body: the
        pre-gathered slice, an in-loop gather for oversized chunks, or an
        in-body derivation under 'node' keying."""
        if "bx" in xs_r:
            return xs_r["bx"], xs_r["by"]
        if self._node_keying:
            idx = self._node_indices(
                xs_r["rnd"], jnp.arange(self.eng.dl.n_nodes)
            )
        else:
            idx = xs_r["idx"]
        bx = jnp.take(self.eng._dev_x, idx, axis=0)
        by = jnp.take(self.eng._dev_y, idx, axis=0)
        return bx, by

    # ------------------------------------------------------------------
    def run_span(self, start: int, n_rounds: int) -> None:
        """Rounds [start, start+n_rounds) as one chunk: stage its inputs,
        dispatch the scanned program, then pull its per-round metrics to
        the host — the chunk's one host sync, which waits for the device.
        Each phase is a host span (``dl.stage``, ``dl.dispatch``,
        ``dl.sync``) inside ``dl.run_span``."""
        spans = self.host_s
        with spans.span("run_span", rnd=start):
            xs = self._stage_xs(start, n_rounds)
            with spans.span("dispatch"):
                metrics = self._dispatch(xs)
            with spans.span("sync"):
                self._pull(metrics)

    def _dispatch(self, xs):
        """Run the chunk on ``xs``, keep its end state; return its
        per-round metric outputs (still on the device)."""
        raise NotImplementedError

    def _pull(self, metrics) -> None:
        """Fold a chunk's metric outputs into the host totals."""
        raise NotImplementedError

    def run_legacy_round(self, rnd: int) -> None:
        raise ValueError(
            f"legacy per-round dispatch (chunk_rounds=0) supports "
            f"semantics='sync' only, not {self.semantics!r}"
        )

    def _accum_faults(self, fstats) -> None:
        """Fold one dispatch's fstats (dict of (R,) stacked arrays, or
        scalars from the legacy path) into the host float64 totals —
        only where a fault axis is active: nothing reads them otherwise,
        and each is a host pull."""
        if not self._track_faults:
            return
        for k in faults_lib.STAT_KEYS:
            self._fault_totals[k] += float(
                np.asarray(fstats[k], np.float64).sum()
            )

    def eval_params(self):
        """The params tree evaluation should run on.  Identity for every
        semantics except the quantized-cold async path, which stores
        ``eng.params`` compressed and decodes here."""
        return self.eng.params

    def extra_metrics(self) -> Dict:
        """Semantics-specific metrics merged into each history record.
        The base contributes the running fault counters whenever a fault
        axis (FaultPlan or secure recovery) is active."""
        if not self._track_faults:
            return {}
        t = self._fault_totals
        m = {k: int(round(t[k])) for k in faults_lib.STAT_KEYS
             if k != "recovery_bytes"}
        m["recovery_bytes"] = t["recovery_bytes"]
        return m


class SyncScheduler(Scheduler):
    """The synchronous round barrier — today's scanned chunk, verbatim:
    every node mixes each round, per-round simulated time is the max over
    nodes, and metrics accumulate as sums.  Also owns the legacy per-round
    dispatch and the node-sharded shard_map chunk."""

    semantics = "sync"

    def __init__(self, eng):
        super().__init__(eng)
        self._chunk_jit = jax.jit(self._chunk_fn)
        self._legacy_jit = jax.jit(self._legacy_round)
        self._shard_jit_cache: Dict = {}

    # -- scan bodies ----------------------------------------------------
    def _chunk_fn(self, params, opt_state, share_state, xs):
        """R rounds in one lax.scan.  ``xs`` is a dict of per-round scan
        inputs (see ``_stage_xs``); static topologies capture one
        device-constant mixing operand."""
        eng = self.eng

        def body(carry, xs_r):
            params, opt_state, share_state = carry
            W = xs_r["mix"] if "mix" in xs_r else eng._mix_static
            act = xs_r.get("act")
            bx, by = self._round_batch(xs_r)
            params, opt_state, share_state, nbytes, sim_t, fstats = (
                eng.steps.train_and_mix(
                    params, opt_state, share_state, bx, by, W, act, xs_r["rnd"]
                )
            )
            return (params, opt_state, share_state), (nbytes, sim_t, fstats)

        carry, (nbytes, times, fstats) = jax.lax.scan(
            body, (params, opt_state, share_state), xs
        )
        return carry + (nbytes, times, fstats)

    def _legacy_round(self, params, opt_state, share_state, bx, by, W, active, rnd):
        return self.eng.steps.train_and_mix(
            params, opt_state, share_state, bx, by, W, active, rnd
        )

    # -- node-sharded chunk (shard_map over the device mesh) -------------
    def _wrap_mix(self, mix):
        """Sharded mixing operand for one round inside the shard body.

        ``mix`` is the scanned per-round operand (this device's row block,
        cut by the in_specs) or None for static topologies — those capture
        the full replicated tables and slice the local block by device
        index, keeping the wrapper shapes identical either way."""
        eng = self.eng
        shard = eng._shard
        if mix is None:
            if eng.mix_mode == "sparse":
                st = eng._mix_static
                topo_l = SparseTopology(
                    shard.local(st.nbr), shard.local(st.w), shard.local(st.w_self)
                )
                return ShardedTopology(topo_l, shard, eng._perm_sched)
            return ShardedDense(shard.local(eng._mix_static), shard)
        if isinstance(mix, SparseTopology):
            return ShardedTopology(mix, shard, None)
        return ShardedDense(mix, shard)

    def _chunk_fn_sharded(self, params, opt_state, share_state, xs):
        """The scanned chunk, run inside shard_map: every node-stacked
        carry/input is this device's (B, ...) row block; gossip crosses
        devices through the sharded mixing operand (collective_permute
        slots or all-gather — see mixing.ShardedTopology) and the per-round
        scalar metrics are psum/pmax-reduced so each device returns the
        same global values."""
        eng = self.eng

        def body(carry, xs_r):
            params, opt_state, share_state = carry
            W = self._wrap_mix(xs_r.get("mix"))
            act = xs_r.get("act")
            bx, by = self._round_batch(xs_r)
            params, opt_state, share_state, nbytes, sim_t, fstats = (
                eng.steps.train_and_mix(
                    params, opt_state, share_state, bx, by, W, act, xs_r["rnd"],
                    shard=eng._shard,
                )
            )
            return (params, opt_state, share_state), (nbytes, sim_t, fstats)

        carry, (nbytes, times, fstats) = jax.lax.scan(
            body, (params, opt_state, share_state), xs
        )
        return carry + (nbytes, times, fstats)

    def _xs_pspec(self, xs):
        """Per-leaf PartitionSpecs for the scan-input dict: the node axis of
        every leaf maps to the mesh 'nodes' axis, everything else is
        replicated."""

        def spec(path, leaf):
            key = path[0].key
            if key == "rnd":
                return P()
            if key in ("bx", "by", "idx"):  # (R, L, N, B, ...)
                return P(None, None, "nodes", *((None,) * (leaf.ndim - 3)))
            if key == "act":                # (R, N)
                return P(None, "nodes")
            if key == "mix":                # (R, N, N) W or (R, N, D)/(R, N) tables
                return P(None, "nodes", *((None,) * (leaf.ndim - 2)))
            raise KeyError(f"unknown scan input {key!r}")

        return jax.tree_util.tree_map_with_path(spec, xs)

    def _node_pspec(self, tree):
        return jax.tree_util.tree_map(
            lambda l: P("nodes", *((None,) * (l.ndim - 1))), tree
        )

    def _sharded_chunk_call(self, xs):
        """shard_map-wrap + jit the chunk for this xs structure (cached —
        structures recur: full chunks and the pre-eval remainder)."""
        eng = self.eng
        leaves, treedef = jax.tree_util.tree_flatten(xs)
        key = (treedef, tuple(l.ndim for l in leaves))
        fn = self._shard_jit_cache.get(key)
        if fn is None:
            state_specs = (
                self._node_pspec(eng.params),
                self._node_pspec(eng.opt_state),
                self._node_pspec(eng.share_state),
            )
            # fstats scalars are replicated by construction (either zeros
            # or psum-reduced, like nbytes/times)
            fstats_specs = {k: P() for k in faults_lib.STAT_KEYS}
            fn = jax.jit(
                shard_map(
                    self._chunk_fn_sharded,
                    mesh=eng._mesh,
                    in_specs=state_specs + (self._xs_pspec(xs),),
                    out_specs=state_specs + (P(), P(), fstats_specs),
                    check_vma=False,
                )
            )
            self._shard_jit_cache[key] = fn
        return fn(eng.params, eng.opt_state, eng.share_state, xs)

    # -- host-side dispatch ----------------------------------------------
    def _dispatch(self, xs):
        eng = self.eng
        if eng.sharded:
            out = self._sharded_chunk_call(xs)
        else:
            out = self._chunk_jit(eng.params, eng.opt_state, eng.share_state, xs)
        eng.params, eng.opt_state, eng.share_state = out[:3]
        return out[3:]

    def _pull(self, metrics) -> None:
        # ONE host sync per chunk for all per-round metrics
        eng = self.eng
        nbytes, times, fstats = metrics
        eng.bytes_sent += float(np.asarray(nbytes, np.float64).sum())
        eng.sim_time_s += float(np.asarray(times, np.float64).sum())
        self._accum_faults(fstats)

    def _round_mix(self, rnd: int):
        """Device mixing operand for one round (legacy per-round dispatch):
        dense (N, N) W or SparseTopology neighbor tables, matching the mode
        the scanned path uses so both execute the identical workload."""
        eng = self.eng
        if eng.sampler is None:
            return eng._mix_static
        if eng.mix_mode == "sparse":
            t = eng.sampler.round_table(rnd)
            return SparseTopology(
                jnp.asarray(t.nbr), jnp.asarray(t.w), jnp.asarray(t.w_self)
            )
        return jnp.asarray(eng.sampler.round_weights(rnd).astype(np.float32))

    def run_legacy_round(self, rnd: int) -> None:
        """Per-round dispatch baseline: host-gathered full batches, one jit
        call and one metric sync per round.  Samples the same round_indices
        as the scanned path so both execute the identical workload."""
        eng = self.eng
        dl = eng.dl
        idx = eng.batcher.round_indices(rnd, dl.local_steps)  # (L, N, B)
        bx = jnp.asarray(eng.batcher.x[idx])
        by = jnp.asarray(eng.batcher.y[idx])
        W = self._round_mix(rnd)
        act = (
            jnp.asarray(self.participation_mask(rnd, 1)[0])
            if dl.participation < 1.0 else None
        )
        out = self._legacy_jit(
            eng.params, eng.opt_state, eng.share_state, bx, by, W, act,
            jnp.int32(rnd),
        )
        eng.params, eng.opt_state, eng.share_state, nbytes, sim_t, fstats = out
        eng.bytes_sent += float(nbytes)
        eng.sim_time_s += float(sim_t)
        self._accum_faults(fstats)


class LocalScheduler(Scheduler):
    """Neighborhood-barrier semantics: trajectories identical to sync (the
    mixing math is untouched), but each node runs on its own virtual
    clock — node i starts round r once it and its *live neighbors* have
    finished round r-1 (a gossip exchange needs both endpoints), then adds
    its own compute+comm time.  No global barrier: stragglers only delay
    their graph neighborhood, so the simulated experiment time (max final
    clock) lower-bounds sync's ``sum of per-round maxima``.  Down (churn)
    nodes stall their clock and rejoin where they left off."""

    semantics = "local"

    def __init__(self, eng):
        super().__init__(eng)
        self._clock = jnp.zeros((eng.dl.n_nodes,), jnp.float32)
        self._chunk_jit = jax.jit(self._chunk_fn)

    def _nbr_clock_max(self, W, act, clock):
        """Per-node max of live-neighbor clocks (-inf when none)."""
        live, gather = _live_edges(W, act)
        return jnp.max(jnp.where(live, gather(clock), -jnp.inf), axis=1)

    def _chunk_fn(self, params, opt_state, share_state, clock, xs):
        eng = self.eng

        def body(carry, xs_r):
            params, opt_state, share_state, clock = carry
            W = xs_r["mix"] if "mix" in xs_r else eng._mix_static
            act = xs_r.get("act")
            bx, by = self._round_batch(xs_r)
            params, opt_state, share_state, nbytes, node_t, fstats = (
                eng.steps.train_and_mix(
                    params, opt_state, share_state, bx, by, W, act, xs_r["rnd"],
                    time_reduce="none",
                )
            )
            # neighborhood barrier: wait for the live neighbors' previous
            # round, then run this one (node_t is 0 for down nodes, whose
            # clocks stall until they rejoin)
            ready = jnp.maximum(clock, self._nbr_clock_max(W, act, clock))
            if act is not None:
                clock = jnp.where(act > 0, ready + node_t, clock)
            else:
                clock = ready + node_t
            return (params, opt_state, share_state, clock), (
                nbytes, jnp.max(clock), fstats
            )

        carry, (nbytes, times, fstats) = jax.lax.scan(
            body, (params, opt_state, share_state, clock), xs
        )
        return carry + (nbytes, times, fstats)

    def _dispatch(self, xs):
        eng = self.eng
        out = self._chunk_jit(
            eng.params, eng.opt_state, eng.share_state, self._clock, xs
        )
        eng.params, eng.opt_state, eng.share_state, self._clock = out[:4]
        return out[4:]

    def _pull(self, metrics) -> None:
        eng = self.eng
        nbytes, times, fstats = metrics
        eng.bytes_sent += float(np.asarray(nbytes, np.float64).sum())
        # the virtual clock is a running maximum, not a per-round sum
        eng.sim_time_s = float(np.asarray(times)[-1])
        self._accum_faults(fstats)

    def extra_metrics(self) -> Dict:
        clock = np.asarray(self._clock, np.float64)
        return {
            "semantics": "local",
            "vclock_min_s": float(clock.min()),
            "vclock_median_s": float(np.median(clock)),
            "vclock_max_s": float(clock.max()),
            **super().extra_metrics(),
        }


class AsyncScheduler(Scheduler):
    """Event-driven asynchronous gossip on a virtual clock (AD-PSGD
    family).  One scanned step = one event *cohort*: the nodes whose next
    event completes inside the earliest ``async_slice_s`` window all fire
    — each takes a local step on that cohort's batch row, gossips against
    possibly-stale neighbor rows, and reschedules its next event at
    ``+compute_time[i] + comm_time[i]`` on its own clock.  Nodes with
    equal event durations therefore stay in lockstep cohorts (with
    homogeneous times and full participation, every cohort is exactly one
    synchronous round — the reduction the equivalence tests pin), while a
    10x straggler fires ~10x fewer events per unit of virtual time.

    Gossip forms (``DLConfig.async_gossip``):

    * ``"neighborhood"`` — the fired node reads its whole (churn-pruned) W
      row through the configured sharing strategy; non-fired rows are
      frozen (one-sided read, no write conflicts).
    * ``"pairwise"`` — classic AD-PSGD: one uniformly-sampled partner per
      event (``topology.sample_neighbor_slots``), ``x_i' = (x_i+x_j)/2``;
      a sampled partner that is churn-down blocks the exchange (the node
      keeps its local step and retries at its next event).

    Down (churn) nodes burn their event slots — virtual time passes, no
    work happens, params freeze — and rejoin with their stale model.
    Traced per-cohort outputs: bytes, the cohort's virtual time (max
    completion among fired events), fired-event count, and the staleness
    (event-count gap receiver-minus-sender over the rows read) sum/max —
    aggregated into :meth:`extra_metrics` for ``history``/results.

    **Population-scale cohort activation** (``DLConfig.cohort_capacity=C``
    > 0): each scanned step selects the top-C earliest-``t_next`` nodes
    inside the time slice (ties by lowest id), **gathers** only those C
    rows of params/opt state plus their neighbor rows from the padded
    ``SparseTopology`` table, runs the identical local-step + one-sided
    gossip on the (C, ...) slice, and **scatters** the results back into
    the cold device-resident (N, ...) population state — O(C·(d+1)·P) per
    event step instead of O(N·P).  In-slice nodes beyond capacity are
    *overflow-carried*: their ``t_next`` is untouched, so they stay inside
    the (monotone) next slice and fire in earliest-deadline order — no
    event is dropped, only deferred (which is when timing semantics can
    differ from the dense oracle; with C >= every fire-count the
    trajectory is the dense one, property-tested).  Per-step cohort
    occupancy and overflow counts are traced outputs.

    Accumulator hygiene at population scale: host-side event totals
    accumulate as Python ints / int64 (int32 wraps at ~2.1e9 events —
    hours of a 100k-node run); ``sim_time_s`` and the vclock metrics are
    fp32 running *maxima* of the device clock, which are exact (max
    selects, never rounds — unlike sums, which lose ulps at every add),
    plus the float64 ``_t_offset`` rebase carry (see ``_REBASE_T_S``).
    """

    semantics = "async"

    def __init__(self, eng):
        super().__init__(eng)
        n = eng.dl.n_nodes
        # completion time of each node's next local step (first event =
        # one local compute; each event's comm delays the one after it)
        self._t_next = jnp.asarray(eng._compute_node, jnp.float32)
        self._vclock = jnp.zeros((n,), jnp.float32)   # last fired completion
        self._events = jnp.zeros((n,), jnp.int32)     # model version counter
        # consecutive failed pairwise exchanges (drives the exponential
        # backoff under a FaultPlan; stays all-zero without one)
        self._retries = jnp.zeros((n,), jnp.int32)
        self._stale_sum = 0.0
        self._stale_n = 0.0
        self._stale_max = 0.0
        self._fired_total = 0          # int: exact at any population scale
        self._t_offset = 0.0           # float64 rebase carry (virtual secs)
        self._cohort_c = int(eng.dl.cohort_capacity)
        self._occ_sum = 0.0
        self._occ_steps = 0
        self._overflow_total = 0
        # --- cohort selection layer (flat oracle vs segment-min hierarchy)
        sel = eng.dl.selection
        if sel == "auto":
            sel = "hier" if (
                self._cohort_c > 0 and n >= _HIER_AUTO_MIN_N
            ) else "flat"
        self._selection = sel
        self._fallback_total = 0
        if sel == "hier":
            seg = int(eng.dl.segment_size)
            if seg <= 0:
                # minimize the per-step selection cost S + C·seg + K·seg
                # (segment scan + seg_min refresh + union gather):
                # seg ~ sqrt(N/C), clamped to sane block sizes
                seg = int(np.clip(
                    round(np.sqrt(n / max(self._cohort_c, 1))), 4, 128
                ))
            self._seg = min(seg, n)
            self._n_seg = -(-n // self._seg)
            # candidate segments per step: at least C, because under
            # uncorrelated (continuous heterogeneous) event times the
            # in-slice nodes land in ~one segment each, plus twice what
            # a cohort of dense segments needs for the clustered/tied
            # case; a slice spanning more segments than this falls back
            # to the flat oracle inside the step (counted in
            # selection_fallback_total).  Union size stays K*seg ~
            # sqrt(N*C) at the auto segment size — sublinear in N.
            self._seg_k = min(
                self._n_seg,
                max(self._cohort_c,
                    2 * (-(-self._cohort_c // self._seg)), 8),
            )
            self._seg_min = self._build_seg_min(self._t_next)
        else:
            self._seg = self._n_seg = self._seg_k = 0
            self._seg_min = None
        # --- cold population storage (DLConfig.cold_dtype) ----------------
        # the (N, P) params / opt moments live compressed; every cohort
        # gather decodes C rows to fp32 and every scatter re-encodes them
        self._cold_dtype = eng.dl.cold_dtype
        if self._cold_dtype != "fp32":
            eng.params = compression_lib.encode_cold(
                eng.params, self._cold_dtype
            )
            eng.opt_state = compression_lib.encode_cold(
                eng.opt_state, self._cold_dtype
            )
        self._chunk_jit = jax.jit(self._chunk_fn)

    def eval_params(self):
        return compression_lib.decode_cold(self.eng.params, self._cold_dtype)

    # -- hierarchical selection state -------------------------------------
    def _build_seg_min(self, t_next):
        """(S,) exact per-segment minima of ``t_next`` — the carried
        selection index.  O(N); init/rebase only (the scan body refreshes
        just the segments its scatter touched)."""
        n = self.eng.dl.n_nodes
        seg, S = self._seg, self._n_seg
        rows = (
            jnp.arange(S, dtype=jnp.int32)[:, None] * seg
            + jnp.arange(seg, dtype=jnp.int32)[None, :]
        )
        vals = jnp.where(
            rows < n, jnp.take(t_next, jnp.minimum(rows, n - 1)), jnp.inf
        )
        return jnp.min(vals, axis=1)

    # -- traced cohort helpers -------------------------------------------
    def _pair_comm(self, partner, ok, rows=None):
        """Per-event comm seconds of a pairwise exchange (one message of
        the full parameter vector from the sampled partner).  ``rows``
        overrides the receiver ids for a gathered cohort (defaults to
        arange — the full node axis)."""
        eng = self.eng
        if eng.steps.lat is None:
            return jnp.zeros_like(ok)
        rows = jnp.arange(partner.shape[0]) if rows is None else rows
        nbytes = eng.n_params * jnp.dtype(jnp.float32).itemsize
        t = (
            eng.steps.lat[rows, partner]
            + nbytes * 8.0 / eng.steps.goodput[rows, partner]
        )
        return ok * t

    def _cohort(self, carry, xs_r):
        eng = self.eng
        dl = eng.dl
        plan = eng.steps.faults
        params, opt_state, share_state, t_next, vclock, events, retries = carry
        W = xs_r["mix"] if "mix" in xs_r else eng._mix_static
        act = xs_r.get("act")
        rnd = xs_r["rnd"]
        fstats = faults_lib.zero_stats()
        guard = plan is not None and plan.corrupt_prob > 0
        if guard:
            snap = (params, opt_state)  # last-good snapshot for rollbacks
        # --- cohort membership on the virtual clock ----------------------
        t_min = jnp.min(t_next)
        fire = (t_next <= t_min + dl.async_slice_s).astype(jnp.float32)
        actv = fire * act if act is not None else fire  # fired AND up
        # --- local step (down/unfired nodes frozen) ----------------------
        bx, by = self._round_batch(xs_r)
        params, opt_state = eng.steps.local_train(
            params, opt_state, bx, by, actv
        )
        X = jax.vmap(tree_vector)(params)
        key = jax.random.fold_in(eng.steps.base_key, rnd)
        ev_f = events.astype(jnp.float32)
        backoff = None
        if dl.async_gossip == "pairwise":
            X2, partner, ok = gossip_pair_avg(W, X, key, fire=actv, act=act)
            share_state_new = share_state
            ok_eff = ok
            comm = self._pair_comm(partner, ok)
            if plan is not None and plan.edge_faults:
                # one exchange per event: per-(round, node) loss/spike draws
                lv, sp = faults_lib.edge_draws(
                    eng.steps.fault_key, rnd, jnp.arange(dl.n_nodes), 1, plan
                )
                live, spike = lv[:, 0], sp[:, 0]
                lost = ok * (1.0 - live)        # exchange hit a dead edge
                ok_eff = ok * live
                X2 = jnp.where(lost[:, None] > 0, X, X2)  # keep local step
                spiked = ok * spike
                comm = comm * (1.0 + spike * (plan.latency_spike_factor - 1.0))
                # retry at the next event, after an exponential backoff on
                # this node's virtual clock (capped) — the same policy the
                # real-network runtime sleeps on the wall clock
                backoff = lost * faults_lib.retry_backoff_delay(
                    retries, plan.retry_backoff_s, plan.retry_backoff_cap
                )
                recovered = ok_eff * (retries > 0).astype(jnp.float32)
                retries = jnp.where(
                    lost > 0, retries + 1,
                    jnp.where(ok_eff > 0, 0, retries),
                )
                fstats["faults_injected"] += jnp.sum(lost) + jnp.sum(spiked)
                fstats["faults_detected"] += jnp.sum(lost)
                fstats["faults_survived"] += jnp.sum(spiked)
                fstats["faults_recovered"] += jnp.sum(recovered)
                fstats["retry_total"] += jnp.sum(lost)
            stale_i = ok_eff * jnp.maximum(ev_f - jnp.take(ev_f, partner), 0.0)
            n_reads = ok_eff
            msg = jnp.float32(eng.n_params * np.dtype(np.float32).itemsize)
            # bytes at pre-loss ok: the sender transmitted either way
            nbytes = jnp.sum(ok) * msg / dl.n_nodes
        else:  # neighborhood: the full (churn-pruned) W row, stale reads
            if act is not None:
                if isinstance(W, SparseTopology):
                    Wm, deg_eff = participation_reweight_sparse(W, act)
                else:
                    Wm, deg_eff = participation_reweight(W, act)
            else:
                Wm, deg_eff = W, eng.steps.mean_degree
            # message-level edge faults: the mixing operand drops lost
            # edges (renormalized — survived by design) while bytes/time
            # still run on the churn-level operand, like the sync path
            Wm_mix, lat_mult = Wm, None
            if plan is not None and plan.edge_faults:
                if isinstance(Wm, SparseTopology):
                    lv, sp = faults_lib.edge_draws(
                        eng.steps.fault_key, rnd,
                        jnp.arange(Wm.nbr.shape[0]), Wm.nbr.shape[1], plan,
                    )
                    sent = (Wm.w > 0).astype(jnp.float32)
                    Wm_mix = edge_reweight_sparse(Wm, lv)
                else:
                    n = Wm.shape[0]
                    lv, sp = faults_lib.edge_draws(
                        eng.steps.fault_key, rnd, jnp.arange(n), n, plan
                    )
                    sent = (
                        Wm * (1.0 - jnp.eye(n, dtype=jnp.float32)) > 0
                    ).astype(jnp.float32)
                    Wm_mix = edge_reweight(Wm, lv)
                dropped = jnp.sum(sent * (1.0 - lv))
                spiked = jnp.sum(sent * sp)
                if plan.latency_spike_prob > 0:
                    lat_mult = 1.0 + sp * (plan.latency_spike_factor - 1.0)
                fstats["faults_injected"] += dropped + spiked
                fstats["faults_survived"] += dropped + spiked
            X2_all, share_state_new, nbytes_rate = eng.sharing.round(
                X, Wm_mix, share_state, key, degree=deg_eff, rnd=rnd
            )
            X2 = jnp.where(actv[:, None] > 0, X2_all, X)
            # staleness over the rows actually read: the same live-edge
            # derivation the local scheduler's barrier uses (the churn
            # reweight above zeroes exactly these down-endpoint slots)
            live_b, gather = _live_edges(W, act)
            live = live_b.astype(jnp.float32)
            gap = jnp.maximum(ev_f[:, None] - gather(ev_f), 0.0)
            cnt = jnp.maximum(live.sum(1), 1.0)
            stale_i = actv * (live * gap).sum(1) / cnt
            n_reads = actv
            # only fired nodes' exchanges hit the wire this cohort
            nbytes = jnp.asarray(nbytes_rate, jnp.float32) * jnp.sum(actv) / dl.n_nodes
            if eng.steps.lat is not None:
                comm = eng.steps.round_time(
                    Wm, None, jnp.asarray(nbytes_rate, jnp.float32), deg_eff,
                    reduce="none", lat_mult=lat_mult,
                )
                comm = comm - eng.steps.compute_node  # compute added below
            else:
                comm = jnp.zeros((dl.n_nodes,), jnp.float32)
        # --- payload corruption + rollback guard --------------------------
        actv_w = actv  # state-write mask (excludes rolled-back rows)
        if guard:
            cmask = actv * faults_lib.corruption_mask(
                eng.steps.fault_key, rnd, jnp.arange(dl.n_nodes), plan
            )
            X2 = faults_lib.corrupt_rows(X2, cmask, plan.corrupt_mode)
            bad = actv * faults_lib.nonfinite_rows(X2)
            actv_w = actv * (1.0 - bad)
            fstats["faults_injected"] += jnp.sum(cmask)
            fstats["faults_detected"] += jnp.sum(bad)
            fstats["faults_recovered"] += jnp.sum(bad)
        share_state = node_where(actv_w, share_state_new, share_state)
        new_params = jax.vmap(lambda v: tree_unvector(v, eng.template))(
            X2.astype(X.dtype)
        )
        params = node_where(actv_w, new_params, params)
        if guard:
            # rolled-back rows discard the local step too: back to the
            # last-good (start-of-event) snapshot
            p0, o0 = snap
            params = node_where(1.0 - bad, params, p0)
            opt_state = node_where(1.0 - bad, opt_state, o0)
        # --- clock advance ------------------------------------------------
        dur = eng.steps.compute_node + comm
        if backoff is not None:
            dur = dur + backoff
        vclock = jnp.where(fire > 0, t_next, vclock)
        t_next = t_next + fire * dur  # down-but-scheduled slots burn time too
        events = events + actv_w.astype(jnp.int32)
        out = (
            nbytes,
            jnp.max(vclock),
            jnp.sum(actv),
            jnp.sum(stale_i),
            jnp.sum(n_reads),
            jnp.max(stale_i),
            fstats,
        )
        return (
            params, opt_state, share_state, t_next, vclock, events, retries
        ), out

    # -- traced cohort selection ------------------------------------------
    def _select_flat(self, t_next, t_min=None):
        """The flat selection oracle: top-C earliest ``t_next`` inside the
        slice over the full (N,) clock — O(N) per step.  Returns
        ``(cids, cmask, occupancy, overflow)`` with ``cids`` sorted
        ascending."""
        dl = self.eng.dl
        C = self._cohort_c
        if t_min is None:
            t_min = jnp.min(t_next)
        in_slice = t_next <= t_min + dl.async_slice_s
        neg, cand = jax.lax.top_k(jnp.where(in_slice, -t_next, -jnp.inf), C)
        pad = jnp.isfinite(neg).astype(jnp.float32)    # (C,) real-vs-pad
        occupancy = jnp.sum(pad)
        overflow = (
            jnp.sum(in_slice.astype(jnp.int32)) - occupancy.astype(jnp.int32)
        )
        cids, cmask = jax.lax.sort_key_val(cand, pad)  # ascending ids
        return cids, cmask, occupancy, overflow

    def _select_hier(self, t_next, seg_min):
        """Hierarchical segment-min selection: pick the K earliest-min
        segments from the carried (S,) ``seg_min``, gather their (K·seg,)
        clock union, and run the slice mask + ``top_k`` inside it — no
        O(N) op on the step.  Exactness: ``min(seg_min) == min(t_next)``
        (each entry is an exact fp32 min), and whenever every in-slice
        segment is among the top K (the ``covered`` predicate), the
        union's masked candidate set equals the flat oracle's and the
        union rows ascend in global id (segments sorted, rows contiguous),
        so ``top_k`` reproduces the flat pick *and* its lowest-id
        tie-break bitwise.  Slices spanning more than K segments take a
        ``lax.cond`` branch into :meth:`_select_flat` (rare; counted).
        Capacity-padding slots may carry out-of-range ids (the union's
        tail rows past N): gathers clip them and scatters drop them, the
        same masked no-op contract in-range pad ids already satisfy."""
        dl = self.eng.dl
        C = self._cohort_c
        n = dl.n_nodes
        seg, K = self._seg, self._seg_k
        t_min = jnp.min(seg_min)
        theta = t_min + dl.async_slice_s
        covered = jnp.sum((seg_min <= theta).astype(jnp.int32)) <= K

        def hier_branch(operand):
            t_next, seg_min = operand
            _, seg_sel = jax.lax.top_k(-seg_min, K)
            seg_sel = jnp.sort(seg_sel)        # union rows ascend globally
            rows = (
                seg_sel[:, None] * seg
                + jnp.arange(seg, dtype=seg_sel.dtype)[None, :]
            ).reshape(-1)                      # (K·seg,) global ids
            u_t = jnp.where(
                rows < n, jnp.take(t_next, jnp.minimum(rows, n - 1)), jnp.inf
            )
            in_sl = u_t <= theta
            neg, pos = jax.lax.top_k(jnp.where(in_sl, -u_t, -jnp.inf), C)
            pad = jnp.isfinite(neg).astype(jnp.float32)
            occupancy = jnp.sum(pad)
            overflow = (
                jnp.sum(in_sl.astype(jnp.int32)) - occupancy.astype(jnp.int32)
            )
            cand = jnp.take(rows, pos).astype(jnp.int32)
            cids, cmask = jax.lax.sort_key_val(cand, pad)
            return cids, cmask, occupancy, overflow

        def flat_branch(operand):
            t_next, _ = operand
            return self._select_flat(t_next, t_min=t_min)

        cids, cmask, occupancy, overflow = jax.lax.cond(
            covered, hier_branch, flat_branch, (t_next, seg_min)
        )
        return cids, cmask, occupancy, overflow, 1 - covered.astype(jnp.int32)

    def _cohort_gs(self, carry, xs_r):
        """Population-scale cohort body: the semantics of :meth:`_cohort`
        executed on a gathered (C, ...) hot set.  Selection is top-C
        earliest ``t_next`` inside the slice (ties by lowest id — the
        ``lax.top_k`` tie-break), either flat over the (N,) clock
        (:meth:`_select_flat`, the oracle) or through the carried
        segment-minimum hierarchy (:meth:`_select_hier`, bitwise the same
        cohort with no O(N) op); unselected in-slice nodes keep their
        ``t_next`` untouched (overflow-carry: the slice window is
        monotone, so they remain inside the next one and fire in
        earliest-deadline order).  Under a compressed ``cold_dtype`` the
        cold population rows decode to fp32 at the gather and re-encode
        at the scatter below.  Capacity padding slots carry
        ``cmask=0``: their gathered rows run through the same masked ops
        as churn-down nodes and scatter back bit-unchanged.  The dense
        oracle reads post-local-step rows of same-step peers, so neighbor
        reads resolve through a slot map — rows inside this cohort read
        the fresh (C, P) slice, rows outside read the cold population —
        which keeps the trajectory bitwise without a second (C, P)
        scatter on the hot path.  Cohort ids are re-sorted ascending
        after selection (membership, per-row math and the scattered
        state are order-invariant) so every gather/scatter below runs
        with sorted unique indices."""
        eng = self.eng
        dl = eng.dl
        C = self._cohort_c
        cold = self._cold_dtype
        hier = self._selection == "hier"
        if hier:
            (params, opt_state, share_state, t_next, vclock, events, vmax,
             seg_min) = carry
        else:
            params, opt_state, share_state, t_next, vclock, events, vmax = carry
        W = xs_r["mix"] if "mix" in xs_r else eng._mix_static
        act = xs_r.get("act")
        rnd = xs_r["rnd"]
        # --- cohort selection on the virtual clock ------------------------
        if hier:
            cids, cmask, occupancy, overflow, fb = self._select_hier(
                t_next, seg_min
            )
        else:
            cids, cmask, occupancy, overflow = self._select_flat(t_next)
            fb = jnp.int32(0)

        def take_rows(tree):
            return jax.tree_util.tree_map(
                lambda a: jnp.take(a, cids, axis=0), tree
            )

        def put_rows(tree, sub):
            return jax.tree_util.tree_map(
                lambda a, s: a.at[cids].set(
                    s, indices_are_sorted=True, unique_indices=True
                ),
                tree, sub,
            )

        # global id -> cohort slot (-1 outside): how neighbor/partner reads
        # find this step's fresh rows without scattering them first.  A
        # sorted-membership probe on the (C,) sorted cids — O(M·log C) per
        # M-row lookup, replacing the former full-(N,) scatter map
        def slot_lookup(ids):
            pos = jnp.minimum(
                jnp.searchsorted(cids, ids).astype(jnp.int32), C - 1
            )
            return jnp.where(jnp.take(cids, pos) == ids, pos, -1)

        act_c = jnp.take(act, cids) if act is not None else None
        actv_c = cmask * act_c if act is not None else cmask  # fired AND up
        # --- local step on the hot slice ----------------------------------
        # gathered rows decode to fp32 (identity under cold_dtype='fp32');
        # the encoded gather is kept so masked rows scatter back bit-exact
        enc_p, enc_o = take_rows(params), take_rows(opt_state)
        p_c = compression_lib.decode_cold(enc_p, cold)
        o_c = compression_lib.decode_cold(enc_o, cold)
        idx_c = self._node_indices(rnd, cids)                 # (L, C, B)
        bx = jnp.take(eng._dev_x, idx_c, axis=0)
        by = jnp.take(eng._dev_y, idx_c, axis=0)
        p_c, o_c = eng.steps.local_train(p_c, o_c, bx, by, actv_c, rows=cids)
        X_c = jax.vmap(tree_vector)(p_c)                      # (C, P)

        def fresh_rows(ids, X_cold):
            """Post-local-step values for global ``ids``: the fresh hot
            slice where ``ids`` is in this cohort, ``X_cold`` otherwise."""
            s = slot_lookup(ids)
            X_f = jnp.take(X_c, jnp.clip(s, 0), axis=0)
            return jnp.where((s >= 0)[..., None], X_f, X_cold)

        key = jax.random.fold_in(eng.steps.base_key, rnd)
        # event counters are gathered as int32 and widened after the
        # gather — an O(N) astype per step would rival the gossip itself
        ev_c = jnp.take(events, cids).astype(jnp.float32)
        topo_c = gather_rows(W, cids)                         # (C, D) view
        if dl.async_gossip == "pairwise":
            slot = sample_neighbor_slots(key, topo_c, rows=cids)
            partner = jnp.take_along_axis(topo_c.nbr, slot[:, None], axis=1)[:, 0]
            ok = actv_c
            if act is not None:
                ok = ok * jnp.take(act, partner)
            p_partner = compression_lib.decode_cold(
                jax.tree_util.tree_map(
                    lambda a: jnp.take(a, partner, axis=0), params
                ),
                cold,
            )
            X_p = fresh_rows(partner, jax.vmap(tree_vector)(p_partner))
            X2_c = jnp.where(ok[:, None] > 0, 0.5 * (X_c + X_p), X_c)
            stale_c = ok * jnp.maximum(
                ev_c - jnp.take(events, partner).astype(jnp.float32), 0.0
            )
            n_reads_c = ok
            msg = jnp.float32(eng.n_params * np.dtype(np.float32).itemsize)
            nbytes = jnp.sum(ok) * msg / dl.n_nodes
            comm = self._pair_comm(partner, ok, rows=cids)
        else:  # neighborhood: the gathered (churn-pruned) W rows
            if act is not None:
                Wm_c = participation_reweight_rows(topo_c, act, cids)
                deg_eff = participation_deg_eff(W, act)
            else:
                Wm_c, deg_eff = topo_c, eng.steps.mean_degree
            nbr_flat = Wm_c.nbr.reshape(-1)                   # (C·D,)
            p_n = compression_lib.decode_cold(
                jax.tree_util.tree_map(
                    lambda a: jnp.take(a, nbr_flat, axis=0), params
                ),
                cold,
            )
            Xn = fresh_rows(nbr_flat, jax.vmap(tree_vector)(p_n)).reshape(
                X_c.shape[0], -1, X_c.shape[1]
            )                                                  # (C, D, P)
            mixed = jnp.einsum("cd,cdp->cp", Wm_c.w.astype(jnp.float32), Xn,
                               precision=F32)
            X2_all = Wm_c.w_self.astype(jnp.float32)[:, None] * X_c + mixed
            X2_c = jnp.where(actv_c[:, None] > 0, X2_all, X_c)
            live_c = topo_c.w > 0
            if act is not None:
                live_c = live_c & (act_c[:, None] > 0) & (
                    jnp.take(act, topo_c.nbr, axis=0) > 0
                )
            live = live_c.astype(jnp.float32)
            gap = jnp.maximum(
                ev_c[:, None]
                - jnp.take(events, topo_c.nbr, axis=0).astype(jnp.float32),
                0.0,
            )
            cnt = jnp.maximum(live.sum(1), 1.0)
            stale_c = actv_c * (live * gap).sum(1) / cnt
            n_reads_c = actv_c
            nbytes_rate = jnp.asarray(
                deg_eff * X_c.shape[1] * jnp.dtype(X_c.dtype).itemsize,
                jnp.float32,
            )
            nbytes = nbytes_rate * jnp.sum(actv_c) / dl.n_nodes
            if eng.steps.lat is not None:
                comm = eng.steps.cohort_comm_time(
                    cids, Wm_c.nbr, (Wm_c.w > 0).astype(jnp.float32),
                    nbytes_rate, deg_eff,
                )
            else:
                comm = jnp.zeros((C,), jnp.float32)
        # (share_state is untouched: semantics='async' is validated to
        # full sharing, whose state is the empty pytree)
        p2_c = jax.vmap(lambda v: tree_unvector(v, eng.template))(
            X2_c.astype(X_c.dtype)
        )
        p2_c = node_where(actv_c, p2_c, p_c)
        # the one (C, P)-scale scatter of the step: post-mix params (which
        # are the post-local params on masked rows) and opt state together.
        # Compressed cold rows re-encode first, and masked rows scatter
        # the *original* encoded gather back — int8 re-encode wobbles the
        # per-row scale by ulps, so untouched rows stay bit-exact by
        # construction, not by codec luck
        if cold == "fp32":
            params = put_rows(params, p2_c)
            opt_state = put_rows(opt_state, o_c)
        else:
            params = put_rows(params, node_where(
                actv_c, compression_lib.encode_cold(p2_c, cold), enc_p
            ))
            opt_state = put_rows(opt_state, node_where(
                actv_c, compression_lib.encode_cold(o_c, cold), enc_o
            ))
        # --- clock advance on the gathered rows ---------------------------
        dur_c = jnp.take(eng.steps.compute_node, cids) + comm
        t_c = jnp.take(t_next, cids)
        vclock = vclock.at[cids].set(
            jnp.where(cmask > 0, t_c, jnp.take(vclock, cids)),
            indices_are_sorted=True, unique_indices=True,
        )
        t_next = t_next.at[cids].add(
            cmask * dur_c, indices_are_sorted=True, unique_indices=True
        )
        events = events.at[cids].add(
            actv_c.astype(jnp.int32),
            indices_are_sorted=True, unique_indices=True,
        )
        # running vclock max carried as a scalar: identical to
        # jnp.max(vclock) (max is exact) without the O(N) reduce per step
        vmax = jnp.maximum(
            vmax, jnp.max(jnp.where(cmask > 0, t_c, -jnp.inf))
        )
        if hier:
            # refresh the carried segment minima for exactly the segments
            # this scatter touched: gather each one's (seg,) clock block
            # and rewrite its exact min — O(C·seg).  Duplicate segments
            # write identical values; out-of-range pad ids clamp into the
            # last segment, whose (unchanged) min is simply recomputed
            n = dl.n_nodes
            seg = self._seg
            segs = jnp.minimum(cids, n - 1) // seg
            rows2 = (
                segs[:, None] * seg
                + jnp.arange(seg, dtype=jnp.int32)[None, :]
            )
            vals = jnp.where(
                rows2 < n,
                jnp.take(t_next, jnp.minimum(rows2, n - 1)),
                jnp.inf,
            )
            seg_min = seg_min.at[segs].set(jnp.min(vals, axis=1))
        out = (
            nbytes,
            vmax,
            jnp.sum(actv_c),
            jnp.sum(stale_c),
            jnp.sum(n_reads_c),
            jnp.max(stale_c),
            occupancy,
            overflow,
            fb,
        )
        state = (params, opt_state, share_state, t_next, vclock, events, vmax)
        if hier:
            state = state + (seg_min,)
        return state, out

    def _chunk_fn(self, params, opt_state, share_state, t_next, vclock, events,
                  retries, seg_min, xs):
        if self._cohort_c > 0:
            # the cohort gather/scatter path runs fault-free (validated):
            # retries pass through untouched, no fstats emitted
            init = (params, opt_state, share_state, t_next, vclock, events,
                    jnp.max(vclock))
            if self._selection == "hier":
                init = init + (seg_min,)
            carry, outs = jax.lax.scan(self._cohort_gs, init, xs)
            seg_out = carry[7] if self._selection == "hier" else None
            return carry[:6] + (retries, seg_out) + outs
        carry, outs = jax.lax.scan(
            self._cohort,
            (params, opt_state, share_state, t_next, vclock, events, retries),
            xs,
        )
        return carry + (None,) + outs

    # -- host-side dispatch ----------------------------------------------
    def _dispatch(self, xs):
        eng = self.eng
        out = self._chunk_jit(
            eng.params, eng.opt_state, eng.share_state,
            self._t_next, self._vclock, self._events, self._retries,
            self._seg_min, xs,
        )
        (eng.params, eng.opt_state, eng.share_state,
         self._t_next, self._vclock, self._events, self._retries) = out[:7]
        self._seg_min = out[7]
        return out[8:]

    def _pull(self, metrics) -> None:
        eng = self.eng
        nbytes, t_virt, fired, stale_sum, stale_n, stale_max = metrics[:6]
        eng.bytes_sent += float(np.asarray(nbytes, np.float64).sum())
        # the virtual clock is a running maximum, not a per-cohort sum —
        # fp32-exact (max selects, never rounds) — plus the rebase offset
        eng.sim_time_s = float(np.asarray(t_virt)[-1]) + self._t_offset
        self._fired_total += int(np.asarray(fired, np.float64).sum())
        self._stale_sum += float(np.asarray(stale_sum, np.float64).sum())
        self._stale_n += float(np.asarray(stale_n, np.float64).sum())
        self._stale_max = max(self._stale_max, float(np.asarray(stale_max).max()))
        if self._cohort_c > 0:
            occ = np.asarray(metrics[6], np.float64)
            self._occ_sum += float(occ.sum())
            self._occ_steps += int(occ.shape[0])
            self._overflow_total += int(np.asarray(metrics[7], np.int64).sum())
            self._fallback_total += int(np.asarray(metrics[8], np.int64).sum())
        else:
            self._accum_faults(metrics[6])
        self._maybe_rebase()

    def _maybe_rebase(self) -> None:
        """fp32 virtual-clock magnitude hygiene.  ``t_next`` advances by
        running *sums* (``+= dur``), which — unlike the running maxima the
        metrics take — lose precision as the clock grows: at t ~ 2^16 s
        the fp32 ulp is ~2^-7 s and sub-ms event durations are absorbed.
        Once every pending event is past ``_REBASE_T_S``, subtract one
        fp32-representable shift from ``t_next``/``vclock`` on device and
        carry it in the float64 ``_t_offset`` (added back in
        ``sim_time_s``/metrics).  Below the threshold nothing changes —
        trajectories there are bitwise identical to the unrebased code."""
        t_min = float(np.asarray(self._t_next).min())
        if t_min < _REBASE_T_S:
            return
        shift = float(np.float32(t_min))
        self._t_offset += shift
        s = jnp.float32(shift)
        self._t_next = self._t_next - s
        self._vclock = self._vclock - s
        if self._seg_min is not None:
            # x - s is monotone in x (fp rounding preserves order), so each
            # segment's min element stays its min and seg_min - s rounds to
            # exactly the shifted t_next entry it mirrors
            self._seg_min = self._seg_min - s

    # -- population-scale memory accounting --------------------------------
    def memory_model(self) -> Dict:
        """Analytic bytes of the async hot/cold memory split — the
        recorded, N-independence-checkable quantity behind the
        ``bench_population`` gate.  Hot = the per-step working set the
        cohort path touches (O(C·(d+1)·P) gossip operands + the (L, C, B)
        batch slice); cold = the device-resident population state
        (O(N·P) params + O(N) clocks) that is only gathered/scattered."""
        eng = self.eng
        dl = eng.dl
        n, p = dl.n_nodes, eng.n_params
        c = self._cohort_c if self._cohort_c > 0 else n
        topo = eng._mix_static
        if isinstance(topo, SparseTopology):
            d = int(topo.dmax)
            topo_bytes = int(
                topo.nbr.nbytes + topo.w.nbytes + topo.w_self.nbytes
            )
        elif topo is None:  # dynamic: (N, degree) tables staged per round
            d = int(dl.degree)
            topo_bytes = n * d * 8 + n * 4
        else:  # dense (N, N) W — the cohort path rejects this at validate
            d = n
            topo_bytes = 4 * n * n
        feat_bytes = int(eng._dev_x.nbytes // max(eng._dev_x.shape[0], 1)) + int(
            eng._dev_y.nbytes // max(eng._dev_y.shape[0], 1)
        )
        hot = {
            "gossip_gather_bytes": c * (1 + d) * p * 4,  # X_c + neighbor rows
            "work_vectors_bytes": 2 * c * p * 4,         # X2 + scatter temp
            "batch_bytes": dl.local_steps * c * dl.batch_size * feat_bytes,
            "topology_rows_bytes": c * (d * 8 + 4),      # nbr+w rows, w_self
        }
        hot["total"] = int(sum(hot.values()))
        # population params/opt bytes come from the *stored* trees — under a
        # compressed cold_dtype that is codes+scales, not N·P·4 — alongside
        # the fp32-equivalent baseline the compression gate divides by
        pop_b, pop_fp32 = compression_lib.cold_tree_bytes(
            (eng.params, eng.opt_state)
        )
        seg_min_bytes = self._n_seg * 4 if self._selection == "hier" else 0
        cold = {
            "population_params_bytes": int(pop_b),
            "clock_bytes": n * (4 + 4 + 4) + seg_min_bytes,
            "topology_bytes": topo_bytes,
        }
        cold["total"] = int(sum(cold.values()))
        cold["population_params_fp32_bytes"] = int(pop_fp32)
        cold["total_fp32"] = int(cold["total"] - pop_b + pop_fp32)
        # the selection layer's per-step working set: O(S + K·seg) for the
        # hierarchy (clock union + segment minima) vs O(N) flat.  Reported
        # separately from `hot`, which stays the N-independent-at-fixed-C
        # gossip working set the bench independence check pins
        if self._selection == "hier":
            selection = {
                "mode": "hier",
                "segment": self._seg,
                "n_segments": self._n_seg,
                "segments_topk": self._seg_k,
                "per_step_bytes": self._seg_k * self._seg * 12
                + self._n_seg * 4,
            }
        else:
            selection = {"mode": "flat", "per_step_bytes": n * 12}
        return {
            "cohort_capacity": c,
            "n_nodes": n,
            "n_params": p,
            "dmax": d,
            "cold_dtype": self._cold_dtype,
            "selection": selection,
            "hot": hot,
            "cold": cold,
        }

    def extra_metrics(self) -> Dict:
        # int64 host totals: the int32 per-node counters are safe (no node
        # fires 2^31 events) but their *population sum* overflows int32 at
        # N >= 100k over long horizons
        events = np.asarray(self._events, np.int64)
        vclock = np.asarray(self._vclock, np.float64) + self._t_offset
        m = {
            "semantics": "async",
            "events_total": int(events.sum()),
            "events_min": int(events.min()),
            "events_max": int(events.max()),
            "vclock_min_s": float(vclock.min()),
            "vclock_median_s": float(np.median(vclock)),
            "vclock_max_s": float(vclock.max()),
            "staleness_mean": self._stale_sum / max(self._stale_n, 1.0),
            "staleness_max": self._stale_max,
        }
        if self._cohort_c > 0:
            m["cohort_capacity"] = self._cohort_c
            m["cohort_occupancy_mean"] = self._occ_sum / max(self._occ_steps, 1)
            m["cohort_overflow_total"] = self._overflow_total
            # overflow per selected event: how often an in-slice node had
            # to carry to a later step — the raw counter's denominator
            m["cohort_overflow_ratio"] = (
                self._overflow_total / max(self._fired_total, 1)
            )
            m["cohort_selection"] = self._selection
            if self._selection == "hier":
                m["selection_fallback_total"] = self._fallback_total
        m.update(super().extra_metrics())
        return m


def make_scheduler(eng) -> Scheduler:
    sem = eng.dl.semantics
    if sem == "sync":
        return SyncScheduler(eng)
    if sem == "local":
        return LocalScheduler(eng)
    if sem == "async":
        return AsyncScheduler(eng)
    raise ValueError(f"unknown semantics {sem!r} (sync|local|async)")
