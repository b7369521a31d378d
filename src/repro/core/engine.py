"""RoundEngine — the compiled multi-round execution core of the DL
emulator (paper Fig. 2 loop, compiled R rounds at a time).

## Execution model

Execution is layered (the pluggable-semantics split):

* **Step layer** (``core/steps.py``): the pure jittable per-round
  functions — local-SGD step, share/mix step through the configured
  sharing strategy, per-node simulated round time — identical inside a
  ``lax.scan`` body, a legacy per-round jit, or a ``shard_map`` block.
* **Scheduler layer** (``core/scheduler.py``): time and activation
  semantics, selected by ``DLConfig.semantics``:

  - ``"sync"`` — the synchronous round barrier (chunks of R rounds in one
    ``lax.scan``; the bit-for-bit equivalence oracle, and the only
    semantics the legacy ``chunk_rounds=0`` dispatch and the node-sharded
    ``shard_map`` chunk run under),
  - ``"local"`` — identical trajectories, per-node virtual clocks with a
    neighborhood barrier (stragglers delay only their graph
    neighborhood),
  - ``"async"`` — event-driven gossip on a first-class virtual clock
    (the AD-PSGD family): per-node next-event times driven by the
    heterogeneous per-node ``compute_time_s`` vector, scanned event
    cohorts, pairwise or neighborhood averaging against possibly-stale
    neighbor params, with staleness / per-node wall-clock / event counts
    as traced outputs.

* **Engine** (this module): resources and the run loop — node-stacked
  state, device-resident data, topology/network/sharing construction,
  eval cadence, history, results.

The mechanics the layers inherit from the earlier engine generations are
unchanged and still property-tested: batches pre-stacked on device with
per-chunk index tensors; sparse neighbor-indexed mixing with traced
per-round (R, N, D) topology stacks (``mixing="auto"|"sparse"|"dense"``);
payload-form compressed sharing (``payload``); jittable secure
aggregation; per-round participation masks for churn — now iid *or*
machine-correlated (``churn_machines``); metrics as traced scan outputs
synced once per chunk; and the node-sharded chunk over a device mesh
(``shard_devices``/``shard_backend``) with collective_permute or
all-gather gossip.  Chunk boundaries align to the eval cadence, so the
recorded history is identical to per-round execution.

Heterogeneous time is a first-class axis: ``compute_time_s`` is the base
per-node local compute, and ``straggler_factor``/``straggler_frac`` mark
a seeded fraction of nodes as stragglers (``network.straggler_compute_
times``); the (N,) vector feeds the traced round-time formula — one
implementation, ``network.node_round_times``, shared with the host
``NetworkModel`` so the Python model and the compiled model cannot drift.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as faults_lib
from repro.core import sharing as sharing_lib
from repro.core.faults import FaultPlan
from repro.core.mixing import NodeShard, PermuteSchedule
from repro.core.network import (
    NetworkModel,
    paper_testbed,
    straggler_compute_times,
    wan_deployment,
)
from repro.core.scheduler import make_scheduler
from repro.core.secure import SecureAggregation
from repro.core.steps import RoundSteps
from repro.core.topology import (
    Graph,
    PeerSampler,
    SparseTopology,
    decompose_slot_permutations,
)
from repro.optim import Optimizer
from repro.utils.pytree import tree_vector

# cap on the (R, N, N) mixing-matrix stack a single *dense-path* chunk
# materializes; dense chunks shrink automatically at very large N.  The
# sparse path stages O(N·d) tables per round and is exempt.
_W_STACK_BYTES_CAP = 64 * 1024 * 1024

# above this node count, circulant topologies (ring / regular) skip the
# dense (N, N) Graph object entirely and build the sparse neighbor table
# directly (topology.circulant_neighbor_table, O(N·d)) — the adjacency of
# a 100k-node overlay alone would be 10 GB.  Tables are bitwise-identical
# either way (property-tested), so the threshold only moves memory.
_DENSE_GRAPH_MAX_N = 4096

# bytes of the largest per-node intermediate, times the nodes evaluated
# together, that one evaluation step may hold (RoundEngine.over_nodes).
# A vmap over all N nodes holds N copies of each activation of the test
# batch: 16 GiB for GN-LeNet's first conv at N=256 and 512 images, which
# one 16 GB chip refuses.
EVAL_BYTES = 1 << 30


def _largest_intermediate(jaxpr) -> int:
    """Bytes of the largest value any equation of ``jaxpr`` (and of the
    jaxprs nested in its equations) produces."""
    big = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = v.aval
            if hasattr(aval, "shape"):
                big = max(big, int(np.prod(aval.shape)) * aval.dtype.itemsize)
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (tuple, list)) else (sub,):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    big = max(big, _largest_intermediate(j))
    return big


def over_nodes(fn, params, *args):
    """``jax.vmap(fn)`` over the stacked node axis of ``params``, with
    ``args`` shared, in groups small enough that the largest per-node
    intermediate times the group stays within ``EVAL_BYTES``: one vmap
    when all N fit (every small model), sequential groups otherwise."""
    n = jax.tree_util.tree_leaves(params)[0].shape[0]
    one = jax.tree_util.tree_map(lambda a: a[0], params)
    per_node = _largest_intermediate(
        jax.make_jaxpr(lambda p: fn(p, *args))(one).jaxpr)
    group = max(1, EVAL_BYTES // max(per_node, 1))
    f = lambda p: fn(p, *args)
    if group >= n:
        return jax.vmap(f)(params)
    return jax.lax.map(f, params, batch_size=group)


@dataclasses.dataclass
class DLConfig:
    """Experiment specification (paper Fig. 1 'specifications' input)."""

    n_nodes: int = 16
    # execution backend: 'simulated' — the in-process RoundEngine (every
    # node a slot of the stacked node axis, time simulated); 'processes' —
    # the real-network runtime (repro.runtime): K OS processes each owning
    # a row-block of nodes, gossiping the payload wire format over real
    # TCP sockets on real clocks (failure detection, retry/backoff,
    # graceful degradation on peer death)
    backend: str = "simulated"  # simulated | processes
    topology: str = "regular"  # ring | regular | fully | star | dynamic | file:<path>
    degree: int = 5
    sharing: str = "full"      # full | randomk | topk | choco | quant
    budget: float = 0.1        # sparsification budget
    choco_gamma: float = 0.3
    # payload wire format for sparsified strategies: 'on' emits compact
    # (idx, val) per-node payloads aggregated in one O(N·d·k) gather +
    # scatter pass (mixing.mix_payload); 'off' runs the dense-mask oracle
    # (scattered (N, P) masks + two apply_W passes — the legacy form, kept
    # property-tested equal); 'auto' = on for randomk/topk/choco.
    payload: str = "auto"      # auto | on | off
    payload_quant: bool = False  # int8-quantize payload values on the wire
    randk_sampler: str = "uniform"  # randomk coord sampler: uniform | strided
    secure: bool = False       # secure aggregation (masked full sharing)
    local_steps: int = 1
    batch_size: int = 8
    rounds: int = 100
    eval_every: int = 10
    seed: int = 0
    results_dir: Optional[str] = None
    # --- engine (scan) execution ------------------------------------------
    chunk_rounds: int = 8      # rounds per compiled lax.scan chunk; 0 = legacy
    mixing: str = "auto"       # auto | sparse (neighbor tables) | dense (N,N W)
    # --- execution semantics (scheduler layer) -----------------------------
    # 'sync'  — synchronous round barrier (the paper's default; oracle)
    # 'local' — same trajectories, per-node clocks w/ neighborhood barrier
    # 'async' — event-driven gossip on a virtual clock (AD-PSGD family)
    semantics: str = "sync"
    async_gossip: str = "neighborhood"  # neighborhood | pairwise (AD-PSGD)
    async_slice_s: float = 0.0  # event-cohort window on the virtual clock
    # population-scale cohort activation (async only): >0 bounds each event
    # step to a gathered hot set of C rows — O(C·(d+1)·P) per step instead
    # of O(N·P) — with overflow-carry for in-slice nodes beyond capacity.
    # 0 = the dense oracle (every step computes over all N rows).
    cohort_capacity: int = 0
    # cohort selection layer: 'flat' = the O(N) min+top_k oracle; 'hier' =
    # carried segment-minimum hierarchy — top-K segments of the (S,)
    # per-segment minima, then top_k inside their gathered clock union —
    # O(S + K·seg) per step with bitwise-identical cohorts (slices
    # spanning more than K segments fall back to the flat oracle inside
    # the step); 'auto' = hier above ~260k nodes.
    selection: str = "auto"    # auto | flat | hier
    segment_size: int = 0      # hier segment length; 0 = auto ~ sqrt(N/C)
    # cold population storage (cohort path): the (N, P) params and float
    # opt-state moments live compressed on device — 'bf16' truncates
    # (round-trip exact for bf16-representable values), 'int8' per-row
    # symmetric quantization (codes + one fp32 scale per row per leaf,
    # ~0.26x fp32 bytes; lossy, gated by a tolerance oracle) — decoded on
    # cohort gather, re-encoded on scatter.
    cold_dtype: str = "fp32"   # fp32 | bf16 | int8
    # batch-index derivation: 'stream' = per-round numpy PCG64 host staging
    # (the original path); 'node' = per-(round, node) jax PRNG keying,
    # derived on device for exactly the rows a step touches — required by
    # cohort_capacity (staging (R, L, N, B) host indices would reintroduce
    # the O(N) per-step cost the cohort path removes).  The two keyings
    # draw different (equally valid) sample streams.
    batch_keying: str = "stream"  # stream | node
    # --- multi-device execution -------------------------------------------
    shard_devices: int = 0     # shard the node axis over this many devices
    shard_backend: str = "auto"  # auto | ppermute (slot collective_permutes) | gather
    # --- scenario axes -----------------------------------------------------
    participation: float = 1.0  # P(node active in a round); <1 models churn
    churn_machines: int = 0    # >0: correlated churn — machines fail, not nodes
    # message-level fault injection (core.faults.FaultPlan): per-edge loss,
    # crash/restart schedules, latency spikes, payload corruption — None
    # disables the fault axis entirely (zero overhead in the scanned body)
    faults: Optional[FaultPlan] = None
    # Bonawitz seed recovery: lets secure=True run under churn — surviving
    # co-neighbors reveal dropped pairs' seed material so the receiver can
    # subtract the uncancelled PRF masks (costs a second mask pass plus
    # SEED_SHARE_BYTES per dropped-pair triple)
    secure_recovery: bool = False
    network: str = "none"       # simulated network: none | lan | wan
    compute_time_s: float = 0.0  # base per-node local compute in the time model
    straggler_factor: float = 1.0  # stragglers run at factor x compute_time_s
    straggler_frac: float = 0.0    # seeded fraction of straggler nodes
    # continuous per-node heterogeneity: node i runs at compute_time_s *
    # U(1, 1 + compute_spread), seeded — de-ties the event clock so the
    # population's t_next is spread instead of lattice-valued (the regime
    # where hierarchical cohort selection can prune segments)
    compute_spread: float = 0.0
    parallel_sends: bool = False  # overlap a node's sends (dedicated NICs)

    # ------------------------------------------------------------------
    def validate(self) -> "DLConfig":
        """Centralized knob validation — every cross-knob constraint lives
        here (the engine calls it first; tests exercise it directly).
        Raises ValueError on the first violation; returns self."""
        def bad(msg):
            raise ValueError(f"invalid DLConfig: {msg}")

        if self.semantics not in ("sync", "local", "async"):
            bad(f"unknown semantics {self.semantics!r} (sync|local|async)")
        if self.backend not in ("simulated", "processes"):
            bad(f"unknown backend {self.backend!r} (simulated|processes)")
        # -- real-network process backend ----------------------------------
        if self.backend == "processes":
            if self.shard_devices > 0:
                bad("backend='processes' shards nodes over OS processes; "
                    "shard_devices is the simulated backend's device mesh — "
                    "drop one of the two")
            if self.semantics != "sync":
                bad(f"backend='processes' implements the synchronous round "
                    f"barrier only for now (got semantics={self.semantics!r});"
                    " use the simulated backend for local/async semantics")
            if self.secure:
                bad("backend='processes' does not run secure aggregation "
                    "over the socket transport yet; set secure=False or use "
                    "the simulated backend")
            if self.faults is not None:
                bad("FaultPlan injects faults into the *simulated* step; the "
                    "processes backend takes real faults (kill a worker, see "
                    "examples/processes.py) — drop the FaultPlan")
            if self.participation < 1.0 or self.churn_machines > 0:
                bad("simulated churn masks (participation/churn_machines) "
                    "don't apply to real processes; model churn by killing "
                    "workers instead")
            if self.cohort_capacity > 0 or self.batch_keying != "stream":
                bad("cohort_capacity/batch_keying='node' are async "
                    "population-scale knobs of the simulated backend")
            if self.topology in ("fully", "star") or self.mixing == "dense":
                bad("processes workers gossip over sparse neighbor tables; "
                    "fully|star topologies / mixing='dense' have no bounded "
                    "per-peer send set — use a sparse overlay")
            if self.topology == "dynamic":
                bad("backend='processes' needs a static graph to derive "
                    "its per-peer send/receive sets; topology='dynamic' "
                    "re-draws them every round")
            if self.sharing.lower() not in ("full", "randomk", "random"):
                bad(f"backend='processes' serializes sharing='full' rows or "
                    f"sharing='randomk' (idx, val) payloads on the wire; "
                    f"{self.sharing!r} is stateful/unsupported there — use "
                    "the simulated backend")
            if self.randk_sampler != "uniform":
                bad("backend='processes' wires the uniform randomk payload "
                    "only (strided phases are a simulated fast path)")
        if self.async_gossip not in ("neighborhood", "pairwise"):
            bad(f"unknown async_gossip {self.async_gossip!r} "
                "(neighborhood|pairwise)")
        if self.payload not in ("auto", "on", "off"):
            bad(f"unknown payload mode {self.payload!r} (auto|on|off)")
        if self.mixing not in ("auto", "sparse", "dense"):
            bad(f"unknown mixing mode {self.mixing!r} (auto|sparse|dense)")
        if self.shard_backend not in ("auto", "ppermute", "gather"):
            bad(f"unknown shard_backend {self.shard_backend!r} "
                "(auto|ppermute|gather)")
        if self.randk_sampler not in ("uniform", "strided"):
            bad(f"unknown randk_sampler {self.randk_sampler!r} "
                "(uniform|strided)")
        if not 0.0 < self.participation <= 1.0:
            bad(f"participation must be in (0, 1], got {self.participation}")
        if self.churn_machines < 0:
            bad("churn_machines must be >= 0")
        if not 0.0 <= self.straggler_frac <= 1.0:
            bad(f"straggler_frac must be in [0, 1], got {self.straggler_frac}")
        if self.straggler_factor <= 0:
            bad("straggler_factor must be > 0")
        if self.compute_time_s < 0 or self.async_slice_s < 0:
            bad("compute_time_s / async_slice_s must be >= 0")
        if (
            self.straggler_frac > 0
            and self.straggler_factor != 1.0
            and self.compute_time_s == 0
        ):
            bad("straggler_factor/straggler_frac scale compute_time_s, "
                "which is 0 — the straggler distribution would be a silent "
                "no-op; set a base compute_time_s")
        if self.compute_spread < 0:
            bad(f"compute_spread must be >= 0, got {self.compute_spread}")
        if self.compute_spread > 0 and self.compute_time_s == 0:
            bad("compute_spread scales compute_time_s, which is 0 — the "
                "spread would be a silent no-op; set a base compute_time_s")
        # (churn_machines with participation=1.0 is permitted: sweeps use
        # p=1.0 as the no-churn baseline row)
        # -- sharing-strategy knob compatibility ---------------------------
        sparsified = sharing_lib.strategy_takes_budget(self.sharing)
        if self.secure:
            if self.topology == "dynamic":
                bad("secure=True needs a static graph (the pairwise-mask "
                    "PRF schedule is per-edge); topology='dynamic' has none")
            crashes = self.faults is not None and bool(self.faults.crashes)
            if (
                self.participation < 1.0 or self.churn_machines > 0 or crashes
            ) and not self.secure_recovery:
                bad("secure=True under churn (participation < 1, "
                    "churn_machines > 0, or FaultPlan crash schedules) "
                    "needs secure_recovery=True: without the Bonawitz "
                    "seed-recovery pass a dropped node's pairwise masks "
                    "would not cancel")
            if self.payload == "on" or self.payload_quant or self.randk_sampler != "uniform":
                bad("payload/payload_quant/randk_sampler do not compose "
                    "with secure=True (masked messages are full fp32 "
                    "vectors; compressing them would break mask "
                    "cancellation)")
        else:
            if self.payload == "on" and not sparsified:
                bad(f"payload='on' needs a sparsified sharing strategy "
                    f"(randomk/topk/choco), not {self.sharing!r}")
            if self.payload_quant and not sparsified:
                bad("payload_quant applies to payload-emitting strategies "
                    "(randomk/topk/choco); use sharing='quant' for "
                    "quantized full sharing")
            if self.randk_sampler != "uniform" and self.sharing.lower() not in (
                "randomk", "random"
            ):
                bad("randk_sampler applies to sharing='randomk' only")
        # -- fault injection -------------------------------------------------
        if self.secure_recovery and not self.secure:
            bad("secure_recovery=True is the seed-recovery pass of secure "
                "aggregation; it needs secure=True")
        if self.faults is not None:
            self.faults.validate()
            for node, _, _ in self.faults.crashes:
                if node >= self.n_nodes:
                    bad(f"FaultPlan crash node {node} out of range for "
                        f"n_nodes={self.n_nodes}")
            if self.chunk_rounds <= 0:
                bad("faults run on the scanned chunk path only "
                    "(chunk_rounds > 0); the legacy per-round dispatch "
                    "predates the fault axis")
            if self.shard_devices > 0:
                bad("faults are single-host for now (per-edge draws and "
                    "the rollback guard are not distributed); drop "
                    "shard_devices or the FaultPlan")
            if self.cohort_capacity > 0:
                bad("faults do not compose with cohort_capacity yet (the "
                    "gather/scatter cohort body has no fault hooks); use "
                    "the dense async path")
            if self.secure and self.faults.msg_loss > 0:
                bad("secure=True with FaultPlan.msg_loss > 0 is not "
                    "modeled: per-edge loss would need per-edge mask "
                    "recovery (secure_recovery covers node-level churn "
                    "and crashes; latency spikes and corruption compose)")
        # -- multi-device constraints --------------------------------------
        if self.shard_devices > 0:
            if self.chunk_rounds <= 0:
                bad("shard_devices requires the scanned chunk path "
                    "(chunk_rounds > 0); the legacy per-round dispatch is "
                    "single-device only")
            if self.n_nodes % self.shard_devices:
                bad(f"n_nodes={self.n_nodes} must divide evenly over "
                    f"shard_devices={self.shard_devices}")
        # -- execution-semantics constraints -------------------------------
        if self.semantics != "sync":
            if self.chunk_rounds <= 0:
                bad(f"semantics={self.semantics!r} runs on the scanned "
                    "chunk path only (chunk_rounds > 0); the legacy "
                    "per-round dispatch is synchronous by construction")
            if self.shard_devices > 0:
                bad(f"semantics={self.semantics!r} is single-host for now "
                    "(the virtual clock is not yet distributed); use "
                    "semantics='sync' with shard_devices")
        if self.semantics == "async":
            if self.secure:
                bad("semantics='async' rejects secure=True until masked "
                    "asynchronous rounds are modeled (pairwise masks "
                    "assume all co-neighbors mix in the same round)")
            if not sharing_lib.is_full_sharing(self.sharing):
                bad("semantics='async' models one-sided stale reads for "
                    f"sharing='full' only (got {self.sharing!r}); "
                    "compressed/stateful strategies assume a synchronous "
                    "exchange")
            if self.async_gossip == "pairwise" and (
                self.mixing == "dense" or self.topology in ("fully", "star")
            ):
                bad("async_gossip='pairwise' samples partners from sparse "
                    "neighbor tables; use async_gossip='neighborhood' for "
                    "dense mixing / fully|star topologies")
        # -- population-scale cohort activation -----------------------------
        if self.batch_keying not in ("stream", "node"):
            bad(f"unknown batch_keying {self.batch_keying!r} (stream|node)")
        if self.batch_keying == "node":
            if self.chunk_rounds <= 0:
                bad("batch_keying='node' derives indices inside the scanned "
                    "chunk (chunk_rounds > 0); the legacy per-round dispatch "
                    "stages host batches")
            if self.shard_devices > 0:
                bad("batch_keying='node' is single-host for now; the "
                    "shard_map chunk stages 'stream' batches per shard")
        if self.cohort_capacity < 0:
            bad(f"cohort_capacity must be >= 0, got {self.cohort_capacity}")
        if self.cohort_capacity > 0:
            if self.semantics != "async":
                bad("cohort_capacity is the async cohort gather/scatter "
                    f"path; set semantics='async' (got {self.semantics!r})")
            if self.cohort_capacity > self.n_nodes:
                bad(f"cohort_capacity={self.cohort_capacity} exceeds "
                    f"n_nodes={self.n_nodes}")
            if self.mixing == "dense" or self.topology in ("fully", "star"):
                bad("cohort_capacity gathers neighbor rows from sparse "
                    "(N, D) tables; dense mixing / fully|star topologies "
                    "have no bounded neighbor set to gather")
            if self.batch_keying != "node":
                bad("cohort_capacity requires batch_keying='node': host "
                    "staging of (R, L, N, B) sample indices is O(N·B) per "
                    "step — the population-scale cost the cohort path "
                    "exists to remove")
        if self.selection not in ("auto", "flat", "hier"):
            bad(f"unknown selection {self.selection!r} (auto|flat|hier)")
        if self.segment_size < 0:
            bad(f"segment_size must be >= 0, got {self.segment_size}")
        if self.cold_dtype not in ("fp32", "bf16", "int8"):
            bad(f"unknown cold_dtype {self.cold_dtype!r} (fp32|bf16|int8)")
        if self.cohort_capacity == 0:
            if self.selection == "hier" or self.segment_size > 0:
                bad("selection='hier'/segment_size tune the cohort "
                    "selection layer; set cohort_capacity > 0")
            if self.cold_dtype != "fp32":
                bad("cold_dtype compresses the cohort path's cold "
                    "population state; set cohort_capacity > 0")
        return self


def build_graph(cfg: DLConfig) -> Optional[Graph]:
    t = cfg.topology
    if t == "ring":
        return Graph.ring(cfg.n_nodes)
    if t == "regular":
        return Graph.regular_circulant(cfg.n_nodes, cfg.degree)
    if t == "random-regular":
        return Graph.random_regular(cfg.n_nodes, cfg.degree, cfg.seed)
    if t == "fully":
        return Graph.fully_connected(cfg.n_nodes)
    if t == "star":
        return Graph.star(cfg.n_nodes)
    if t == "dynamic":
        return None  # per-round via PeerSampler
    if t.startswith("file:"):
        return Graph.from_edge_list(t[5:], cfg.n_nodes)
    raise ValueError(f"unknown topology {t!r}")


def compute_time_vector(cfg: DLConfig) -> np.ndarray:
    """THE per-node (N,) compute-time vector of a config — the single
    derivation (including the straggler draw's seed offset) shared by the
    host ``NetworkModel`` and the engine's traced step/scheduler layers,
    so the two cannot disagree about who the stragglers are."""
    ct = straggler_compute_times(
        cfg.n_nodes, cfg.compute_time_s, cfg.straggler_factor,
        cfg.straggler_frac, seed=cfg.seed + 31,
    )
    if cfg.compute_spread > 0:
        # continuous multiplier on top of the (possibly bimodal) straggler
        # draw — distinct seed stream so toggling stragglers does not
        # reshuffle the spread
        rng = np.random.default_rng(cfg.seed + 47)
        ct = (ct * (1.0 + cfg.compute_spread
                    * rng.random(cfg.n_nodes, dtype=np.float32))
              ).astype(np.float32)
    return ct


def build_network(cfg: DLConfig) -> Optional[NetworkModel]:
    if cfg.network in (None, "", "none"):
        return None
    if cfg.network == "lan":
        net = paper_testbed(cfg.n_nodes)
    elif cfg.network == "wan":
        net = wan_deployment(cfg.n_nodes)
    else:
        raise ValueError(f"unknown network model {cfg.network!r} (none|lan|wan)")
    # promote the config's (possibly heterogeneous) compute times into the
    # model, so the host-side NetworkModel and the traced engine agree
    net.compute_time_s = compute_time_vector(cfg)
    return net


class RoundEngine:
    """Emulates N DL nodes with node-stacked state and scanned rounds.

    loss_fn(params, batch_x, batch_y) -> scalar    (single node)
    acc_fn(params, batch_x, batch_y) -> scalar     (single node)
    heterogeneous_lrs: optional (N,) per-node learning-rate multipliers
    applied to each node's optimizer updates (system heterogeneity axis).
    """

    def __init__(
        self,
        dl: DLConfig,
        init_params_fn: Callable[[jax.Array], Any],
        loss_fn: Callable,
        acc_fn: Callable,
        optimizer: Optimizer,
        batcher,
        heterogeneous_lrs: Optional[np.ndarray] = None,
    ):
        dl.validate()
        if dl.backend == "processes":
            raise ValueError(
                "RoundEngine is the simulated backend; backend='processes' "
                "runs K real OS processes — construct "
                "repro.runtime.ProcessRunner(dl, workload) directly, or pass "
                "workload= to DecentralizedRunner and it will dispatch"
            )
        self.dl = dl
        self.loss_fn = loss_fn
        self.acc_fn = acc_fn
        self.opt = optimizer
        self.batcher = batcher
        if heterogeneous_lrs is not None:
            lrs = np.asarray(heterogeneous_lrs, np.float32)
            assert lrs.shape == (dl.n_nodes,), "heterogeneous_lrs must be (n_nodes,)"
            self.lr_scales = jnp.asarray(lrs)
        else:
            self.lr_scales = None
        key = jax.random.key(dl.seed)
        keys = jax.random.split(key, dl.n_nodes)
        # fully-decentralized: every node initializes its *own* model
        self.params = jax.vmap(init_params_fn)(keys)
        self.opt_state = jax.vmap(self.opt.init)(self.params)
        self.template = jax.tree_util.tree_map(lambda a: a[0], self.params)
        # population scale: circulant overlays above the dense-graph cap go
        # straight to (N, d) tables — no (N, N) adjacency is ever built
        self._circulant_direct = (
            dl.topology in ("ring", "regular")
            and dl.n_nodes > _DENSE_GRAPH_MAX_N
            and not dl.secure
            and dl.mixing != "dense"
        )
        self.graph = None if self._circulant_direct else build_graph(dl)
        self.sampler = PeerSampler(dl.n_nodes, dl.degree, dl.seed) if dl.topology == "dynamic" else None
        if dl.secure:
            assert self.graph is not None, "secure aggregation needs a static graph"
            self.sharing = SecureAggregation(
                self.graph.adj, recovery=dl.secure_recovery
            )
        else:
            sparsified = sharing_lib.strategy_takes_budget(dl.sharing)
            kw = {"gamma": dl.choco_gamma} if dl.sharing.startswith("choco") else {}
            if sparsified:
                kw["budget"] = dl.budget
                kw["payload"] = dl.payload != "off"
                if dl.payload_quant:
                    kw["quantize"] = "int8"
                if dl.sharing.lower() in ("randomk", "random"):
                    kw["sampler"] = dl.randk_sampler
            self.sharing = sharing_lib.make_sharing(dl.sharing, **kw)
        X0 = jax.vmap(tree_vector)(self.params)
        self.share_state = self.sharing.init_state(X0)
        self.n_params = int(X0.shape[1])
        # per-round wire format metrics: the dtype values ship in, and the
        # bytes of message tensors the sharing stage materializes per round
        # ((idx, val) payloads vs scattered (N, P) mask matrices)
        self.wire_dtype = str(np.dtype(self.sharing.wire_dtype(X0.dtype)))
        self.share_stage_bytes = int(
            self.sharing.stage_bytes_per_round(dl.n_nodes, self.n_params)
        )
        self.mix_mode = self._resolve_mix_mode()
        if (
            dl.semantics == "async"
            and dl.async_gossip == "pairwise"
            and self.mix_mode != "sparse"
        ):
            raise ValueError(
                "async_gossip='pairwise' needs sparse neighbor tables; this "
                "topology resolved to dense mixing — use "
                "async_gossip='neighborhood'"
            )
        if dl.cohort_capacity > 0 and self.mix_mode != "sparse":
            raise ValueError(
                "cohort_capacity gathers neighbor rows from sparse (N, D) "
                "tables; this topology resolved to dense mixing — drop "
                "cohort_capacity or use a sparse overlay"
            )
        # --- node-axis sharding (multi-device execution) -------------------
        self.sharded = dl.shard_devices > 0
        self._shard: Optional[NodeShard] = None
        self._perm_sched: Optional[PermuteSchedule] = None
        if self.sharded:
            from repro.launch.mesh import make_node_mesh

            self._mesh = make_node_mesh(dl.shard_devices)
            self._shard = NodeShard(
                "nodes", (dl.shard_devices,), dl.n_nodes // dl.shard_devices
            )
            self._shard_backend = self._resolve_shard_backend()
        # peak host->device bytes staged per chunk (or once, if static) for
        # the mixing topology — O(N·d) sparse vs 4·N² dense; the perf gate
        # benchmarks record it
        self.topo_stage_bytes_peak = 0
        if self.graph is not None:
            self._mean_degree = float(self.graph.degrees().mean())
            # static topology: the mixing operand is a captured device
            # constant of the scan, not a per-chunk host transfer
            if self.mix_mode == "sparse":
                # never materialize the (N, N) W on the sparse path
                st = SparseTopology.from_graph(self.graph)
                if self.sharded and self._shard_backend == "ppermute":
                    # slot-rebalance the table so each column is a
                    # permutation lowering to collective_permutes
                    dec = decompose_slot_permutations(st)
                    if dec is None:
                        raise ValueError(
                            "topology does not decompose into per-slot "
                            "permutations; use shard_backend='gather'"
                        )
                    st = dec
                    self._perm_sched = PermuteSchedule.from_table(
                        dec.nbr, dl.shard_devices
                    )
                self._mix_static = SparseTopology(
                    jnp.asarray(st.nbr), jnp.asarray(st.w), jnp.asarray(st.w_self)
                )
                self.topo_stage_bytes_peak = st.stage_bytes()
            else:
                W_np = self.graph.metropolis_hastings().astype(np.float32)
                self._mix_static = jnp.asarray(W_np)
                self.topo_stage_bytes_peak = int(W_np.nbytes)
        elif self._circulant_direct:
            if self.sharded and self._shard_backend == "ppermute":
                raise ValueError(
                    "shard_backend='ppermute' builds its slot schedule from "
                    f"the dense graph, capped at n_nodes={_DENSE_GRAPH_MAX_N}; "
                    "use shard_backend='gather' at population scale"
                )
            deg = 2 if dl.topology == "ring" else dl.degree
            st = SparseTopology.regular_circulant(dl.n_nodes, deg)
            self._mean_degree = float(st.dmax)  # circulants are regular
            self._mix_static = SparseTopology(
                jnp.asarray(st.nbr), jnp.asarray(st.w), jnp.asarray(st.w_self)
            )
            self.topo_stage_bytes_peak = st.stage_bytes()
        else:
            self._mix_static = None
            self._mean_degree = float(dl.degree)  # PeerSampler is d-regular
        self.network_model = build_network(dl)
        if self.network_model is not None:
            lat, gp = self.network_model.matrices()
            self._lat = jnp.asarray(lat)
            self._goodput = jnp.asarray(gp)
        else:
            self._lat = self._goodput = None
        # heterogeneous per-node compute times — the (N,) vector both the
        # traced round-time formula and the async event clock consume;
        # reuse the network model's copy so both sides see one derivation
        self._compute_node_np = (
            self.network_model.compute_time_s
            if self.network_model is not None
            else compute_time_vector(dl)
        )
        self._compute_node = jnp.asarray(self._compute_node_np)
        # device-resident dataset for in-scan batch gathers
        self._dev_x = jnp.asarray(batcher.x)
        self._dev_y = jnp.asarray(batcher.y)
        self._base_key = jax.random.key(dl.seed + 17)
        if dl.batch_keying == "node":
            # per-(round, node) keyed sampling: partition tables live on
            # device; the batch key is folded off the engine stream so
            # batch draws never collide with sharing/gossip draws
            self._dev_lens, self._dev_parts_pad = batcher.device_tables()
            self._batch_key = jax.random.fold_in(self._base_key, 0x0BA7)
        else:
            self._dev_lens = self._dev_parts_pad = self._batch_key = None
        n = dl.n_nodes
        if dl.chunk_rounds <= 0:
            self.chunk = 0
        elif self.sampler is not None and self.mix_mode == "dense":
            # dense dynamic topologies stage an (R, N, N) W stack per chunk;
            # bound it.  (The sparse path stages (R, N, D) — no cap needed,
            # chunks stay full-length at N=1024+.)
            self.chunk = max(1, min(dl.chunk_rounds, _W_STACK_BYTES_CAP // (4 * n * n)))
        else:
            self.chunk = dl.chunk_rounds
        # --- the two execution layers --------------------------------------
        self._fault_key = (
            faults_lib.fault_key(dl.faults, dl.seed)
            if dl.faults is not None else None
        )
        self.steps = RoundSteps(
            loss_fn=loss_fn,
            opt=optimizer,
            sharing=self.sharing,
            template=self.template,
            base_key=self._base_key,
            mean_degree=self._mean_degree,
            compute_node=self._compute_node,
            parallel_sends=dl.parallel_sends,
            lr_scales=self.lr_scales,
            lat=self._lat,
            goodput=self._goodput,
            faults=dl.faults,
            fault_key=self._fault_key,
        )
        self.scheduler = make_scheduler(self)
        self.history: List[Dict] = []
        self.bytes_sent = 0.0
        self.sim_time_s = 0.0
        # crash-resume cursor: load_state() advances it so run() continues
        # from the checkpointed round instead of round 0
        self._start_round = 0
        self.rounds_done = 0
        self._eval_jit = jax.jit(self._eval)

    def _resolve_shard_backend(self) -> str:
        """Distributed gossip lowering: 'ppermute' decomposes the static
        neighbor table into per-slot permutations, each applied as
        rotation-grouped `collective_permute`s (O(D·B·P) wire — the mesh-
        native path); 'gather' all-gathers the node axis and reuses the
        single-device neighbor gather (any table, incl. per-round dynamic
        ones whose schedule cannot be static).  'auto' picks ppermute on
        TPU interconnects and gather on CPU emulation, where host-emulated
        collectives cost more than the bytes they save."""
        b = self.dl.shard_backend
        static_sparse = self.sampler is None and self.mix_mode == "sparse"
        if b == "ppermute":
            if not static_sparse:
                raise ValueError(
                    "shard_backend='ppermute' needs a static sparse "
                    "topology (dynamic tables have no static schedule; "
                    "dense mixing all-gathers by construction)"
                )
            return b
        if b == "auto" and static_sparse and jax.default_backend() == "tpu":
            return "ppermute"
        return "gather"

    def _resolve_mix_mode(self) -> str:
        """'sparse' (neighbor-indexed O(N·d·P) gossip) for sparse overlays,
        'dense' (W @ X) where the graph is effectively complete."""
        m = self.dl.mixing
        if m != "auto":
            return m
        if self.dl.topology in ("fully", "star"):
            return "dense"  # D ~ N: padded tables would be the dense matrix
        if self.graph is not None and int(self.graph.degrees().max()) >= self.dl.n_nodes - 1:
            return "dense"
        return "sparse"

    # ------------------------------------------------------------------
    # back-compat shims (tests and external callers poke these)
    # ------------------------------------------------------------------
    def _participation_mask(self, start: int, n_rounds: int) -> np.ndarray:
        return self.scheduler.participation_mask(start, n_rounds)

    def _eval(self, params, tx, ty):
        return over_nodes(self.acc_fn, params, tx, ty)

    # ------------------------------------------------------------------
    def _record(self, rnd: int, tx, ty, t0: float, log: bool):
        # eval through the scheduler hook: the quantized-cold async path
        # stores self.params compressed and decodes them here
        accs = np.asarray(self._eval_jit(self.scheduler.eval_params(), tx, ty))
        rec = {
            "round": rnd,
            "acc_mean": float(accs.mean()),
            "acc_std": float(accs.std()),
            "bytes_per_node": self.bytes_sent,
            "wall_s": time.time() - t0,
            "sim_time_s": self.sim_time_s,
            "wire_dtype": self.wire_dtype,
        }
        rec.update(self.scheduler.extra_metrics())
        self.history.append(rec)
        if log:
            print(
                f"[{self.dl.topology}/{type(self.sharing).__name__}] round {rnd:4d} "
                f"acc {rec['acc_mean']:.4f}±{rec['acc_std']:.4f} "
                f"MB/node {self.bytes_sent / 1e6:.1f}"
                + (f" sim {self.sim_time_s:.1f}s" if self.network_model else "")
            )

    def run(self, rounds: Optional[int] = None, log: bool = True) -> List[Dict]:
        """Execute ``rounds`` scheduler steps (synchronous rounds, or event
        cohorts under ``semantics='async'``) with evals every
        ``eval_every``."""
        dl = self.dl
        rounds = rounds if rounds is not None else dl.rounds
        tx, ty = self.batcher.test_batch()
        tx, ty = jnp.asarray(tx), jnp.asarray(ty)
        ev = max(dl.eval_every, 1)
        t0 = time.time()
        if self.chunk == 0:  # legacy per-round dispatch (sync only)
            for rnd in range(self._start_round, rounds):
                self.scheduler.run_legacy_round(rnd)
                if rnd % ev == 0 or rnd == rounds - 1:
                    self._record(rnd, tx, ty, t0, log)
        else:
            rnd = self._start_round
            while rnd < rounds:
                nxt = -(-rnd // ev) * ev  # next eval round >= rnd
                if nxt >= rounds:
                    nxt = rounds - 1
                end = nxt + 1
                while rnd < end:
                    r = min(self.chunk, end - rnd)
                    self.scheduler.run_span(rnd, r)
                    rnd += r
                self._record(nxt, tx, ty, t0, log)
        self.rounds_done = max(rounds, self._start_round)
        self._dump_results()
        return self.history

    # ------------------------------------------------------------------
    # crash-resume: checkpoint/ integration.  Batches are keyed by absolute
    # round and gossip/sharing draws by fold_in(base_key, rnd), so a
    # restarted process that restores (params, opt_state, share_state) and
    # continues from the saved round reproduces the uninterrupted
    # trajectory exactly (test_resume.py pins this across a real process
    # restart).
    # ------------------------------------------------------------------
    def save_state(self, path: str, step: Optional[int] = None) -> str:
        """Checkpoint the node-stacked engine state plus the round cursor
        into ``path`` (directory).  Returns the checkpoint file path."""
        if self.dl.semantics != "sync":
            raise ValueError(
                "save_state captures the synchronous barrier state only; "
                "the local/async virtual clocks are not checkpointed yet"
            )
        from repro.checkpoint import save_checkpoint

        step = self.rounds_done if step is None else step
        return save_checkpoint(
            path, step, params=self.params, opt_state=self.opt_state,
            share_state=self.share_state,
        )

    def load_state(self, path: str, step: Optional[int] = None) -> int:
        """Restore a ``save_state`` checkpoint (latest in ``path`` unless
        ``step`` names one) and position ``run()`` to continue from it."""
        from repro.checkpoint import load_checkpoint, restore_tree

        step, trees = load_checkpoint(path, step)
        self.params = restore_tree(self.params, trees.get("params"))
        self.opt_state = restore_tree(self.opt_state, trees.get("opt_state"))
        self.share_state = restore_tree(
            self.share_state, trees.get("share_state")
        )
        self._start_round = self.rounds_done = int(step)
        return int(step)

    def _dump_results(self):
        """Per-node JSON results, DecentralizePy-style (aggregated later)."""
        if not self.dl.results_dir:
            return
        os.makedirs(self.dl.results_dir, exist_ok=True)
        with open(os.path.join(self.dl.results_dir, "results.json"), "w") as f:
            json.dump({"config": dataclasses.asdict(self.dl), "history": self.history}, f, indent=1)
