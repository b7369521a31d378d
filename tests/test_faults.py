"""Message-level fault injection (core.faults): FaultPlan validation, the
chunk/gather-invariant draw chain, crash schedules, edge-loss renormalization
(rows stay stochastic under arbitrary masks), the gathered round-time form
under per-edge fault masks, and the engine-level counter conservation
invariant ``faults_injected == faults_detected + faults_survived`` across
sync / local / async semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import DLConfig, FaultPlan, RoundEngine
from repro.core import faults as faults_lib
from repro.core.network import (
    gathered_round_times,
    node_round_times,
    paper_testbed,
)
from repro.core.sharing import (
    edge_readmit_sparse,
    edge_reweight,
    edge_reweight_sparse,
)
from repro.core.topology import Graph, SparseTopology, neighbor_table
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.optim import make_optimizer

SHAPE = (2, 2, 1)


def _loss(p, x, y):
    t = x.reshape(x.shape[0], -1).mean(0)
    return jnp.mean((p["w"].reshape(-1, t.shape[0]) - t) ** 2)


def _acc(p, x, y):
    return -_loss(p, x, y)


def _engine(p_dim: int = 8, **kw) -> RoundEngine:
    n = kw.setdefault("n_nodes", 12)
    ds = make_dataset("cifar10", n_train=256, n_test=32, shape=SHAPE, sigma=2.0)
    parts = sharding_partition(ds.train_y, n, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=4, seed=0)
    kw.setdefault("chunk_rounds", 4)
    kw.setdefault("eval_every", 4)
    kw.setdefault("topology", "regular")
    kw.setdefault("degree", 4)
    dl = DLConfig(local_steps=1, batch_size=4, **kw)
    init = lambda key: {"w": jax.random.normal(key, (p_dim,))}
    return RoundEngine(dl, init, _loss, _acc, make_optimizer("sgd", 0.05), batcher)


def _w(e):
    return np.asarray(jax.vmap(lambda p: p["w"])(e.params))


def _totals(e):
    return {k: float(v) for k, v in e.scheduler._fault_totals.items()}


def _assert_conserved(t):
    """The module invariant: no fault is silently dropped."""
    assert t["faults_injected"] == pytest.approx(
        t["faults_detected"] + t["faults_survived"], abs=1e-6
    )


# ---------------------------------------------------------------------------
# FaultPlan validation
# ---------------------------------------------------------------------------

class TestFaultPlanValidate:
    def test_defaults_valid(self):
        p = FaultPlan()
        assert p.validate() is p  # returns self, no raise
        FaultPlan(msg_loss=0.5, latency_spike_prob=0.1, corrupt_prob=0.01,
                  crashes=((0, 2, 5), (3, 1, -1))).validate()

    @pytest.mark.parametrize("kw", [
        dict(msg_loss=1.0),
        dict(msg_loss=-0.1),
        dict(latency_spike_prob=1.0),
        dict(latency_spike_factor=0.0),
        dict(corrupt_prob=1.5),
        dict(corrupt_mode="zap"),
        dict(retry_backoff_s=-1e-3),
        dict(retry_backoff_cap=-1),
        dict(crashes=((0, 2),)),           # wrong arity
        dict(crashes=((-1, 2, 5),)),       # bad node
        dict(crashes=((0, -2, 5),)),       # bad crash round
        dict(crashes=((0, 5, 5),)),        # restart <= crash
        dict(crashes=((0, 5, 2),)),
    ], ids=lambda kw: next(iter(kw)))
    def test_bad_plans_rejected(self, kw):
        with pytest.raises(ValueError, match="invalid FaultPlan"):
            FaultPlan(**kw).validate()

    def test_fault_axis_flags(self):
        assert not FaultPlan().any_faults
        assert FaultPlan(msg_loss=0.1).edge_faults
        assert FaultPlan(latency_spike_prob=0.1).edge_faults
        assert not FaultPlan(corrupt_prob=0.1).edge_faults
        assert FaultPlan(corrupt_prob=0.1).any_faults
        assert FaultPlan(crashes=((0, 1, 2),)).any_faults


# ---------------------------------------------------------------------------
# crash schedules
# ---------------------------------------------------------------------------

class TestCrashMask:
    PLAN = FaultPlan(crashes=((3, 2, 5), (7, 4, -1)))

    def test_windows(self):
        m = faults_lib.crash_mask(self.PLAN, 8, 0, 8)
        assert m.shape == (8, 8)
        # node 3 down for rounds [2, 5)
        np.testing.assert_array_equal(m[:, 3], [1, 1, 0, 0, 0, 1, 1, 1])
        # node 7 never restarts
        np.testing.assert_array_equal(m[:, 7], [1, 1, 1, 1, 0, 0, 0, 0])
        # everyone else untouched
        others = np.delete(m, [3, 7], axis=1)
        assert (others == 1).all()

    def test_chunk_slice_invariance(self):
        """Any chunking slices the same absolute-round schedule."""
        full = faults_lib.crash_mask(self.PLAN, 8, 0, 8)
        parts = np.vstack([
            faults_lib.crash_mask(self.PLAN, 8, 0, 3),
            faults_lib.crash_mask(self.PLAN, 8, 3, 5),
        ])
        np.testing.assert_array_equal(full, parts)


# ---------------------------------------------------------------------------
# the per-(round, node) draw chain
# ---------------------------------------------------------------------------

class TestEdgeDraws:
    PLAN = FaultPlan(msg_loss=0.3, latency_spike_prob=0.2, seed=7)

    def test_row_gather_invariance(self):
        """The realization is a pure function of (round, global node id):
        drawing for a row subset gives the bitwise rows of the full draw —
        what makes the cohort/gathered paths see the same faults."""
        key = faults_lib.fault_key(self.PLAN, 0)
        live, spike = faults_lib.edge_draws(key, 5, jnp.arange(16), 4, self.PLAN)
        rows = jnp.array([2, 9, 13])
        lsub, ssub = faults_lib.edge_draws(key, 5, rows, 4, self.PLAN)
        np.testing.assert_array_equal(np.asarray(live)[np.asarray(rows)], lsub)
        np.testing.assert_array_equal(np.asarray(spike)[np.asarray(rows)], ssub)

    def test_rounds_decorrelated(self):
        key = faults_lib.fault_key(self.PLAN, 0)
        a, _ = faults_lib.edge_draws(key, 1, jnp.arange(32), 6, self.PLAN)
        b, _ = faults_lib.edge_draws(key, 2, jnp.arange(32), 6, self.PLAN)
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_zero_rates_draw_nothing(self):
        plan = FaultPlan()
        key = faults_lib.fault_key(plan, 0)
        live, spike = faults_lib.edge_draws(key, 0, jnp.arange(8), 3, plan)
        assert (np.asarray(live) == 1).all() and (np.asarray(spike) == 0).all()

    def test_corruption_modes_are_nonfinite(self):
        X = jnp.ones((4, 6), jnp.float32)
        cmask = jnp.array([0.0, 1.0, 0.0, 1.0])
        for mode in ("nan", "bitflip"):
            bad = faults_lib.corrupt_rows(X, cmask, mode)
            det = np.asarray(faults_lib.nonfinite_rows(bad))
            np.testing.assert_array_equal(det, np.asarray(cmask))


# ---------------------------------------------------------------------------
# edge-loss renormalization: rows stay stochastic
# ---------------------------------------------------------------------------

class TestEdgeReweight:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_rows_stay_stochastic_under_arbitrary_masks(self, seed):
        """Property: for ANY {0,1} per-edge loss mask, the reweighted dense
        W keeps row sums == 1 with nonnegative entries, and surviving
        off-diagonal edges keep their weight."""
        rng = np.random.default_rng(seed)
        g = Graph.regular_circulant(12, 4)
        W = g.metropolis_hastings().astype(np.float32)
        live = (rng.random((12, 12)) > rng.random()).astype(np.float32)
        Wm = np.asarray(edge_reweight(jnp.asarray(W), jnp.asarray(live)))
        np.testing.assert_allclose(Wm.sum(1), 1.0, atol=1e-6)
        assert (Wm >= -1e-7).all()
        off = ~np.eye(12, dtype=bool)
        kept = off & (live > 0)
        np.testing.assert_allclose(Wm[kept], W[kept], atol=1e-7)
        assert (Wm[off & (live == 0)] == 0).all()

    @settings(max_examples=10, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_sparse_matches_dense(self, seed):
        """edge_reweight_sparse under a slot mask == dense edge_reweight
        under the slot-scattered mask (the sync sparse path's oracle)."""
        rng = np.random.default_rng(seed)
        topo = SparseTopology.regular_circulant(10, 4)
        live_slots = (rng.random(topo.w.shape) > 0.4).astype(np.float32)
        tm = edge_reweight_sparse(topo, jnp.asarray(live_slots))
        dense_live = np.ones((10, 10), np.float32)
        valid = np.asarray(topo.w) > 0
        rows = np.repeat(np.arange(10), topo.dmax).reshape(valid.shape)
        dense_live[rows[valid], np.asarray(topo.nbr)[valid]] = live_slots[valid]
        Wm = edge_reweight(jnp.asarray(topo.to_dense()), jnp.asarray(dense_live))
        np.testing.assert_allclose(
            np.asarray(tm.to_dense()), np.asarray(Wm), atol=1e-6
        )


# ---------------------------------------------------------------------------
# re-admission restore: reweight -> readmit round-trips to pristine
# ---------------------------------------------------------------------------

class TestEdgeReadmitRoundTrip:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_arbitrary_dead_set_sequences_round_trip_bitwise(self, seed):
        """Property: for ANY sequence of node dead-sets (deaths and
        rejoins in arbitrary order), recomputing the effective topology
        from the pristine table + live mask is row-stochastic at every
        intermediate state, and the moment everyone is live again the
        result is the pristine topology — **bitwise**, w_self included
        (the last-ulp trap: pristine w_self comes from a float64
        accumulation that fp32 ``1 - w.sum(-1)`` cannot reproduce)."""
        rng = np.random.default_rng(seed)
        n = 12
        topo0 = SparseTopology.regular_circulant(n, 4)
        w0 = np.asarray(topo0.w)
        ws0 = np.asarray(topo0.w_self)
        nbr = np.asarray(topo0.nbr)
        # a random walk over dead-sets, ending with everyone alive
        n_steps = rng.integers(2, 6)
        dead_sets = [set(rng.choice(n, size=rng.integers(1, n // 2),
                                    replace=False))
                     for _ in range(n_steps)] + [set()]
        for dead in dead_sets:
            live_nodes = np.ones(n, np.float32)
            for v in dead:
                live_nodes[v] = 0.0
            eff = edge_readmit_sparse(topo0, jnp.asarray(live_nodes[nbr]))
            w = np.asarray(eff.w)
            ws = np.asarray(eff.w_self)
            # row-stochastic at every intermediate state
            np.testing.assert_allclose(ws + w.sum(-1), 1.0, atol=1e-6)
            assert (w >= 0).all()
            # surviving edges keep their pristine weight exactly
            kept = (live_nodes[nbr] > 0) & (w0 > 0)
            np.testing.assert_array_equal(w[kept], w0[kept])
            if not dead:
                # full recovery: the pristine object itself, bitwise
                assert eff is topo0
                np.testing.assert_array_equal(w, w0)
                np.testing.assert_array_equal(ws, ws0)

    def test_readmit_matches_reweight_when_dead_remain(self):
        topo0 = SparseTopology.regular_circulant(10, 4)
        nbr = np.asarray(topo0.nbr)
        live_nodes = np.ones(10, np.float32)
        live_nodes[3] = 0.0
        mask = jnp.asarray(live_nodes[nbr])
        a = edge_readmit_sparse(topo0, mask)
        b = edge_reweight_sparse(topo0, mask)
        np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
        np.testing.assert_array_equal(
            np.asarray(a.w_self), np.asarray(b.w_self)
        )


# ---------------------------------------------------------------------------
# gathered round times under per-edge fault masks
# ---------------------------------------------------------------------------

class TestGatheredRoundTimes:
    @pytest.mark.parametrize("parallel", [False, True], ids=["serial", "nic"])
    def test_bitwise_row_slice_under_edge_masks(self, parallel):
        """The (C, D) gathered form stays the bitwise row-slice of the dense
        formula when edges are masked out by a per-edge fault mask."""
        n = 16
        g = Graph.regular_circulant(n, 5)
        nbr, valid = neighbor_table(g.adj)
        lat, gp = paper_testbed(n).matrices()
        plan = FaultPlan(msg_loss=0.4, seed=3)
        key = faults_lib.fault_key(plan, 0)
        live, _ = faults_lib.edge_draws(key, 2, jnp.arange(n), nbr.shape[1], plan)
        A = valid.astype(np.float32) * np.asarray(live)
        ct = np.linspace(0.01, 0.05, n).astype(np.float32)
        r = np.arange(n)[:, None]
        dense = node_round_times(A, lat[r, nbr], gp[r, nbr], 4e6, ct, parallel)
        rows = np.array([3, 7, 1, 11, 14])
        got = gathered_round_times(lat, gp, rows, nbr[rows], A[rows], 4e6,
                                   ct[rows], parallel)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(dense)[rows])

    def test_masked_edges_cost_nothing(self):
        n = 8
        g = Graph.ring(n)
        nbr, valid = neighbor_table(g.adj)
        lat, gp = paper_testbed(n).matrices()
        rows = np.arange(n)
        full = gathered_round_times(lat, gp, rows, nbr, valid.astype(np.float32),
                                    1e6, 0.0)
        none = gathered_round_times(lat, gp, rows, nbr, np.zeros_like(valid, np.float32),
                                    1e6, 0.0)
        assert (np.asarray(full) > 0).all()
        assert (np.asarray(none) == 0).all()


# ---------------------------------------------------------------------------
# engine-level fault injection: counters conserve in every scenario
# ---------------------------------------------------------------------------

class TestEngineFaults:
    def test_msg_loss_counters_and_divergence(self):
        plan = FaultPlan(msg_loss=0.3, seed=1)
        e = _engine(rounds=8, seed=3, faults=plan)
        e.run(log=False)
        t = _totals(e)
        _assert_conserved(t)
        assert t["faults_injected"] > 0
        # pure loss is absorbed by renormalization: survived-by-design
        assert t["faults_survived"] == t["faults_injected"]
        assert t["faults_detected"] == 0
        assert np.isfinite(_w(e)).all()
        clean = _engine(rounds=8, seed=3)
        clean.run(log=False)
        assert not np.allclose(_w(e), _w(clean))

    def test_msg_loss_dense_topology(self):
        """The dense-mixing branch uses the (N, N) edge_reweight path."""
        plan = FaultPlan(msg_loss=0.3, seed=1)
        e = _engine(rounds=8, seed=3, topology="fully", degree=0, faults=plan)
        e.run(log=False)
        t = _totals(e)
        _assert_conserved(t)
        assert t["faults_injected"] > 0
        assert np.isfinite(_w(e)).all()

    def test_faulty_trajectory_chunk_invariant(self):
        """Fault draws are pure functions of the absolute round, so the
        scan chunk length cannot change the trajectory."""
        plan = FaultPlan(msg_loss=0.25, latency_spike_prob=0.1, seed=5)
        e4 = _engine(rounds=8, seed=3, chunk_rounds=4, faults=plan)
        e4.run(log=False)
        e2 = _engine(rounds=8, seed=3, chunk_rounds=2, faults=plan)
        e2.run(log=False)
        np.testing.assert_allclose(_w(e4), _w(e2), rtol=2e-5, atol=1e-6)
        assert _totals(e4) == pytest.approx(_totals(e2))

    @pytest.mark.parametrize("mode", ["nan", "bitflip"])
    def test_corruption_detected_and_rolled_back(self, mode):
        plan = FaultPlan(corrupt_prob=0.2, corrupt_mode=mode, seed=2)
        e = _engine(rounds=8, seed=3, faults=plan)
        e.run(log=False)
        t = _totals(e)
        _assert_conserved(t)
        assert t["faults_injected"] > 0
        # both corruption modes are non-finite by construction: detection
        # is exact, and every detection rolls back to the snapshot
        assert t["faults_detected"] == t["faults_injected"]
        assert t["faults_recovered"] == t["faults_detected"]
        assert np.isfinite(_w(e)).all()

    def test_crash_schedule_counts_downtime(self):
        plan = FaultPlan(crashes=((3, 2, 5), (7, 4, -1)))
        e = _engine(rounds=8, seed=3, faults=plan)
        e.run(log=False)
        t = _totals(e)
        _assert_conserved(t)
        # node 3 down rounds [2,5) = 3, node 7 down rounds [4,8) = 4
        assert t["faults_injected"] == 7
        assert t["faults_survived"] == 7
        # crashed nodes freeze (churn machinery): run still converges finite
        assert np.isfinite(_w(e)).all()

    def test_latency_spikes_slow_the_clock(self):
        plan = FaultPlan(latency_spike_prob=0.5, latency_spike_factor=10.0,
                         seed=4)
        kw = dict(rounds=8, seed=3, network="lan", compute_time_s=0.01)
        e = _engine(faults=plan, **kw)
        e.run(log=False)
        clean = _engine(**kw)
        clean.run(log=False)
        t = _totals(e)
        _assert_conserved(t)
        assert t["faults_survived"] == t["faults_injected"] > 0
        # delivered-but-late: same trajectory, slower virtual clock
        np.testing.assert_allclose(_w(e), _w(clean), rtol=2e-5, atol=1e-6)
        assert e.sim_time_s > 1.5 * clean.sim_time_s

    def test_local_semantics_msg_loss_with_churn(self):
        plan = FaultPlan(msg_loss=0.2, seed=6)
        e = _engine(rounds=8, seed=3, semantics="local", participation=0.7,
                    network="lan", compute_time_s=0.01, faults=plan)
        e.run(log=False)
        t = _totals(e)
        _assert_conserved(t)
        assert t["faults_injected"] > 0
        assert np.isfinite(_w(e)).all()

    def test_async_neighborhood_msg_loss(self):
        plan = FaultPlan(msg_loss=0.2, seed=6)
        e = _engine(rounds=12, seed=3, semantics="async", network="lan",
                    compute_time_s=0.01, faults=plan)
        e.run(log=False)
        t = _totals(e)
        _assert_conserved(t)
        assert t["faults_injected"] > 0
        assert np.isfinite(_w(e)).all()

    def test_async_pairwise_retry_backoff(self):
        """Failed pairwise exchanges retry with exponential backoff on the
        virtual clock; a later success after >=1 failure counts recovered."""
        plan = FaultPlan(msg_loss=0.35, retry_backoff_s=1e-3, seed=8)
        e = _engine(rounds=24, seed=3, semantics="async",
                    async_gossip="pairwise", network="lan",
                    compute_time_s=0.01, faults=plan)
        e.run(log=False)
        t = _totals(e)
        _assert_conserved(t)
        assert t["retry_total"] > 0
        assert t["faults_detected"] == t["retry_total"]  # every loss detected
        assert t["faults_recovered"] > 0                  # some retries landed
        assert np.isfinite(_w(e)).all()

    def test_history_carries_fault_metrics(self):
        plan = FaultPlan(msg_loss=0.2, seed=1)
        e = _engine(rounds=8, seed=3, faults=plan)
        e.run(log=False)
        rec = e.history[-1]
        for k in faults_lib.STAT_KEYS:
            assert k in rec
        assert rec["faults_injected"] == int(round(_totals(e)["faults_injected"]))

    def test_fault_free_history_stays_clean(self):
        e = _engine(rounds=4, seed=3)
        e.run(log=False)
        assert "faults_injected" not in e.history[-1]
