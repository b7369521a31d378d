"""Secure aggregation: mask cancellation exactness, privacy of individual
messages, byte accounting, and the Bonawitz-style seed-recovery pass that
keeps cancellation exact under churn."""
import jax
import jax.numpy as jnp
import numpy as np

import pytest

from hypothesis import given, settings, strategies as st

from repro.core.secure import SEED_SHARE_BYTES, SecureAggregation
from repro.core.sharing import (
    participation_reweight,
    participation_reweight_sparse,
)
from repro.core.topology import Graph, SparseTopology


def _setup(n=8, p=128, degree=4, seed=0):
    g = Graph.regular_circulant(n, degree)
    X = jax.random.normal(jax.random.key(seed), (n, p))
    W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
    return g, X, W


class TestSecureAggregation:
    def test_aggregate_equals_plain(self):
        """Masks cancel: secure aggregate == plain MH aggregate (fp32 tol —
        the paper's 'precision loss' is this rounding)."""
        g, X, W = _setup()
        s = SecureAggregation(g.adj, mask_bound=1.0)
        X2, _, _ = s.round(X, W, (), jax.random.key(1), degree=4.0, rnd=0)
        np.testing.assert_allclose(np.asarray(X2), np.asarray(W @ X), rtol=5e-4, atol=5e-5)

    def test_messages_look_masked(self):
        """Each individual message must differ substantially from the raw
        model (one-time pad), even though aggregates match."""
        g, X, W = _setup()
        s = SecureAggregation(g.adj, mask_bound=5.0)
        msgs = s.messages(X, jax.random.key(2), 0)
        for (i, r), m in list(msgs.items())[:8]:
            diff = float(jnp.linalg.norm(m - X[i]) / jnp.linalg.norm(X[i]))
            assert diff > 0.5, (i, r, diff)

    def test_masks_differ_per_round(self):
        g, X, W = _setup()
        s = SecureAggregation(g.adj)
        m0 = s.messages(X, jax.random.key(3), 0)
        m1 = s.messages(X, jax.random.key(3), 1)
        k = next(iter(m0))
        assert not np.allclose(np.asarray(m0[k]), np.asarray(m1[k]))

    def test_byte_overhead_three_percent(self):
        g, X, W = _setup(p=1000)
        s = SecureAggregation(g.adj)
        _, _, nbytes = s.round(X, W, (), jax.random.key(0), degree=4.0, rnd=0)
        plain = 4.0 * 1000 * 4
        assert abs(nbytes / plain - 1.03) < 1e-6

    def test_single_neighbour_round_equals_plain(self):
        """Degree 1: a receiver has no co-neighbor pair, so its one message
        goes unmasked and the round is the plain MH aggregate."""
        g, X, W = _setup(n=12, degree=1, p=64)
        X2, _, _ = SecureAggregation(g.adj).round(X, W, (), jax.random.key(6),
                                                  degree=1.0, rnd=2)
        np.testing.assert_allclose(np.asarray(X2), np.asarray(W @ X), rtol=5e-4, atol=5e-5)

    def test_mean_preserved(self):
        g, X, W = _setup(n=12, degree=5, p=64)
        s = SecureAggregation(g.adj)
        X2, _, _ = s.round(X, W, (), jax.random.key(4), degree=5.0, rnd=7)
        np.testing.assert_allclose(
            np.asarray(X2).mean(0), np.asarray(X).mean(0), rtol=1e-3, atol=1e-4
        )


class TestVectorizedEquivalence:
    """The jittable masked path must equal both the Python-scheduled
    reference and plain (unmasked) MH mixing to fp32 tolerance."""

    @pytest.mark.parametrize("topo,degree", [("ring", 2), ("5-regular", 5)])
    def test_vectorized_equals_unmasked_mh(self, topo, degree):
        n, p = 12, 256
        g = Graph.ring(n) if topo == "ring" else Graph.regular_circulant(n, 5)
        X = jax.random.normal(jax.random.key(8), (n, p))
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        s = SecureAggregation(g.adj, mask_bound=1.0)
        X2, _, _ = s.round(X, W, (), jax.random.key(9), degree=float(degree), rnd=3)
        np.testing.assert_allclose(np.asarray(X2), np.asarray(W @ X),
                                   rtol=5e-4, atol=5e-5)

    @pytest.mark.parametrize("topo", ["ring", "5-regular"])
    def test_vectorized_equals_reference(self, topo):
        n, p = 10, 128
        g = Graph.ring(n) if topo == "ring" else Graph.regular_circulant(n, 5)
        X = jax.random.normal(jax.random.key(10), (n, p))
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        s = SecureAggregation(g.adj, mask_bound=2.0)
        key = jax.random.key(11)
        got, _, nb_v = s.round(X, W, (), key, degree=float(g.degrees().mean()), rnd=5)
        want, _, nb_r = s.round_reference(X, W, (), key,
                                          degree=float(g.degrees().mean()), rnd=5)
        # identical PRF keying -> identical masks; only summation order differs
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert float(nb_v) == pytest.approx(float(nb_r), rel=1e-6)

    def test_vectorized_round_is_jittable_with_traced_round_index(self):
        g, X, W = _setup(n=8, degree=4)
        s = SecureAggregation(g.adj)

        @jax.jit
        def f(X, W, key, rnd):
            X2, _, nb = s.round(X, W, (), key, degree=4.0, rnd=rnd)
            return X2, nb

        X2, nb = f(X, W, jax.random.key(12), jnp.int32(4))
        ref, _, _ = s.round_reference(X, W, (), jax.random.key(12), degree=4.0, rnd=4)
        np.testing.assert_allclose(np.asarray(X2), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestSeedRecovery:
    """Bonawitz seed recovery: with ``recovery=True`` and the participation
    mask passed as ``act``, the corrected masked aggregate must equal the
    churn-reweighted plain aggregate at fp32 tolerance — dropped senders'
    uncancelled pair masks are re-derived by surviving co-neighbors and
    subtracted (core/secure.py recovery pass)."""

    def _act(self, n, seed):
        """A churn mask with at least one down and one live node."""
        rng = np.random.default_rng(seed)
        act = (rng.random(n) > 0.4).astype(np.float32)
        act[rng.integers(n)] = 0.0
        act[rng.integers(n)] = 1.0
        return jnp.asarray(act)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_dense_recovery_equals_churn_reweighted(self, seed):
        g, X, W = _setup(n=12, degree=4, p=64, seed=seed % 97)
        act = self._act(12, seed)
        Wm, _ = participation_reweight(W, act)
        s = SecureAggregation(g.adj, mask_bound=1.0, recovery=True)
        X2, _, _ = s.round(X, Wm, (), jax.random.key(seed), degree=4.0,
                           rnd=seed % 13, act=act)
        want = np.asarray(Wm @ X)
        live = np.asarray(act) > 0
        np.testing.assert_allclose(np.asarray(X2)[live], want[live],
                                   rtol=5e-4, atol=5e-5)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_sparse_recovery_matches_dense_oracle(self, seed):
        g, X, W = _setup(n=12, degree=4, p=64, seed=seed % 89)
        act = self._act(12, seed)
        Wm, _ = participation_reweight(W, act)
        topo, _ = participation_reweight_sparse(SparseTopology.from_graph(g), act)
        s = SecureAggregation(g.adj, mask_bound=1.0, recovery=True)
        X2, _, _ = s.round(X, topo, (), jax.random.key(seed), degree=4.0,
                           rnd=seed % 11, act=act)
        live = np.asarray(act) > 0
        np.testing.assert_allclose(np.asarray(X2)[live], np.asarray(Wm @ X)[live],
                                   rtol=5e-4, atol=5e-5)

    def test_without_recovery_masks_do_not_cancel(self):
        """Negative control: skipping the recovery pass under churn leaves
        the dropped pairs' PRF masks in the aggregate."""
        g, X, W = _setup(n=12, degree=4, p=64, seed=0)
        act = self._act(12, 3)
        Wm, _ = participation_reweight(W, act)
        s = SecureAggregation(g.adj, mask_bound=1.0, recovery=True)
        X2, _, _ = s.round(X, Wm, (), jax.random.key(5), degree=4.0, rnd=2)
        live = np.asarray(act) > 0
        err = np.abs(np.asarray(X2)[live] - np.asarray(Wm @ X)[live]).max()
        assert err > 1e-2

    def test_recovery_doubles_stage_bytes(self):
        g, _, _ = _setup()
        plain = SecureAggregation(g.adj)
        rec = SecureAggregation(g.adj, recovery=True)
        assert rec.stage_bytes_per_round(8, 128) == 2 * plain.stage_bytes_per_round(8, 128)

    def test_prf_words_per_round(self):
        """One cipher word per position of each receiver's C(D, 2)
        co-neighbor pair masks; the recovery pass expands them again."""
        g = Graph.regular_circulant(12, 5)
        words = 12 * 10 * 1000
        assert SecureAggregation(g.adj).prf_words_per_round(12, 1000) == words
        rec = SecureAggregation(g.adj, recovery=True)
        assert rec.prf_words_per_round(12, 1000) == 2 * words

    def test_full_participation_recovery_is_a_noop(self):
        """With everyone live the recovery pass subtracts nothing: same
        result as the plain secure round."""
        g, X, W = _setup(n=8, degree=4, p=64)
        act = jnp.ones((8,), jnp.float32)
        s = SecureAggregation(g.adj, mask_bound=1.0, recovery=True)
        a, _, _ = s.round(X, W, (), jax.random.key(7), degree=4.0, rnd=1, act=act)
        b, _, _ = SecureAggregation(g.adj, mask_bound=1.0).round(
            X, W, (), jax.random.key(7), degree=4.0, rnd=1)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


class TestSeedRecoveryEngine:
    """End-to-engine: secure=True now runs under churn (and crash
    schedules) with secure_recovery=True, matching the plain engine's
    trajectory at fp32 tolerance on a single device (the 8-emulated-device
    equivalence lives in tests/test_sharded_engine.py)."""

    def _engine(self, **kw):
        from repro.core import DLConfig, RoundEngine
        from repro.data import NodeBatcher, make_dataset, sharding_partition
        from repro.optim import make_optimizer

        n = kw.setdefault("n_nodes", 12)
        ds = make_dataset("cifar10", n_train=256, n_test=32, shape=(2, 2, 1),
                          sigma=2.0)
        parts = sharding_partition(ds.train_y, n, 2, seed=0)
        batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=4, seed=0)
        kw.setdefault("chunk_rounds", 4)
        kw.setdefault("eval_every", 4)
        kw.setdefault("topology", "regular")
        kw.setdefault("degree", 4)
        dl = DLConfig(local_steps=1, batch_size=4, **kw)

        def loss(p, x, y):
            t = x.reshape(x.shape[0], -1).mean(0)
            return jnp.mean((p["w"].reshape(-1, t.shape[0]) - t) ** 2)

        init = lambda key: {"w": jax.random.normal(key, (8,))}
        return RoundEngine(dl, init, loss, lambda p, x, y: -loss(p, x, y),
                           make_optimizer("sgd", 0.05), batcher)

    def _w(self, e):
        return np.asarray(jax.vmap(lambda p: p["w"])(e.params))

    def test_secure_churn_matches_plain_trajectory(self):
        kw = dict(rounds=8, seed=3, participation=0.6)
        es = self._engine(secure=True, secure_recovery=True, **kw)
        es.run(log=False)
        ep = self._engine(**kw)
        ep.run(log=False)
        np.testing.assert_allclose(self._w(es), self._w(ep), rtol=1e-3,
                                   atol=1e-4)

    def test_secure_crash_schedule_matches_plain_trajectory(self):
        from repro.core import FaultPlan

        plan = FaultPlan(crashes=((2, 1, 4), (9, 3, -1)))
        kw = dict(rounds=8, seed=3, faults=plan)
        es = self._engine(secure=True, secure_recovery=True, **kw)
        es.run(log=False)
        ep = self._engine(**kw)
        ep.run(log=False)
        np.testing.assert_allclose(self._w(es), self._w(ep), rtol=1e-3,
                                   atol=1e-4)

    def test_recovery_bytes_accounted(self):
        e = self._engine(rounds=8, seed=3, secure=True, secure_recovery=True,
                         participation=0.6)
        e.run(log=False)
        rb = float(e.scheduler._fault_totals["recovery_bytes"])
        assert rb > 0
        assert rb % SEED_SHARE_BYTES == 0
        assert e.history[-1]["recovery_bytes"] == pytest.approx(rb)
        # recovery traffic is part of the wire-byte account
        clean = self._engine(rounds=8, seed=3, participation=0.6)
        clean.run(log=False)
        assert e.bytes_sent > clean.bytes_sent
