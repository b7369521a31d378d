"""Sharing strategies: sparse-aggregation algebra, CHOCO consensus,
byte accounting."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.sharing import (
    ChocoSGD,
    FullSharing,
    RandomKSharing,
    TopKSharing,
    make_sharing,
    sparse_aggregate,
)
from repro.core.topology import Graph


def _setup(n=8, p=64, seed=0):
    X = jax.random.normal(jax.random.key(seed), (n, p))
    g = Graph.regular_circulant(n, 4)
    W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
    return X, W, g


class TestSparseAggregate:
    @given(st.integers(0, 20))
    @settings(max_examples=10, deadline=None)
    def test_matches_loop_reference(self, seed):
        n, p = 6, 16
        X, W, _ = _setup(n, p, seed)
        M = jax.random.bernoulli(jax.random.key(seed + 100), 0.3, (n, p))
        got = sparse_aggregate(X, W, M)
        # reference: x_i'[c] = sum_j W_ij (m_j[c] x_j[c] + (1-m_j[c]) x_i[c])
        Xn, Wn, Mn = np.asarray(X), np.asarray(W), np.asarray(M, np.float32)
        want = np.zeros_like(Xn)
        for i in range(n):
            for c in range(p):
                want[i, c] = sum(
                    Wn[i, j] * (Mn[j, c] * Xn[j, c] + (1 - Mn[j, c]) * Xn[i, c])
                    for j in range(n)
                )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)

    def test_full_mask_equals_full_sharing(self):
        X, W, _ = _setup()
        M = jnp.ones_like(X, bool)
        np.testing.assert_allclose(
            sparse_aggregate(X, W, M), W @ X, rtol=2e-5, atol=1e-6
        )

    def test_empty_mask_is_identity(self):
        X, W, _ = _setup()
        M = jnp.zeros_like(X, bool)
        np.testing.assert_allclose(sparse_aggregate(X, W, M), X, rtol=1e-6)


class TestStrategies:
    def test_full_sharing_is_w_matmul(self):
        X, W, g = _setup()
        s = FullSharing()
        X2, _, nbytes = s.round(X, W, s.init_state(X), jax.random.key(0), 4.0)
        np.testing.assert_allclose(X2, W @ X, rtol=2e-5, atol=1e-6)
        assert nbytes == 4.0 * X.shape[1] * 4

    def test_randomk_budget_bytes(self):
        X, W, _ = _setup(p=1000)
        s = RandomKSharing(0.1)
        _, _, nbytes = s.round(X, W, s.init_state(X), jax.random.key(0), 4.0)
        assert nbytes == 4.0 * 100 * 8  # k=100, idx+val

    def test_topk_shares_biggest_changes(self):
        X, W, _ = _setup(n=6, p=50)
        s = TopKSharing(0.2)
        st_ = s.init_state(X)
        # change only 5 coords massively; they must be selected
        X2 = X.at[:, :5].add(100.0)
        _, st2, _ = s.round(X2, jnp.eye(6), st_, jax.random.key(0), 4.0)
        changed = np.asarray(st2["last_shared"] != st_["last_shared"])
        assert changed[:, :5].all()

    def test_choco_consensus(self):
        """Pure gossip (no gradients): CHOCO must drive all nodes toward the
        initial mean."""
        X, W, _ = _setup(n=8, p=32, seed=3)
        s = ChocoSGD(budget=0.3, gamma=0.5)
        state = s.init_state(X)
        target = np.asarray(X).mean(0)
        d0 = float(jnp.linalg.norm(X - target))
        Xc = X
        for r in range(60):
            Xc, state, _ = s.round(Xc, W, state, jax.random.fold_in(jax.random.key(9), r), 4.0)
        d1 = float(jnp.linalg.norm(Xc - target))
        assert d1 < 0.15 * d0, (d0, d1)
        np.testing.assert_allclose(np.asarray(Xc).mean(0), target, rtol=5e-2, atol=5e-2)

    def test_factory(self):
        assert isinstance(make_sharing("full"), FullSharing)
        assert isinstance(make_sharing("randomk", 0.2), RandomKSharing)
        assert isinstance(make_sharing("topk", 0.2), TopKSharing)
        assert isinstance(make_sharing("choco", 0.2, gamma=0.1), ChocoSGD)


class TestQuantizedSharing:
    def test_matches_full_within_quant_error(self):
        from repro.core.sharing import QuantizedSharing

        X, W, _ = _setup(n=8, p=256, seed=4)
        s = QuantizedSharing(stochastic=False)
        X2, _, nbytes = s.round(X, W, (), jax.random.key(0), 4.0)
        full = W @ X
        step = float(jnp.max(jnp.abs(X), axis=1).max()) / 127.0
        assert float(jnp.max(jnp.abs(X2 - full))) <= step * 1.01
        assert nbytes == 4.0 * (256 + 4)

    def test_runner_integration(self):
        from repro.core.sharing import QuantizedSharing, make_sharing

        assert isinstance(make_sharing("int8"), QuantizedSharing)
