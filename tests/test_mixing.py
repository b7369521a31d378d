"""Mixing strategies: all lowerings compute the same math; gossip
preserves the global average (the consensus invariant D-PSGD relies on)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.mixing import mix_circulant, mix_dense, mix_fully
from repro.core.topology import Graph


def _tree(key, n):
    k1, k2 = jax.random.split(key)
    return {
        "a": jax.random.normal(k1, (n, 7, 3)),
        "b": {"c": jax.random.normal(k2, (n, 11))},
    }


class TestDense:
    @given(st.integers(4, 32), st.integers(0, 5))
    @settings(max_examples=15, deadline=None)
    def test_preserves_mean(self, n, seed):
        g = Graph.regular_circulant(n, min(4, n - 1) // 2 * 2)
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        t = _tree(jax.random.key(seed), n)
        t2 = mix_dense(t, W)
        for l1, l2 in zip(jax.tree_util.tree_leaves(t), jax.tree_util.tree_leaves(t2)):
            np.testing.assert_allclose(l1.mean(0), l2.mean(0), rtol=2e-5, atol=2e-6)

    def test_identity_on_identity_w(self):
        t = _tree(jax.random.key(0), 8)
        t2 = mix_dense(t, jnp.eye(8))
        for l1, l2 in zip(jax.tree_util.tree_leaves(t), jax.tree_util.tree_leaves(t2)):
            np.testing.assert_allclose(l1, l2, rtol=1e-6)


class TestCirculantEquivalence:
    @pytest.mark.parametrize("n,degree", [(16, 2), (16, 4), (16, 5), (12, 3), (32, 5)])
    def test_matches_dense(self, n, degree):
        g = Graph.regular_circulant(n, degree)
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        t = _tree(jax.random.key(1), n)
        d = mix_dense(t, W)
        c = mix_circulant(t, n, degree)
        for l1, l2 in zip(jax.tree_util.tree_leaves(d), jax.tree_util.tree_leaves(c)):
            np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-6)

    def test_fully_is_mean(self):
        t = _tree(jax.random.key(2), 8)
        f = mix_fully(t)
        for l1, l2 in zip(jax.tree_util.tree_leaves(t), jax.tree_util.tree_leaves(f)):
            np.testing.assert_allclose(
                np.broadcast_to(l1.mean(0, keepdims=True), l1.shape), l2, rtol=1e-5
            )


SHMAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.mixing import mix_circulant, mix_circulant_shmap, mix_dense
    from repro.core.topology import Graph
    mesh = jax.make_mesh((8,), ("data",))
    n, degree = 8, 4
    t = {"a": jax.random.normal(jax.random.key(0), (n, 5, 3)),
         "b": jax.random.normal(jax.random.key(1), (n, 9))}
    W = jnp.asarray(Graph.regular_circulant(n, degree).metropolis_hastings(), jnp.float32)
    dense = mix_dense(t, W)
    sh = mix_circulant_shmap(t, mesh, ("data",), degree)
    for l1, l2 in zip(jax.tree_util.tree_leaves(dense), jax.tree_util.tree_leaves(sh)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-5, atol=2e-6)
    print("SHMAP_OK")
""")


class TestShardMapPath:
    def test_collective_permute_path_matches_dense(self):
        """The ppermute lowering runs on an 8-fake-device mesh in a
        subprocess (device count is locked at jax init)."""
        r = subprocess.run(
            [sys.executable, "-c", SHMAP_SCRIPT], capture_output=True, text=True,
            timeout=300,
        )
        assert "SHMAP_OK" in r.stdout, r.stdout + r.stderr
