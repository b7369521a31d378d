"""Substrate: optimizers, data pipeline, checkpointing, compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import latest_checkpoint, load_checkpoint, save_checkpoint
from repro.core.compression import (
    dequantize_int4,
    dequantize_int8,
    quantize_int4,
    quantize_int8,
    delta_decode_indices,
    delta_encode_indices,
)
from repro.data import NodeBatcher, iid_partition, make_dataset, sharding_partition
from repro.data.partition import classes_per_node
from repro.optim import make_optimizer
from repro.optim.optimizers import apply_updates, clip_by_global_norm, global_norm


class TestOptimizers:
    @pytest.mark.parametrize("name,kw", [("sgd", {}), ("momentum", {}), ("adamw", {})])
    def test_quadratic_convergence(self, name, kw):
        opt = make_optimizer(name, 0.1, **kw)
        params = {"x": jnp.array([3.0, -2.0])}
        state = opt.init(params)
        for _ in range(200):
            g = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
            u, state = opt.update(g, state, params)
            params = apply_updates(params, u)
        assert float(jnp.abs(params["x"]).max()) < 1e-2, name

    def test_clip_by_global_norm(self):
        g = {"a": jnp.full((10,), 10.0)}
        gc = clip_by_global_norm(g, 1.0)
        np.testing.assert_allclose(float(global_norm(gc)), 1.0, rtol=1e-5)
        g2 = {"a": jnp.full((10,), 1e-3)}
        gc2 = clip_by_global_norm(g2, 1.0)
        np.testing.assert_allclose(np.asarray(gc2["a"]), np.asarray(g2["a"]))


class TestPartition:
    @given(st.integers(2, 32), st.integers(1, 4), st.integers(0, 5))
    @settings(max_examples=20, deadline=None)
    def test_sharding_partition_covers_exactly(self, n_nodes, shards, seed):
        labels = np.random.default_rng(seed).integers(0, 10, 640)
        parts = sharding_partition(labels, n_nodes, shards, seed)
        allidx = np.concatenate(parts)
        assert len(allidx) == len(labels)
        assert len(np.unique(allidx)) == len(labels)

    def test_two_sharding_limits_classes(self):
        """Paper: 2-sharding caps classes/node (~4 for CIFAR-10 @ 256)."""
        labels = np.random.default_rng(0).integers(0, 10, 12800)
        parts = sharding_partition(labels, 64, 2, 0)
        cpn = classes_per_node(labels, parts)
        assert cpn.max() <= 4 and cpn.mean() <= 3.5

    def test_iid_covers(self):
        labels = np.arange(100) % 7
        parts = iid_partition(labels, 8, 0)
        assert len(np.unique(np.concatenate(parts))) == 100

    def test_batcher_deterministic(self):
        ds = make_dataset("cifar10", n_train=256, n_test=64)
        parts = iid_partition(ds.train_y, 4, 0)
        b = NodeBatcher(ds.train_x, ds.train_y, parts, 8, seed=3)
        x1, y1 = b.batch(5, 0)
        x2, y2 = b.batch(5, 0)
        np.testing.assert_array_equal(x1, x2)
        x3, _ = b.batch(6, 0)
        assert (x1 != x3).any()
        assert x1.shape == (4, 8, 32, 32, 3)


class TestDatasets:
    def test_images_learnable_structure(self):
        ds = make_dataset("cifar10", n_train=512, n_test=128, sigma=0.5)
        # nearest-prototype classification must beat chance by a lot
        protos = ds.prototypes.reshape(10, -1)
        x = ds.test_x.reshape(len(ds.test_x), -1)
        pred = ((x[:, None, :] - protos[None]) ** 2).sum(-1).argmin(1)
        acc = (pred == ds.test_y).mean()
        assert acc > 0.9

    def test_lm_stream_shapes(self):
        ds = make_dataset("lm", n_train=32, n_test=8, seq_len=16, vocab=64)
        assert ds.train_x.shape == (32, 16)
        assert ds.train_x.max() < 64


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"layer": {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                          "b": np.zeros(3, np.float32)}}
        opt = {"mu": {"layer": {"w": np.ones((2, 3), np.float32)}}}
        save_checkpoint(str(tmp_path), 42, params=tree, opt_state=opt)
        assert latest_checkpoint(str(tmp_path)) == 42
        step, out = load_checkpoint(str(tmp_path))
        assert step == 42
        np.testing.assert_array_equal(out["params"]["layer"]["w"], tree["layer"]["w"])
        np.testing.assert_array_equal(out["opt_state"]["mu"]["layer"]["w"], 1.0)

    def test_multiple_steps(self, tmp_path):
        for s in (1, 5, 3):
            save_checkpoint(str(tmp_path), s, params={"w": np.zeros(2)})
        assert latest_checkpoint(str(tmp_path)) == 5


class TestCompression:
    @given(st.integers(0, 50))
    @settings(max_examples=15, deadline=None)
    def test_int8_roundtrip_bounded(self, seed):
        x = jax.random.normal(jax.random.key(seed), (4, 257)) * (seed + 1)
        c, s = quantize_int8(x)
        y = dequantize_int8(c, s)
        assert float(jnp.max(jnp.abs(y - x))) <= float(jnp.max(s)) * 0.51 + 1e-9

    def test_int4_roundtrip_bounded(self):
        x = jax.random.normal(jax.random.key(0), (2, 128))
        packed, s = quantize_int4(x)
        assert packed.shape == (2, 64)
        y = dequantize_int4(packed, s)
        assert float(jnp.max(jnp.abs(y - x))) <= float(jnp.max(s)) * 0.51 + 1e-9

    def test_stochastic_rounding_unbiased(self):
        x = jnp.full((1, 4096), 0.3)  # between quant levels
        outs = []
        for i in range(20):
            c, s = quantize_int8(x, key=jax.random.key(i))
            outs.append(np.asarray(dequantize_int8(c, s)).mean())
        assert abs(np.mean(outs) - 0.3) < 2e-3

    def test_delta_indices_roundtrip(self):
        idx = jnp.sort(jax.random.permutation(jax.random.key(0), 1000)[:64])[None]
        d = delta_encode_indices(idx)
        np.testing.assert_array_equal(np.asarray(delta_decode_indices(d)), np.asarray(idx))
