"""Sparse neighbor-indexed mixing: SparseTopology tables == dense W for
every sharing strategy, churn reweighting, the Pallas kernel backends, and
the engine end-to-end (dense path kept as the equivalence oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import DLConfig, RoundEngine
from repro.core.mixing import (
    apply_W, gather_mix, gossip_operands, mix_dense, mix_sparse,
)
from repro.core.secure import SecureAggregation
from repro.core.sharing import (
    make_sharing,
    participation_reweight,
    participation_reweight_sparse,
)
from repro.core.topology import (
    Graph,
    PeerSampler,
    SparseTopology,
    random_regular_neighbors,
)
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.kernels import ops, ref
from repro.models.api import cross_entropy
from repro.models.mlp import mlp_apply, mlp_init
from repro.optim import make_optimizer


def _graphs(n=12):
    return {
        "ring": Graph.ring(n),
        "regular": Graph.regular_circulant(n, 4),
        "random-regular": Graph.random_regular(n, 4, seed=2),
    }


def _dev(st_):
    return SparseTopology(jnp.asarray(st_.nbr), jnp.asarray(st_.w), jnp.asarray(st_.w_self))


class TestSparseTopology:
    @pytest.mark.parametrize("name", ["ring", "regular", "random-regular"])
    def test_to_dense_matches_metropolis_hastings(self, name):
        g = _graphs()[name]
        st_ = SparseTopology.from_graph(g)
        np.testing.assert_allclose(st_.to_dense(), g.metropolis_hastings(), atol=1e-6)
        assert (np.asarray(st_.w_self) > 0).all()  # MH keeps diagonal mass

    def test_sampler_table_matches_graph(self):
        ps = PeerSampler(32, 5, seed=3)
        t = ps.round_table(9)
        np.testing.assert_allclose(t.to_dense(), ps.round_weights(9), atol=1e-6)

    def test_sparse_stack_shape_and_bytes(self):
        ps = PeerSampler(64, 6, seed=1)
        s = ps.sparse_stack(4, 5)
        assert s.nbr.shape == (5, 64, 6) and s.w.shape == (5, 64, 6)
        np.testing.assert_array_equal(s.nbr[3], ps.round_table(7).nbr)
        # O(N·d) staging: ~(2·d+1)/N of the (R, N, N) dense stack
        assert s.stage_bytes() < 0.25 * (5 * 64 * 64 * 4)

    def test_random_regular_neighbors_valid(self):
        n, d = 256, 6
        nbr = random_regular_neighbors(n, d, seed=11)
        assert nbr.shape == (n, d)
        rows = np.repeat(np.arange(n), d)
        assert (nbr != rows.reshape(n, d)).all()  # no self loops
        for r in range(0, n, 37):
            assert len(set(nbr[r])) == d  # no multi-edges
        # symmetry: i in nbr[j] iff j in nbr[i]
        edges = {(min(a, b), max(a, b)) for a, b in zip(rows, nbr.reshape(-1))}
        assert len(edges) == n * d // 2


class TestMixSparseEquivalence:
    @pytest.mark.parametrize("name", ["ring", "regular", "random-regular"])
    def test_pytree_matches_dense(self, name):
        g = _graphs()[name]
        st_ = _dev(SparseTopology.from_graph(g))
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        k1, k2 = jax.random.split(jax.random.key(0))
        t = {"a": jax.random.normal(k1, (g.n, 7, 3)),
             "b": jax.random.normal(k2, (g.n, 11))}
        d = mix_dense(t, W)
        s = mix_sparse(t, st_)
        for l1, l2 in zip(jax.tree_util.tree_leaves(d), jax.tree_util.tree_leaves(s)):
            np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                       rtol=2e-5, atol=2e-6)

    def test_pallas_backend_matches_xla(self):
        g = Graph.regular_circulant(16, 5)
        st_ = _dev(SparseTopology.from_graph(g))
        t = {"a": jax.random.normal(jax.random.key(1), (16, 300))}
        a = gather_mix(st_, t["a"])
        b = ops.gossip_mix_nodes(*gossip_operands(st_, t["a"]), interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)

    @given(st.integers(0, 10))
    @settings(max_examples=8, deadline=None)
    def test_apply_w_preserves_mean(self, seed):
        g = Graph.random_regular(16, 4, seed)
        st_ = _dev(SparseTopology.from_graph(g))
        X = jax.random.normal(jax.random.key(seed), (16, 33))
        Y = apply_W(st_, X)
        np.testing.assert_allclose(np.asarray(Y.mean(0)), np.asarray(X.mean(0)),
                                   rtol=2e-5, atol=2e-6)


class TestSharingStrategiesSparse:
    """Every strategy's round must be W-representation agnostic."""

    @pytest.mark.parametrize("strategy,kw", [
        ("full", {}), ("randomk", {}), ("topk", {}),
        ("choco", {"gamma": 0.4}), ("quant", {}),
    ])
    @pytest.mark.parametrize("name", ["ring", "regular", "random-regular"])
    def test_round_matches_dense(self, strategy, kw, name):
        g = _graphs()[name]
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        st_ = _dev(SparseTopology.from_graph(g))
        X = jax.random.normal(jax.random.key(5), (g.n, 96))
        budget = 0.2 if strategy not in ("full", "quant") else None
        s = make_sharing(strategy, budget, **kw)
        key = jax.random.key(6)
        deg = float(g.degrees().mean())
        Xd, std, bd = s.round(X, W, s.init_state(X), key, deg, rnd=1)
        Xs, sts, bs = s.round(X, st_, s.init_state(X), key, deg, rnd=1)
        np.testing.assert_allclose(np.asarray(Xd), np.asarray(Xs),
                                   rtol=5e-5, atol=5e-6)
        assert float(bd) == pytest.approx(float(bs))
        for a, b in zip(jax.tree_util.tree_leaves(std), jax.tree_util.tree_leaves(sts)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-5, atol=5e-6)

    @pytest.mark.parametrize("name", ["ring", "regular"])
    def test_secure_round_matches_dense(self, name):
        g = _graphs()[name]
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        st_ = _dev(SparseTopology.from_graph(g))
        X = jax.random.normal(jax.random.key(7), (g.n, 64))
        s = SecureAggregation(g.adj, mask_bound=1.5)
        key = jax.random.key(8)
        Xd, _, bd = s.round(X, W, (), key, degree=4.0, rnd=2)
        Xs, _, bs = s.round(X, st_, (), key, degree=4.0, rnd=2)
        # identical PRF bits either way; only the weight source differs
        np.testing.assert_allclose(np.asarray(Xd), np.asarray(Xs),
                                   rtol=2e-5, atol=2e-5)
        assert float(bd) == pytest.approx(float(bs))

    def test_secure_round_matches_reference_via_kernel(self):
        """The fused-kernel path keeps the reference oracle equivalence."""
        g = Graph.regular_circulant(10, 4)
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        X = jax.random.normal(jax.random.key(9), (10, 80))
        s = SecureAggregation(g.adj, mask_bound=2.0)
        key = jax.random.key(10)
        got, _, _ = s.round(X, W, (), key, degree=4.0, rnd=3)
        want, _, _ = s.round_reference(X, W, (), key, degree=4.0, rnd=3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestSparseReweight:
    @given(st.integers(0, 15))
    @settings(max_examples=10, deadline=None)
    def test_matches_dense_reweight(self, seed):
        g = Graph.random_regular(12, 4, seed)
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        st_ = _dev(SparseTopology.from_graph(g))
        act = jnp.asarray(
            np.random.default_rng(seed).random(12) < 0.6, jnp.float32
        )
        Wd, degd = participation_reweight(W, act)
        ts, degs = participation_reweight_sparse(st_, act)
        dense_of_sparse = SparseTopology(
            np.asarray(ts.nbr), np.asarray(ts.w), np.asarray(ts.w_self)
        ).to_dense()
        np.testing.assert_allclose(dense_of_sparse, np.asarray(Wd), atol=1e-6)
        assert float(degd) == pytest.approx(float(degs), abs=1e-5)

    def test_down_rows_identity_no_dense_materialization(self):
        g = Graph.regular_circulant(8, 4)
        st_ = _dev(SparseTopology.from_graph(g))
        act = jnp.asarray([1, 1, 0, 1, 1, 0, 1, 1], jnp.float32)
        ts, _ = participation_reweight_sparse(st_, act)
        w, ws = np.asarray(ts.w), np.asarray(ts.w_self)
        for i in (2, 5):
            assert (w[i] == 0).all() and ws[i] == pytest.approx(1.0)
        # surviving rows stay stochastic
        np.testing.assert_allclose(w.sum(1) + ws, np.ones(8), atol=1e-6)


class TestBatchedKernels:
    @pytest.mark.parametrize("B,K,M", [(4, 3, 100), (16, 7, 1000), (2, 2, 65536 + 3)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_gossip_mix_nodes(self, B, K, M, dtype):
        nb = jax.random.normal(jax.random.key(B * M), (B, K, M), jnp.float32).astype(dtype)
        w = jax.random.uniform(jax.random.key(1), (B, K))
        got = ops.gossip_mix_nodes(jnp.swapaxes(nb, 0, 1), w)  # slot-major
        want = ref.gossip_mix_nodes_ref(nb, w)
        tol = 1e-5 if dtype == jnp.float32 else 1e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=tol, atol=tol)

    @pytest.mark.parametrize("B,K,M", [(5, 4, 120), (12, 6, 900)])
    def test_secure_mask_apply_nodes(self, B, K, M):
        x = jax.random.normal(jax.random.key(0), (B, M))
        bits = jax.random.bits(jax.random.key(1), (B, K, M), jnp.uint32)
        signs = jnp.asarray(
            np.random.default_rng(2).choice([-1.0, 0.0, 1.0], (B, K)), jnp.float32
        )
        got = ops.secure_mask_apply_nodes(x, bits, signs, 0.8)
        want = ref.secure_mask_apply_nodes_ref(x, bits, signs, 0.8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def _engine(dl):
    ds = make_dataset("cifar10", n_train=256, n_test=64, sigma=0.8, shape=(8, 8, 3))
    parts = sharding_partition(ds.train_y, dl.n_nodes, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, 8, seed=0)

    def loss_fn(p, x, y):
        return cross_entropy(mlp_apply(p, x), y)

    def acc_fn(p, x, y):
        return (mlp_apply(p, x).argmax(-1) == y).mean()

    init = lambda k: mlp_init(k, in_dim=8 * 8 * 3, hidden=16)
    return RoundEngine(dl, init, loss_fn, acc_fn, make_optimizer("sgd", 0.05), batcher)


def _flat(params):
    return np.concatenate([np.asarray(l).reshape(-1)
                           for l in jax.tree_util.tree_leaves(params)])


class TestEngineSparseVsDense:
    @pytest.mark.parametrize("cfg", [
        dict(topology="regular", degree=4),
        dict(topology="dynamic", degree=5),
        dict(topology="regular", degree=4, participation=0.6),
        dict(topology="regular", degree=4, sharing="topk", budget=0.2),
        dict(topology="regular", degree=4, secure=True),
    ], ids=["regular", "dynamic", "churn", "topk", "secure"])
    def test_trajectories_match(self, cfg):
        outs = {}
        for mixing in ("dense", "sparse"):
            dl = DLConfig(n_nodes=8, rounds=4, eval_every=3, chunk_rounds=2,
                          seed=2, mixing=mixing, **cfg)
            e = _engine(dl)
            assert e.mix_mode == mixing
            e.run(log=False)
            outs[mixing] = (_flat(e.params), e.bytes_sent, e.sim_time_s)
        pd, bd, _ = outs["dense"]
        ps, bs, _ = outs["sparse"]
        np.testing.assert_allclose(ps, pd, rtol=5e-4, atol=5e-5)
        assert bs == pytest.approx(bd, rel=1e-6)

    def test_round_time_matches_dense(self):
        times = {}
        for mixing in ("dense", "sparse"):
            dl = DLConfig(n_nodes=8, topology="regular", degree=4, rounds=3,
                          eval_every=2, network="lan", compute_time_s=0.01,
                          mixing=mixing)
            e = _engine(dl)
            e.run(log=False)
            times[mixing] = e.sim_time_s
        assert times["sparse"] == pytest.approx(times["dense"], rel=1e-5)

    def test_auto_mode_selection(self):
        for topo, want in [("regular", "sparse"), ("ring", "sparse"),
                           ("dynamic", "sparse"), ("fully", "dense"),
                           ("star", "dense")]:
            dl = DLConfig(n_nodes=8, topology=topo, degree=4, rounds=1)
            assert _engine(dl).mix_mode == want, topo

    def test_sparse_dynamic_keeps_full_chunks(self):
        """The (R, N, D) stack is exempt from the W-stack byte cap: chunks
        stay at the requested length and staging is O(N·d) per round."""
        dl = DLConfig(n_nodes=128, topology="dynamic", degree=5, rounds=4,
                      eval_every=10, chunk_rounds=4, mixing="sparse")
        e = _engine(dl)
        assert e.chunk == 4
        e.run(log=False)
        # 4 rounds of (N, D) int32+f32 tables + (N,) diagonals ≪ 4·R·N²
        assert e.topo_stage_bytes_peak < 4 * 128 * 128 * 4

    def test_unknown_mixing_rejected(self):
        dl = DLConfig(n_nodes=8, mixing="banana")
        with pytest.raises(ValueError):
            _engine(dl)


class TestPayloadEquivalence:
    """Payload-form compressed sharing == the dense-mask oracle: every
    sparsified strategy, both W representations, quantized wire, the
    histogram selector, and the engine end-to-end across topology/churn."""

    @pytest.mark.parametrize("strategy,kw", [
        ("randomk", {}),
        ("randomk", {"sampler": "strided"}),
        ("topk", {}),
        ("choco", {"gamma": 0.4}),
        ("choco", {"compressor": "randk"}),
        ("randomk", {"quantize": "int8"}),
        ("topk", {"quantize": "int8"}),
        ("randomk", {"sampler": "strided", "quantize": "int8"}),
        ("topk", {"selector": "hist"}),
    ], ids=["randomk", "randomk-strided", "topk", "choco", "choco-randk",
            "randomk-int8", "topk-int8", "strided-int8", "topk-hist"])
    @pytest.mark.parametrize("name", ["ring", "random-regular"])
    def test_round_payload_matches_masked(self, strategy, kw, name):
        g = _graphs()[name]
        W = jnp.asarray(g.metropolis_hastings(), jnp.float32)
        st_ = _dev(SparseTopology.from_graph(g))
        X = jax.random.normal(jax.random.key(5), (g.n, 96))
        key = jax.random.key(6)
        outs = {}
        for payload in (True, False):
            s = make_sharing(strategy, 0.2, payload=payload, **kw)
            for Wf, tag in ((W, "dense"), (st_, "sparse")):
                X2, stt, nb = s.round(X, Wf, s.init_state(X), key, 4.0, rnd=1)
                outs[(payload, tag)] = (np.asarray(X2), float(nb),
                                        jax.tree_util.tree_leaves(stt))
        x_ref, nb_ref, st_ref = outs[(False, "dense")]
        for k_, (x2, nb, stt) in outs.items():
            np.testing.assert_allclose(x2, x_ref, rtol=5e-5, atol=5e-6,
                                       err_msg=str(k_))
            assert nb == pytest.approx(nb_ref), k_
            for a, b in zip(stt, st_ref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=5e-5, atol=5e-6)

    def test_quantized_payload_bytes_and_dtype(self):
        s = make_sharing("topk", 0.1, quantize="int8")
        X = jax.random.normal(jax.random.key(0), (8, 100))
        _, _, nb = s.round(X, jnp.eye(8), s.init_state(X), jax.random.key(1), 4.0)
        # k=10 coords: 4B index + 1B code each, + 4B per-node scale header
        assert float(nb) == pytest.approx(4.0 * (10 * 5 + 4))
        assert s.wire_dtype(np.float32) == np.dtype(np.int8)
        s32 = make_sharing("topk", 0.1)
        assert s32.wire_dtype(np.float32) == np.dtype(np.float32)

    def test_full_sharing_bytes_track_dtype(self):
        from repro.core.sharing import FullSharing

        s = FullSharing()
        Xb = jax.random.normal(jax.random.key(0), (4, 64)).astype(jnp.bfloat16)
        _, _, nb = s.round(Xb, jnp.eye(4), (), jax.random.key(1), 3.0)
        assert float(nb) == pytest.approx(3.0 * 64 * 2)  # bf16 = 2 bytes/val

    def test_make_sharing_rejects_unused_args(self):
        with pytest.raises(ValueError, match="does not apply"):
            make_sharing("full", 0.2)
        with pytest.raises(ValueError, match="does not apply"):
            make_sharing("quant", 0.2)
        with pytest.raises(ValueError, match="invalid kwargs"):
            make_sharing("topk", 0.2, banana=1)
        with pytest.raises(ValueError, match="invalid kwargs"):
            make_sharing("randomk", 0.2, gamma=0.5)
        # valid kwargs still forwarded
        assert make_sharing("quant", stochastic=False).stochastic is False
        assert make_sharing("randomk", 0.2, sampler="strided").sampler == "strided"

    def test_topk_quantized_error_feedback(self):
        """last_shared must record the *dequantized* wire value so the
        quantization residual stays in the delta and is re-shared."""
        from repro.core.compression import dequantize_int8, quantize_int8

        s = make_sharing("topk", 0.1, quantize="int8")
        X = jax.random.normal(jax.random.key(0), (6, 50))
        st0 = s.init_state(X)
        X1 = X.at[:, :5].add(100.0)
        _, st1, _ = s.round(X1, jnp.eye(6), st0, jax.random.key(1), 4.0)
        idx = np.asarray(jax.lax.top_k(jnp.abs(X1 - st0["last_shared"]), 5)[1])
        vals = np.take_along_axis(np.asarray(X1), idx, axis=1)
        codes, scale = quantize_int8(jnp.asarray(vals))
        want = np.asarray(dequantize_int8(codes, scale))
        got = np.take_along_axis(np.asarray(st1["last_shared"]), idx, axis=1)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        assert (np.abs(got - vals) > 0).any()  # residual is really nonzero

    def test_hist_selector_selects_above_threshold(self):
        from repro.core.sharing import _topk_idx

        x = jnp.abs(jax.random.normal(jax.random.key(3), (6, 4000)))
        k = 40
        idx = _topk_idx(x, k, selector="hist")
        assert idx.shape == (6, k)
        picked = np.asarray(jnp.take_along_axis(x, idx, axis=1))
        for r in range(6):
            assert len(set(np.asarray(idx[r]))) == k  # distinct
        # every selected magnitude within one fine bin of the exact top-k
        exact = np.asarray(jax.lax.top_k(x, k)[0])
        assert (picked.min(1) >= exact.min(1) * 0.95).all()


def _run_engine_pair(cfg, seed=2, rounds=4, n_nodes=8):
    """Engine trajectories with payload on vs off; everything else equal."""
    outs = {}
    for payload in ("on", "off"):
        dl = DLConfig(n_nodes=n_nodes, rounds=rounds, eval_every=3,
                      chunk_rounds=2, seed=seed, payload=payload, **cfg)
        e = _engine(dl)
        e.run(log=False)
        outs[payload] = (_flat(e.params), e.bytes_sent, e.share_stage_bytes,
                         e.wire_dtype)
    return outs


class TestEnginePayload:
    """DLConfig.payload on == off (the dense-mask oracle) end-to-end for
    every sparsified strategy × {static ring, dynamic 5-regular} ×
    {churn on/off} (the 8-device axis lives in test_sharded_engine)."""

    @pytest.mark.parametrize("churn", [False, True], ids=["all-up", "churn"])
    @pytest.mark.parametrize("topo", [
        dict(topology="ring"), dict(topology="dynamic", degree=5),
    ], ids=["ring", "dynamic"])
    @pytest.mark.parametrize("sharing", [
        dict(sharing="randomk", budget=0.2),
        dict(sharing="randomk", budget=0.2, randk_sampler="strided"),
        dict(sharing="topk", budget=0.2),
        dict(sharing="choco", budget=0.2),
    ], ids=["randomk", "randomk-strided", "topk", "choco"])
    def test_trajectories_match(self, sharing, topo, churn):
        cfg = {**sharing, **topo}
        if churn:
            cfg["participation"] = 0.6
        outs = _run_engine_pair(cfg)
        p_on, b_on, stage_on, dt_on = outs["on"]
        p_off, b_off, stage_off, _ = outs["off"]
        np.testing.assert_allclose(p_on, p_off, rtol=5e-4, atol=5e-5)
        assert b_on == pytest.approx(b_off, rel=1e-6)
        if sharing["sharing"] != "choco":  # choco stages payloads either way
            assert stage_on < stage_off  # compact payloads vs (N, P) masks
        assert dt_on == "float32"

    def test_quantized_payload_trajectories(self):
        outs = _run_engine_pair(dict(sharing="topk", budget=0.2,
                                     topology="ring", payload_quant=True))
        np.testing.assert_allclose(outs["on"][0], outs["off"][0],
                                   rtol=5e-4, atol=5e-5)
        assert outs["on"][3] == "int8"

    def test_chunk_invariance(self):
        """Payload trajectories must not depend on the scan chunking."""
        base = dict(sharing="topk", budget=0.2, topology="dynamic", degree=5)
        flats = {}
        for chunk in (1, 3, 4):
            dl = DLConfig(n_nodes=8, rounds=4, eval_every=4, chunk_rounds=chunk,
                          seed=3, payload="on", **base)
            e = _engine(dl)
            e.run(log=False)
            flats[chunk] = (_flat(e.params), e.bytes_sent)
        for chunk in (3, 4):
            np.testing.assert_array_equal(flats[chunk][0], flats[1][0])
            assert flats[chunk][1] == pytest.approx(flats[1][1])

    def test_payload_on_requires_sparsified(self):
        dl = DLConfig(n_nodes=8, sharing="full", payload="on")
        with pytest.raises(ValueError, match="sparsified"):
            _engine(dl)
        dl = DLConfig(n_nodes=8, sharing="full", payload_quant=True)
        with pytest.raises(ValueError, match="payload_quant"):
            _engine(dl)
        dl = DLConfig(n_nodes=8, sharing="topk", payload="banana")
        with pytest.raises(ValueError, match="payload mode"):
            _engine(dl)
        for kw in (dict(payload="on"), dict(payload_quant=True),
                   dict(randk_sampler="strided")):
            dl = DLConfig(n_nodes=8, topology="regular", degree=4,
                          secure=True, **kw)
            with pytest.raises(ValueError, match="secure"):
                _engine(dl)


class TestBatchedParticipationMask:
    def _engine(self, participation=0.5, seed=4):
        dl = DLConfig(n_nodes=16, topology="regular", degree=4, rounds=2,
                      participation=participation, seed=seed)
        return _engine(dl)

    def test_chunk_boundary_invariance(self):
        e = self._engine()
        full = e._participation_mask(0, 12)
        np.testing.assert_array_equal(full[3:7], e._participation_mask(3, 4))
        np.testing.assert_array_equal(full[7:12], e._participation_mask(7, 5))

    def test_at_least_one_alive_and_rate(self):
        e = self._engine(participation=0.05, seed=1)
        m = e._participation_mask(0, 400)
        assert (m.sum(1) >= 1).all()
        e2 = self._engine(participation=0.5, seed=1)
        m2 = e2._participation_mask(0, 400)
        assert abs(m2.mean() - 0.5) < 0.03

    def test_seed_dependence(self):
        a = self._engine(seed=1)._participation_mask(0, 8)
        b = self._engine(seed=2)._participation_mask(0, 8)
        assert (a != b).any()
