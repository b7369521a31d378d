"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, secure_mask


class TestGossipMix:
    @pytest.mark.parametrize("K", [2, 3, 6, 10])
    @pytest.mark.parametrize("M", [1000, 65536, 70000])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, K, M, dtype):
        nb = jax.random.normal(jax.random.key(K * M), (K, M), jnp.float32).astype(dtype)
        w = jax.random.dirichlet(jax.random.key(1), jnp.ones(K))
        got = ops.gossip_mix(nb, w)
        want = ref.gossip_mix_ref(nb, w)
        tol = 1e-5 if dtype == jnp.float32 else 1e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
        )


class TestQuantize:
    @pytest.mark.parametrize("R,C", [(1, 256), (8, 1024), (3, 4096)])
    def test_deterministic(self, R, C):
        x = jax.random.normal(jax.random.key(R * C), (R, C)) * 3.0
        c, s = ops.quantize(x)
        cr, sr = ref.quantize_ref(x)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)

    def test_stochastic_matches_ref_bits(self):
        x = jax.random.normal(jax.random.key(0), (4, 512))
        noise = jax.random.uniform(jax.random.key(1), (4, 512))
        c, s = ops.quantize(x, noise)
        cr, sr = ref.quantize_ref(x, noise)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))

    def test_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.key(2), (2, 2048))
        c, s = ops.quantize(x)
        y = ops.dequantize(c, s)
        assert float(jnp.max(jnp.abs(y - x))) <= float(jnp.max(s)) * 0.51

    def test_dequantize(self):
        c = jnp.array([[-127, 0, 64, 127]], jnp.int8)
        s = jnp.array([[0.01]], jnp.float32)
        np.testing.assert_allclose(
            np.asarray(ops.dequantize(c, s)), [[-1.27, 0.0, 0.64, 1.27]], rtol=1e-6
        )


class TestSecureMaskKernel:
    @pytest.mark.parametrize("K,M", [(1, 4096), (4, 65536), (7, 70001)])
    def test_sweep(self, K, M):
        x = jax.random.normal(jax.random.key(M), (M,))
        bits = jax.random.bits(jax.random.key(K), (K, M), jnp.uint32)
        signs = jnp.where(jnp.arange(K) % 2 == 0, 1.0, -1.0)
        got = ops.secure_mask_apply(x, bits, signs, 0.7)
        want = ref.secure_mask_apply_ref(x, bits, signs, 0.7)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_pairwise_cancellation(self):
        """+mask and -mask from identical bits cancel exactly."""
        M = 10_000
        x = jax.random.normal(jax.random.key(0), (M,))
        bits = jax.random.bits(jax.random.key(1), (1, M), jnp.uint32)
        plus = ops.secure_mask_apply(x, bits, jnp.array([1.0]), 2.0)
        both = ops.secure_mask_apply(
            x, jnp.concatenate([bits, bits]), jnp.array([1.0, -1.0]), 2.0
        )
        np.testing.assert_allclose(np.asarray(both), np.asarray(x), atol=1e-6)
        assert float(jnp.abs(plus - x).mean()) > 0.5


class TestSparsify:
    @pytest.mark.parametrize("M", [50_000, 65536, 131072])
    def test_histogram_exact(self, M):
        x = jax.random.normal(jax.random.key(M), (M,))
        edges = jnp.exp(jnp.linspace(jnp.log(1e-6), jnp.log(6.0), 96))
        np.testing.assert_array_equal(
            np.asarray(ops.abs_histogram(x, edges)),
            np.asarray(ref.abs_histogram_ref(x, edges)),
        )

    def test_threshold_mask_exact(self):
        x = jax.random.normal(jax.random.key(5), (70_000,))
        vals, mask = ops.threshold_mask(x, 0.9)
        vr, mr = ref.threshold_mask_ref(x, 0.9)
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(mr))
        np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), rtol=1e-6)

    @pytest.mark.parametrize("k_frac", [0.01, 0.1, 0.3])
    def test_topk_approx_quality(self, k_frac):
        M = 100_000
        k = int(M * k_frac)
        x = jax.random.normal(jax.random.key(77), (M,))
        vals, mask, t = ops.topk_mask_approx(x, k)
        nsel = int(mask.sum())
        assert k <= nsel <= int(k * 1.35) + 8, (k, nsel)
        # everything selected must dominate everything dropped
        amin_sel = float(jnp.min(jnp.where(mask, jnp.abs(x), jnp.inf)))
        amax_drop = float(jnp.max(jnp.where(mask, 0.0, jnp.abs(x))))
        assert amin_sel >= amax_drop - 1e-6 or nsel == M


class TestScatterGossip:
    @pytest.mark.parametrize("N,P,K,k", [(4, 100, 3, 5), (8, 1000, 7, 11),
                                         (2, 65536 + 3, 2, 4)])
    def test_sweep(self, N, P, K, k):
        x = jax.random.normal(jax.random.key(N * P), (N, P))
        idx = jax.random.randint(jax.random.key(1), (N, K, k), 0, P)
        val = jax.random.normal(jax.random.key(2), (N, K, k))
        w = jax.random.uniform(jax.random.key(3), (N, K))
        got = ops.payload_mix_nodes(x, idx, val, w)
        want = ref.payload_mix_nodes_ref(x, idx, val, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_duplicate_indices_accumulate(self):
        """Two operands landing on the same coordinate must both apply."""
        x = jnp.zeros((1, 8))
        idx = jnp.array([[[3], [3]]], jnp.int32)
        val = jnp.array([[[1.0], [2.0]]])
        w = jnp.array([[0.5, 0.25]])
        out = ops.payload_mix_nodes(x, idx, val, w)
        np.testing.assert_allclose(np.asarray(out[0, 3]), 0.5 * 1.0 + 0.25 * 2.0,
                                   rtol=1e-6)
        assert float(jnp.abs(out).sum()) == pytest.approx(1.0)


class TestSparsifyRows:
    @pytest.mark.parametrize("N,P", [(4, 1000), (7, 65536 + 5)])
    def test_histogram_rows_exact(self, N, P):
        x = jax.random.normal(jax.random.key(N * P), (N, P))
        edges = jnp.sort(
            jnp.abs(jax.random.normal(jax.random.key(1), (N, 48))), axis=1
        )
        np.testing.assert_array_equal(
            np.asarray(ops.abs_histogram_rows(x, edges)),
            np.asarray(ref.abs_histogram_rows_ref(x, edges)),
        )

    @pytest.mark.parametrize("k_frac", [0.01, 0.1])
    def test_topk_threshold_rows_quality(self, k_frac):
        N, P = 6, 20_000
        k = int(P * k_frac)
        x = jax.random.normal(jax.random.key(77), (N, P))
        t = ops.topk_threshold_rows(x, k)
        nsel = np.asarray((jnp.abs(x) >= t[:, None]).sum(1))
        assert (nsel >= k).all() and (nsel <= int(k * 1.35) + 8).all(), nsel

    def test_zero_rows(self):
        t = ops.topk_threshold_rows(jnp.zeros((3, 256)), 4)
        assert (np.asarray(t) == 0).all()  # all-zero row: everything survives


class TestThreefryKernel:
    @pytest.mark.parametrize("P", [1, 9, 100, 257, 70001])
    def test_counter_bits_bit_identical_to_jax(self, P):
        """The positional threefry expansion must reproduce
        jax.random.bits exactly — the property the in-kernel generation
        of secure masks rests on."""
        key = jax.random.fold_in(jax.random.key(3), 7)
        want = np.asarray(jax.random.bits(key, (P,), jnp.uint32))
        kd = jax.random.key_data(key)
        got = ref.counter_bits_ref(kd[0], kd[1], jnp.arange(P), P)
        np.testing.assert_array_equal(np.asarray(got), want)

    @staticmethod
    def _pair_operands(D, B, padded):
        """Keys of the D(D-1)/2 slot pairs and (B, D, D) coefficients,
        signed as a receiver signs them (the own slot, which the kernel
        does not read, set to 1); a padded last slot (validf 0) masks
        nothing and takes no mask."""
        lo, hi = secure_mask.slot_pairs(D)
        keys = jax.random.bits(jax.random.key(D * B), (B, len(lo), 2), jnp.uint32)
        sign = np.random.default_rng(D).choice([-1.0, 1.0], (B, len(lo)))
        valid = np.ones((B, D))
        if padded:
            valid[:, -1] = 0.0
        signs = np.ones((B, D, D))
        signs[:, lo, hi] = sign * valid[:, hi]
        signs[:, hi, lo] = -sign * valid[:, lo]
        return keys, jnp.asarray(signs, jnp.float32)

    @pytest.mark.parametrize("D,B,M,padded", [
        (2, 3, 333, False),
        (5, 9, 333, True),
        (5, 4, 7001, True),
    ])
    def test_keyed_kernel_bit_identical_to_bits_kernel(self, D, B, M, padded):
        """secure_mask_apply_pairs_keyed(pair keys) == secure_mask_apply_nodes
        fed each message's pre-expanded jax.random bits (D slots, own slot
        zero-signed) — bit-for-bit, odd M; at D = 5, M = 7001 two message
        blocks of six inner tiles, the second block ragged."""
        xs = jax.random.normal(jax.random.key(7), (D, B, M))
        keys, signs = self._pair_operands(D, B, padded)
        got = secure_mask.secure_mask_apply_pairs_keyed(
            xs, keys, signs, 0.9, interpret=True)
        slot_keys, slot_signs = ref.pairs_to_slots(keys, signs)
        expand = jax.vmap(jax.vmap(lambda kd: jax.random.bits(
            jax.random.wrap_key_data(kd), (M,), jnp.uint32)))
        for s in range(D):
            want = ops.secure_mask_apply_nodes(xs[s], expand(slot_keys[s]),
                                               slot_signs[s], 0.9)
            np.testing.assert_array_equal(np.asarray(got[s]), np.asarray(want))

    @pytest.mark.parametrize("D,B,M", [(2, 3, 128), (5, 2, 70001)])
    def test_keyed_kernel_matches_ref(self, D, B, M):
        xs = jax.random.normal(jax.random.key(M), (D, B, M))
        keys, signs = self._pair_operands(D, B, padded=False)
        got = ops.secure_mask_apply_pairs_keyed(xs, keys, signs, 1.3)
        want = ref.secure_mask_apply_pairs_keyed_ref(xs, keys, signs, 1.3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_keyed_kernel_single_slot_is_unmasked(self):
        """D = 1: no co-neighbor pairs, so the message goes out as it is; a
        pair count that does not match D is refused."""
        xs = jax.random.normal(jax.random.key(4), (1, 3, 200))
        got = ops.secure_mask_apply_pairs_keyed(
            xs, jnp.zeros((3, 0, 2), jnp.uint32), jnp.zeros((3, 1, 1)))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(xs))
        with pytest.raises(ValueError):
            ops.secure_mask_apply_pairs_keyed(
                jnp.zeros((3, 3, 200)), jnp.zeros((3, 2, 2), jnp.uint32),
                jnp.zeros((3, 3, 3)))


class TestSSDChunk:
    @pytest.mark.parametrize("L,N,P,H", [(32, 16, 16, 2), (64, 32, 32, 4), (128, 64, 64, 2)])
    def test_sweep(self, L, N, P, H):
        G = 2
        key = jax.random.key(L * N)
        xdt = jax.random.normal(key, (G, L, H, P)) * 0.2
        Bc = jax.random.normal(jax.random.fold_in(key, 1), (G, L, N)) * 0.4
        Cc = jax.random.normal(jax.random.fold_in(key, 2), (G, L, N)) * 0.4
        cum = -jnp.cumsum(jax.random.uniform(jax.random.fold_in(key, 3), (G, L, H)) * 0.1, axis=1)
        y, st, dec = ops.ssd_chunk(xdt, Bc, Cc, cum)
        for g in range(G):
            yr, sr, dr = ref.ssd_chunk_ref(xdt[g], Bc[g], Cc[g], cum[g])
            np.testing.assert_allclose(np.asarray(y[g]), np.asarray(yr), rtol=3e-4, atol=2e-5)
            np.testing.assert_allclose(np.asarray(st[g]), np.asarray(sr), rtol=3e-4, atol=2e-5)
            np.testing.assert_allclose(np.asarray(dec[g]), np.asarray(dr), rtol=1e-5)

    def test_ssd_scan_equals_sequential_recurrence(self):
        B, nc, L, H, P, N = 1, 3, 16, 2, 8, 8
        key = jax.random.key(0)
        xdt = jax.random.normal(key, (B, nc, L, H, P)) * 0.2
        Bc = jax.random.normal(jax.random.fold_in(key, 1), (B, nc, L, N)) * 0.3
        Cc = jax.random.normal(jax.random.fold_in(key, 2), (B, nc, L, N)) * 0.3
        cum = -jnp.cumsum(jax.random.uniform(jax.random.fold_in(key, 3), (B, nc, L, H)) * 0.05, axis=2)
        yk = np.asarray(ops.ssd_scan(xdt, Bc, Cc, cum))
        S = nc * L
        xf = np.asarray(xdt).reshape(B, S, H, P)
        Bf = np.asarray(Bc).reshape(B, S, N)
        Cf = np.asarray(Cc).reshape(B, S, N)
        dA = np.diff(np.asarray(cum), axis=2, prepend=np.zeros((B, nc, 1, H))).reshape(B, S, H)
        h = np.zeros((B, H, N, P))
        ys = []
        for t in range(S):
            h = h * np.exp(dA[:, t])[:, :, None, None] + np.einsum(
                "bn,bhp->bhnp", Bf[:, t], xf[:, t]
            )
            ys.append(np.einsum("bn,bhnp->bhp", Cf[:, t], h))
        want = np.stack(ys, 1).reshape(B, nc, L, H, P)
        np.testing.assert_allclose(yk, want, rtol=3e-3, atol=1e-4)


class TestSWAAttention:
    @pytest.mark.parametrize("S,W,D", [(256, 128, 32), (512, 256, 64), (384, 128, 64)])
    def test_sweep(self, S, W, D):
        BH = 2
        key = jax.random.key(S + W)
        q = jax.random.normal(key, (BH, S, D))
        k = jax.random.normal(jax.random.fold_in(key, 1), (BH, S, D))
        v = jax.random.normal(jax.random.fold_in(key, 2), (BH, S, D))
        o = ops.swa_attention(q, k, v, W)
        for b in range(BH):
            want = ref.swa_attention_ref(q[b], k[b], v[b], W)
            np.testing.assert_allclose(np.asarray(o[b]), np.asarray(want), rtol=3e-4, atol=3e-5)

    def test_bf16(self):
        BH, S, W, D = 1, 256, 128, 32
        q = jax.random.normal(jax.random.key(0), (BH, S, D)).astype(jnp.bfloat16)
        k = jax.random.normal(jax.random.key(1), (BH, S, D)).astype(jnp.bfloat16)
        v = jax.random.normal(jax.random.key(2), (BH, S, D)).astype(jnp.bfloat16)
        o = ops.swa_attention(q, k, v, W)
        want = ref.swa_attention_ref(q[0], k[0], v[0], W)
        np.testing.assert_allclose(
            np.asarray(o[0], np.float32), np.asarray(want, np.float32), rtol=3e-2, atol=3e-2
        )
