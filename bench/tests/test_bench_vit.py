"""ViT-S/4 (``models/vit.py``) against its plain reference
(``bench/configs/vit_ref.py``) on seeded weights, on the CPU: the initial
weights, the logits and every gradient leaf at a small width; the
parameter count and the operations count at the published width."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_path import BENCH  # noqa: F401

import harness  # noqa: E402
from reference import flat_leaves  # noqa: E402

from repro.models.api import cross_entropy  # noqa: E402
from repro.models.vit import vit_apply, vit_init  # noqa: E402

CELL = "vits4-cifar10-n16.dynamic-full"
ref = harness.load_module("configs", "vit_ref")
flops = harness.load_module("flops", "vit")


def test_deit_s_width_has_the_published_parameter_count():
    shapes = jax.eval_shape(lambda k: vit_init(k, num_classes=10, channels=3, width=384),
                            jax.random.key(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 21_342_346 == harness.load_cell(CELL).config["model"]["params_per_node"]
    blocks = shapes["blocks"]
    assert blocks["qkv"]["w"].shape == (12, 384, 1152)
    assert blocks["fc1"]["w"].shape == (12, 384, 1536)
    assert shapes["pos"].shape == (65, 384)


@pytest.mark.parametrize("width", [8, 128])
def test_initial_weights_are_the_documented_rule(width):
    key = jax.random.key(2147483647 + 3)
    ours = flat_leaves(vit_init(key, num_classes=10, channels=3, width=width))
    theirs = flat_leaves(ref.init(key, width, 3, 10))
    assert sorted(ours) == sorted(theirs)
    for k in ours:
        np.testing.assert_array_equal(ours[k], theirs[k], err_msg=k)


@pytest.mark.parametrize("width", [32, 128])   # one head of 32; two heads of 64
def test_logits_and_gradients_match_the_reference(width):
    key = jax.random.key(7)
    params = ref.init(key, width, 3, 10)
    x = jax.random.normal(jax.random.key(8), (4, 32, 32, 3), jnp.float32)
    y = jnp.arange(4, dtype=jnp.int32) % 10
    ours = jax.jit(vit_apply)(params, x)
    theirs = jax.jit(ref.apply)(params, x)
    # both compute in float32 on the CPU; they differ only in the order of
    # operations (fused matmuls, rsqrt against 1/sqrt, the library softmax
    # and GELU against the written-out ones): 0.3e-6 and 1.2e-6 of the
    # largest logit over the 12 blocks, so 1e-5 leaves 8x room; bfloat16
    # weights would miss by ~1e-2
    scale = float(jnp.max(jnp.abs(theirs)))
    np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-5 * scale)
    g_ours = flat_leaves(jax.jit(jax.grad(lambda p: cross_entropy(vit_apply(p, x), y)))(params))
    g_ref = flat_leaves(jax.jit(jax.grad(ref.loss))(params, x, y))
    assert sorted(g_ours) == sorted(g_ref)
    for k in g_ref:
        # per leaf, against its own largest entry: the same rounding,
        # carried back through the 12 rematerialised blocks, reads at most
        # 2.2e-6 (a LayerNorm leaf), 45x under the tolerance
        tol = 1e-4 * float(jnp.max(jnp.abs(g_ref[k])))
        np.testing.assert_allclose(g_ours[k], g_ref[k], rtol=0, atol=tol, err_msg=k)


def test_operations_count_by_hand_and_against_xla():
    m = {"width": 384, "channels": 3, "num_classes": 10}
    per = flops.layer_flops(m)
    assert per == {"patch_embed": 2 * 64 * 48 * 384,           # 2.36 M
                   "block_matmuls": 2 * 65 * 12 * 12 * 384 ** 2,  # 2,760.4 M
                   "attention": 12 * 2 * 2 * 65 * 65 * 384,     # 77.9 M
                   "head": 2 * 384 * 10}
    assert flops.forward_flops(m) == 2_840_618_496
    assert flops.train_flops(m) == 3 * 2_840_618_496 - 2_359_296 == 8_519_496_192
    # XLA's count of the reference's forward pass (its blocks unrolled) also
    # holds LayerNorm, softmax, GELU and the adds, which the count leaves out
    shapes = jax.eval_shape(lambda k: ref.init(k, 384, 3, 10), jax.random.key(0))
    ca = jax.jit(ref.apply).lower(
        shapes, jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert 1.0 <= ca["flops"] / flops.forward_flops(m) < 1.04
