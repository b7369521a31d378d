"""Operations and bytes the metrics divide by, against what XLA and the
program's own operands say."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_path import BENCH  # noqa: F401

import harness  # noqa: E402
from flops import kernels  # noqa: E402


@pytest.mark.parametrize("width", [8, 32])
def test_gnlenet_forward_flops_against_cost_analysis(width):
    from repro.models.cnn import cnn_apply, cnn_init

    flops = harness.load_module("flops", "gnlenet")
    m = {"width": width, "channels": 3, "num_classes": 10}
    params = cnn_init(jax.random.key(0), width=width)
    batch = 2
    ca = jax.jit(cnn_apply).lower(params, jnp.ones((batch, 32, 32, 3))).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    ours = batch * flops.forward_flops(m)
    # XLA also counts the elementwise work (GroupNorm, ReLU, bias), which
    # the count leaves out: a few percent on top, never less
    assert 1.0 <= ca["flops"] / ours < 1.08
    assert flops.train_flops(m) == 3 * ours // batch - flops.layer_flops(m)["conv1"]


def test_gossip_bytes_match_the_kernel_operands():
    from repro.core.mixing import gossip_operands
    from repro.core.topology import SparseTopology

    n, p, d = 16, 1000, 5
    topo = SparseTopology.regular_circulant(n, d)
    topo = SparseTopology(jnp.asarray(topo.nbr), jnp.asarray(topo.w), jnp.asarray(topo.w_self))
    xs, ws = gossip_operands(topo, jnp.ones((n, p), jnp.float32))
    assert xs.shape == (1 + d, n, p)
    assert kernels.gossip_mix_nodes(n, p, d) == xs.nbytes + n * p * 4


def test_survival_and_secure_bytes_from_shapes():
    n, p = 16, 1000
    assert kernels.abs_survival_rows(n, p) == 4 * n * p + 2 * 4 * n * 128
    assert kernels.secure_mask_keyed(n, p, 5) == 8 * n * p + n * 5 * 12
