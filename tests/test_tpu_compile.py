"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Interpret-mode tests (test_kernels.py) cannot see what the TPU compiler
refuses: blocks off the (8, 128) tiling, or more fast memory than a kernel
may use.  These tests compile each kernel with ``interpret=False`` for a
described (not attached) v5e chip, at GN-LeNet's full width (579,594
parameters per node) over N=256 nodes of degree 5, and check that the
compiled program holds the kernel as a ``tpu_custom_call``.  Nothing runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import gossip_mix, secure_mask, sparsify

N, D, P = 256, 5, 579_594
Q = D * (D - 1) // 2  # co-neighbor slot pairs of a receiver
K_TOPK = int(0.01 * P)  # top-k sharing at budget 0.01


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep it out."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


CASES = {
    "gossip_mix_nodes": (
        lambda x, w: gossip_mix.gossip_mix_nodes(x, w, interpret=False),
        [((1 + D, N, P), jnp.float32), ((N, 1 + D), jnp.float32)],
    ),
    "secure_mask_keyed": (
        lambda x, k, s: secure_mask.secure_mask_apply_pairs_keyed(
            x, k, s, 1.0, interpret=False),
        [((D, N, P), jnp.float32), ((N, Q, 2), jnp.uint32), ((N, D, D), jnp.float32)],
    ),
    "abs_survival_rows": (
        lambda x, e: sparsify.abs_histogram_rows(x, e, interpret=False),
        [((N, P), jnp.float32), ((N, sparsify.NBINS), jnp.float32)],
    ),
    "topk_threshold_rows": (
        lambda x: sparsify.topk_threshold_rows(x, K_TOPK, interpret=False),
        [((N, P), jnp.float32)],
    ),
}
KERNEL_NAME = {"topk_threshold_rows": "abs_survival_rows"}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernel = KERNEL_NAME.get(case, case)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert any(kernel in line for line in calls), (case, calls[:3])
