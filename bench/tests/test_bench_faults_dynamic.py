"""A whole run of gnlenet-cifar10-n256.dynamic-full at a tiny size on the
CPU, past the harness's look for a chip: sound, it comes out correct; with
the timed path broken underneath, ``correct`` comes out false.

Half of each batch left out is caught by ``lead_spread_gap``: the fresh
random graph of every round mixes most of each node's batch noise away
within the chunk, but the nodes' spread in the output layer's bias, which
they all start equal, still carries it."""
import jax
import pytest

from _bench_faults import FAULTS, run_tiny

CELL = "gnlenet-cifar10-n256.dynamic-full"


def test_sound_run_is_correct():
    result = run_tiny(CELL)
    assert result["correct"], result["checks"]
    assert result["device"]["platform"] == jax.devices()[0].platform
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_step_is_not_correct(fault):
    result = run_tiny(CELL, FAULTS[fault])
    assert not result["correct"], (fault, result["checks"])
