"""The reduction from trace to metrics, on a trace recorded on a TPU v5e
chip (trimmed) and on hand-made intervals."""
from pathlib import Path

import pytest

from _bench_path import BENCH

import traces  # noqa: E402
from traces import Op, Trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _trace(ops, spans):
    return Trace([Op(d, n, s, e - s) for d, n, s, e in ops], spans)


def test_union_and_subtract():
    assert traces.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert traces.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert traces.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_idle_share_and_busy_seconds():
    # the scan's loop spans the whole window, but its gaps are idle
    t = _trace([(0, "while.3", 0, 10e9), (0, "fusion.1", 0, 4e9), (0, "fusion.2", 2e9, 6e9),
                (0, "copy", 8e9, 9e9)],
               [("bench.run_span", 0.0, 10e9)])
    assert traces.busy_seconds(t) == pytest.approx(7.0)
    assert traces.idle_share(t) == pytest.approx(0.3)
    assert traces.idle_gaps(t) == [["bench.run_span", 2.0], ["bench.run_span", 1.0]]


def test_exposed_collective_share_averages_over_devices():
    t = _trace([(0, "all-gather.1", 0, 4e9), (0, "fusion.1", 2e9, 3e9),
                (1, "all-gather.1", 0, 2e9), (1, "fusion.1", 0, 2e9)],
               [("bench.run_span", 0.0, 10e9)])
    # device 0: 3 s of collective alone; device 1: none
    assert traces.exposed_collective_share(t) == pytest.approx(0.15)
    assert traces.exposed_collective_share(_trace([(0, "fusion.1", 0, 1e9)],
                                                  [("bench.block", 0.0, 2e9)])) is None


def test_kernel_time_counts_only_the_window():
    t = _trace([(0, "gossip_mix_nodes", 1e9, 2e9), (0, "gossip_mix_nodes", 11e9, 12e9)],
               [("bench.run_span", 0.0, 10e9)])
    assert [o.start for o in traces.kernel_ops(t, "gossip_mix_nodes")] == [1e9]


@pytest.fixture(scope="module")
def chip_trace():
    """Two chunks of gnlenet-cifar10-n256.dynamic-full traced on one TPU v5e."""
    return traces.load_json_gz(DATA / "v5e_dynamic_full_two_chunks.json.gz")


def test_chip_trace_busy_union_against_a_timeline(chip_trace):
    import numpy as np

    lo, hi = traces.window(chip_trace)
    us = np.zeros(int((hi - lo) // 1000) + 1, bool)   # one cell a microsecond
    for o in chip_trace.on(0):
        if traces.is_container(o):
            continue
        a, b = max(o.start, lo), min(o.end, hi)
        if b > a:
            us[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    busy = traces.busy_seconds(chip_trace)
    assert busy == pytest.approx(us.sum() / 1e6, abs=2e-3)
    assert traces.idle_share(chip_trace) == pytest.approx(1 - busy * 1e9 / (hi - lo))
    assert 0 < traces.idle_share(chip_trace) < 0.1


def test_chip_trace_kernel_time(chip_trace):
    calls = traces.kernel_ops(chip_trace, "gossip_mix_nodes")
    assert len(calls) == 16                       # one a round, two chunks of 8
    per_call = sum(o.dur for o in calls) / len(calls) / 1e6
    assert 3 < per_call < 10                      # ms: 4.15 GB at most 819 GB/s is >= 5 ms
    assert traces.kernel_ops(chip_trace, "secure_mask_keyed") == []


def test_chip_trace_has_no_collectives_on_one_chip(chip_trace):
    assert traces.exposed_collective_share(chip_trace) is None
    kinds = [k for k, _ in traces.top_ops(chip_trace)]
    assert "gossip_mix_nodes" in kinds and "while" not in kinds
