"""Tracing inside the engine: the host spans every ``run_span`` opens
(``dl.run_span`` > ``dl.stage`` > ``dl.stage.batches`` /
``dl.stage.graphs``, then ``dl.dispatch`` and ``dl.sync``), their running
totals on the scheduler, the named scopes of the round step in the
compiled chunk's op metadata, and the one staging program."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DLConfig, RoundEngine
from repro.core import scheduler as scheduler_lib
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.optim import make_optimizer

SHAPE = (2, 2, 1)
SCOPES = ("local_step", "flatten", "share_mix", "unflatten")


def _loss(p, x, y):
    t = x.reshape(x.shape[0], -1).mean(0)
    return jnp.mean((p["w"].reshape(-1, t.shape[0]) - t) ** 2) + jnp.mean(p["b"] ** 2)


def _engine(**kw) -> RoundEngine:
    n = kw.setdefault("n_nodes", 8)
    ds = make_dataset("cifar10", n_train=128, n_test=16, shape=SHAPE, sigma=2.0)
    parts = sharding_partition(ds.train_y, n, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=4, seed=0)
    kw.setdefault("chunk_rounds", 2)
    dl = DLConfig(local_steps=1, batch_size=4, rounds=4, eval_every=4, **kw)
    # two leaves of two shapes, so flattening and unflattening are real ops
    init = lambda key: {"w": jax.random.normal(key, (8,)), "b": jnp.zeros((3,))}
    return RoundEngine(dl, init, _loss, lambda p, x, y: -_loss(p, x, y),
                       make_optimizer("sgd", 0.05), batcher)


def _host_spans(logdir):
    """[(name, start, end, stats)] of the ``dl.*`` events in a profile."""
    from jax.profiler import ProfileData

    path = sorted(logdir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dl."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(a, b):
    return b[1] <= a[1] and a[2] <= b[2]


@pytest.mark.parametrize("semantics", ["sync", "local", "async"])
def test_run_span_emits_nested_host_spans(tmp_path, semantics):
    e = _engine(topology="dynamic", degree=4, semantics=semantics)
    e.scheduler.run_span(0, 2)          # compile outside the profile
    before = dict(e.scheduler.host_s)
    with jax.profiler.trace(str(tmp_path)):
        e.scheduler.run_span(2, 2)
    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    assert names == ["dl.run_span", "dl.stage", "dl.stage.batches", "dl.stage.graphs",
                     "dl.dispatch", "dl.sync"]
    run, stage, batches, graphs, dispatch, sync = spans
    assert run[3]["rnd"] == 2
    assert int(stage[3]["bytes"]) > 0
    for child in (stage, dispatch, sync):
        assert _inside(child, run)
    assert _inside(batches, stage) and _inside(graphs, stage)
    assert stage[2] <= dispatch[1] and dispatch[2] <= sync[1]
    # the totals grew by the traced spans' durations (the clocks differ
    # by how long the annotation takes to open and close)
    host = e.scheduler.host_s
    assert host.counts["run_span"] == 2
    for name, start, end, _ in spans:
        grown = host[name[3:]] - before[name[3:]]
        assert grown == pytest.approx((end - start) / 1e9, abs=2e-3)


def test_static_topology_stages_no_graphs():
    e = _engine(topology="regular", degree=4)
    e.scheduler.run_span(0, 2)
    assert set(e.scheduler.host_s) == {"run_span", "stage", "stage.batches",
                                       "dispatch", "sync"}
    assert e.scheduler.host_s.counts == {k: 1 for k in e.scheduler.host_s}


@pytest.mark.parametrize("traffic", [
    dict(topology="dynamic", degree=4),
    dict(topology="regular", degree=4, secure=True),
], ids=["full", "secure"])
def test_chunk_op_metadata_names_the_round_layers(traffic):
    e = _engine(**traffic)
    sched = e.scheduler
    xs = sched._stage_xs(0, 2)
    text = sched._chunk_jit.lower(e.params, e.opt_state, e.share_state, xs).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in SCOPES:
        assert any(re.search(rf"\b{scope}\b", n) for n in op_names), scope


def test_stage_batches_matches_separate_takes():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 2, 2, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, size=64).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, 64, size=(2, 1, 8, 4)).astype(np.int32))
    bx, by = scheduler_lib.stage_batches(x, y, idx)
    np.testing.assert_array_equal(np.asarray(bx), np.asarray(jnp.take(x, idx, axis=0)))
    np.testing.assert_array_equal(np.asarray(by), np.asarray(jnp.take(y, idx, axis=0)))
    assert bx.dtype == x.dtype and by.dtype == y.dtype
    lowered = scheduler_lib.stage_batches.lower(x, y, idx)
    assert "jit_stage_batches" in lowered.as_text()


def test_fault_stats_are_folded_only_with_a_fault_axis():
    e = _engine(topology="regular", degree=4)
    # nothing reads the fault counters without a fault axis: the chunk's
    # seven fault-stat arrays are never pulled to the host
    e.scheduler._accum_faults(None)
    e.scheduler.run_span(0, 2)
    assert all(v == 0.0 for v in e.scheduler._fault_totals.values())
