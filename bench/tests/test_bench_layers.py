"""Per-layer attribution (layers.py): scopes from the compiled programs'
op metadata, programs from the device's module intervals, the program's
``dl.*`` host spans, and the readers built on them — on hand-made traces,
on a profile recorded here on the CPU, and on traces recorded on a TPU v5e
chip (trimmed to two chunks)."""
import json
from pathlib import Path

import pytest

from _bench_path import BENCH

import harness  # noqa: E402
import layers  # noqa: E402
import traces  # noqa: E402
from layers import Layers, LayerOp, Span  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
EXISTING = ("idle_share", "mfu", "gossip_mix_nodes_roofline", "secure_mask.ms_per_round",
            "secure_mask_keyed_roofline", "resident_gb")
NEW = ("local_step.ms_per_round", "share_mix.ms_per_round", "flatten.ms_per_round",
       "stage.device_ms_per_chunk", "stage.ms_per_chunk", "stage_idle.ms_per_chunk")
MS = 1e6   # ns


def _ctx(rounds, window_s=1.0, traffic="dynamic-full"):
    cell = harness.load_cell(f"gnlenet-cifar10-n256.{traffic}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    cfg = cell.config
    return {"config": cfg, "rounds": rounds, "window_s": window_s, "n_nodes": 256,
            "n_local": 256, "params_per_node": 579594, "degree": 5, "chips": 1,
            "peaks": peaks, "resident_bytes": 1282800000,
            "flops": harness.load_module("flops", cfg["model"]["flops"])}


def _read(metric, trace, ctx):
    return harness.load_module("metrics", metric).read(trace, ctx)


# -- scopes from op metadata ---------------------------------------------------


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_chunk_fn)/while/body/closed_call/local_step/vmap(transpose(jvp()))/dot_general",
     "local_step"),
    ("jit(f)/transpose(jvp(share_mix))/mul", "share_mix"),
    ("jit(_chunk_fn)/while/body/closed_call/unflatten/vmap()/slice", "unflatten"),
    ("jit(_chunk_fn)/while/body/closed_call/flatten/vmap()/concatenate", "flatten"),
    ("share_mix/jvp(local_step)/add", "local_step"),      # the innermost wins
    ("jit(_chunk_fn)/while/body/local_stepper/add", ""),  # whole words only
    ("", ""),
])
def test_scope_of_reads_words_of_the_name_stack(op_name, scope):
    assert layers.scope_of(op_name) == scope


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(field, value):
    """One protobuf field: an int as a varint, bytes or str length-delimited."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _instr(iid, name, op_name="", operands=(), calls=()):
    meta = _f(7, _f(2, op_name)) if op_name else b""
    return _f(2, _f(1, name) + meta + _f(35, iid)
              + b"".join(_f(36, o) for o in operands) + b"".join(_f(38, c) for c in calls))


def test_hlo_scopes_fall_back_to_fused_instructions_then_operands():
    fused = _f(1, "fused_computation") + _f(5, 1) + _instr(1, "add.1", "jit(f)/share_mix/add") \
        + _instr(2, "param_0", "")
    entry = _f(1, "main") + _f(5, 2) + _instr(3, "param.1") \
        + _instr(4, "fusion.7", "", operands=[3], calls=[1]) \
        + _instr(5, "copy.2", "", operands=[4]) \
        + _instr(6, "dot.3", "jit(f)/local_step/dot_general", operands=[5])
    proto = _f(1, _f(1, "jit_f") + _f(3, fused) + _f(3, entry))
    assert layers.hlo_scopes(proto) == {"add.1": "share_mix", "param_0": "", "param.1": "",
                                        "fusion.7": "share_mix", "copy.2": "share_mix",
                                        "dot.3": "local_step"}


def test_hlo_scopes_agree_with_the_compiled_text():
    import re

    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("local_step"):
            y = jnp.tanh(x) @ x
        with jax.named_scope("share_mix"):
            return jnp.sum(y * 2.0, axis=0)

    compiled = jax.jit(f).lower(jnp.ones((8, 8))).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    scopes = layers.hlo_scopes(_f(1, module.as_serialized_hlo_module_proto()))
    named = dict(re.findall(r'%?([\w.\-]+) = [^\n]*op_name="([^"]*)"', compiled.as_text()))
    assert named and {"local_step", "share_mix"} <= set(scopes.values())
    for instr, op_name in named.items():
        if layers.scope_of(op_name):
            assert scopes[instr] == layers.scope_of(op_name), instr


# -- a profile recorded on the CPU ----------------------------------------------


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """Two chunks of a tiny dynamic-topology engine under the profiler,
    the second inside a ``bench.run_span`` span as the benchmark runs it."""
    import jax
    import jax.numpy as jnp

    from repro.core import DLConfig, RoundEngine
    from repro.data import NodeBatcher, make_dataset, sharding_partition
    from repro.optim import make_optimizer

    def loss(p, x, y):
        t = x.reshape(x.shape[0], -1).mean(0)
        return jnp.mean((p["w"].reshape(-1, t.shape[0]) - t) ** 2) + jnp.mean(p["b"] ** 2)

    ds = make_dataset("cifar10", n_train=128, n_test=16, shape=(2, 2, 1), sigma=2.0)
    parts = sharding_partition(ds.train_y, 8, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=4, seed=0)
    dl = DLConfig(n_nodes=8, topology="dynamic", degree=4, local_steps=1, batch_size=4,
                  rounds=4, chunk_rounds=2, eval_every=4)
    init = lambda key: {"w": jax.random.normal(key, (8,)), "b": jnp.zeros((3,))}
    eng = RoundEngine(dl, init, loss, lambda p, x, y: -loss(p, x, y),
                      make_optimizer("sgd", 0.05), batcher)
    eng.scheduler.run_span(0, 2)
    d = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(d)):
        with jax.profiler.TraceAnnotation("bench.run_span"):
            eng.scheduler.run_span(2, 2)
    return d


def test_load_keeps_host_spans_with_stats_and_parents(cpu_profile):
    run = layers.load(traces.find_xplane(cpu_profile))
    assert [(h.name, h.parent) for h in run.host] == [
        ("dl.run_span", -1), ("dl.stage", 0), ("dl.stage.batches", 1),
        ("dl.stage.graphs", 1), ("dl.dispatch", 0), ("dl.sync", 0)]
    assert run.host[0].stats["rnd"] == 2 and run.host[1].stats["bytes"] > 0
    assert layers.chunks(run) == 1
    assert layers.span_seconds(run, "dl.stage") == pytest.approx(
        (run.host[1].end - run.host[1].start) / 1e9)
    # no device plane on the CPU: nothing to attribute, and no reading
    assert run.ops == [] and layers.idle_under(run, "dl.stage") is None


def test_load_reads_the_programs_compiled_scopes(cpu_profile):
    # the profile holds every program the process has loaded, each under
    # its own id: other engines' chunks (an async one has no scopes) too
    scopes = layers.module_scopes(traces.find_xplane(cpu_profile).read_bytes())
    chunks = [set(v.values()) for k, v in scopes.items() if layers.module_name(k) == layers.CHUNK]
    assert any(set(layers.SCOPES) <= names for names in chunks)
    assert layers.STAGE in {layers.module_name(k) for k in scopes}


def test_of_finds_the_profile_a_trace_came_from(cpu_profile, tmp_path):
    path = traces.find_xplane(cpu_profile)
    trace = traces.load(path)
    found = layers.of(trace, cpu_profile)
    assert found is not None and found.spans == trace.spans and found.host
    assert layers.of(trace, tmp_path) is None
    assert layers.of(found) is found


# -- hand-made traces --------------------------------------------------------------


def _handmade():
    """Two chunks on one device: stage 0-3 ms (program 2-3), dispatch,
    chunk 4-10 ms with local_step 4-7, share_mix 7-9, unflatten 9-9.5,
    an unclaimed copy 9.5-10; sync to 11 ms; the second chunk the same
    12 ms later."""
    ops, programs, host = [], [], []
    for c in range(2):
        t = c * 12 * MS
        programs += [(0, layers.STAGE, t + 2 * MS, t + 3 * MS),
                     (0, layers.CHUNK, t + 4 * MS, t + 10 * MS)]
        ops += [LayerOp(0, "gather.1", t + 2 * MS, 1 * MS, False, layers.STAGE, ""),
                LayerOp(0, "while.1", t + 4 * MS, 6 * MS, False, layers.CHUNK, ""),
                LayerOp(0, "fusion.1", t + 4 * MS, 3 * MS, False, layers.CHUNK, "local_step"),
                LayerOp(0, "gossip_mix_nodes.3", t + 7 * MS, 2 * MS, False, layers.CHUNK,
                        "share_mix"),
                LayerOp(0, "slice.2", t + 9 * MS, 0.5 * MS, False, layers.CHUNK, "unflatten"),
                LayerOp(0, "copy.4", t + 9.5 * MS, 0.5 * MS, False, layers.CHUNK, "")]
        k = len(host)
        host += [Span("dl.run_span", t, t + 11 * MS, {"rnd": 8 * c}, -1),
                 Span("dl.stage", t, t + 3 * MS, {"bytes": 100}, k),
                 Span("dl.stage.batches", t + 1 * MS, t + 2.5 * MS, {}, k + 1),
                 Span("dl.dispatch", t + 3 * MS, t + 3.5 * MS, {}, k),
                 Span("dl.sync", t + 3.5 * MS, t + 11 * MS, {}, k)]
    spans = [("bench.run_span", 0.0, 11.5 * MS), ("bench.run_span", 11.5 * MS, 23 * MS),
             ("bench.block", 23 * MS, 24 * MS)]
    return Layers(ops, spans, programs, host)


def test_handmade_reductions():
    run = _handmade()
    assert layers.chunks(run) == 2
    assert layers.scope_seconds(run, ("local_step",)) == pytest.approx(6e-3)
    assert layers.scope_seconds(run, ("flatten", "unflatten")) == pytest.approx(1e-3)
    assert layers.program_seconds(run, layers.STAGE) == pytest.approx(2e-3)
    assert layers.span_seconds(run, "dl.stage") == pytest.approx(6e-3)
    # idle under dl.stage: 0-2 ms of each chunk (the program runs 2-3)
    assert layers.idle_under(run, "dl.stage") == pytest.approx(4e-3)
    share, rest = layers.claimed(run)
    assert share == pytest.approx(5.5 / 6) and rest == [["copy", pytest.approx(1e-3)]]


def test_idle_split_and_gaps_name_the_innermost_span():
    run = _handmade()
    split = layers.idle_split(run)
    # per chunk: 0-1 stage, 1-2 stage.batches, 3-3.5 dispatch, 3.5-4 and
    # 10-11 sync; then bench.run_span to 11.5 (12-23 likewise, 23-24 block)
    assert split == pytest.approx({"dl.stage": 2e-3, "dl.stage.batches": 2e-3,
                                   "dl.dispatch": 1e-3, "dl.sync": 3e-3,
                                   "bench.run_span": 1e-3, "bench.block": 1e-3})
    assert sum(split.values()) * 1e9 == pytest.approx(
        traces.idle_share(run) * (traces.window(run)[1] - traces.window(run)[0]))
    gaps = layers.idle_gaps(run, n=3)
    assert [g[0] for g in gaps] == ["dl.stage", "dl.stage.batches", "bench.block"]
    # traces.idle_gaps still names only the benchmark's spans
    assert traces.idle_gaps(run, n=1)[0][0] == "bench.run_span"


def test_new_readers_on_handmade_trace():
    run, ctx = _handmade(), _ctx(rounds=16)
    got = {m: _read(m, run, ctx) for m in NEW}
    assert got == pytest.approx({
        "local_step.ms_per_round": 6 / 16, "share_mix.ms_per_round": 4 / 16,
        "flatten.ms_per_round": 1 / 16, "stage.device_ms_per_chunk": 1.0,
        "stage.ms_per_chunk": 3.0, "stage_idle.ms_per_chunk": 2.0})


def test_new_readers_are_silent_on_a_program_without_spans_or_scopes():
    run = _handmade()
    bare = Layers([LayerOp(o.device, o.name, o.start, o.dur, o.in_flight, o.program, "")
                   for o in run.ops if o.program != layers.STAGE], run.spans,
                  [p for p in run.programs if p[1] != layers.STAGE], [])
    assert {m: _read(m, bare, _ctx(rounds=16)) for m in NEW} == {m: None for m in NEW}


def test_json_round_trip_and_trim():
    run = _handmade()
    back = Layers.from_json(json.loads(json.dumps(run.to_json())))
    assert back == run
    one = layers.trim(run, 1, 1)
    assert one.spans == [run.spans[1]] and layers.chunks(one) == 1
    assert [h.parent for h in one.host] == [-1, 0, 1, 0, 0]
    assert one.host[0].stats == {"rnd": 8}


# -- a trace recorded on the chip before the program had spans or scopes -----------


@pytest.fixture(scope="module")
def old_chip_trace():
    return traces.load_json_gz(DATA / "v5e_dynamic_full_two_chunks.json.gz")


def test_existing_metrics_read_what_they_read_before(old_chip_trace, tmp_path, monkeypatch):
    monkeypatch.setattr(layers, "TRACE_DIR", tmp_path)
    lo, hi = traces.window(old_chip_trace)
    ctx = _ctx(rounds=16, window_s=(hi - lo) / 1e9)
    got = {m: _read(m, old_chip_trace, ctx) for m in EXISTING + NEW}
    assert got == {
        "idle_share": pytest.approx(1.2066987047505084, rel=1e-12),
        "mfu": pytest.approx(1.0537504383994707, rel=1e-12),
        "gossip_mix_nodes_roofline": pytest.approx(87.3829400381934, rel=1e-12),
        "secure_mask.ms_per_round": None, "secure_mask_keyed_roofline": None,
        "resident_gb": pytest.approx(1.2828),
        **{m: None for m in NEW}}
    assert traces.idle_gaps(old_chip_trace, 2) == [["bench.run_span", 0.007867433],
                                                   ["bench.run_span", 0.006525316]]


# -- traces recorded on the chip with scopes, programs and dl.* spans -------------


@pytest.fixture(scope="module", params=["dynamic_full", "static_secure"])
def chip_layers(request):
    """Two chunks of each cell traced on one TPU v5e, trimmed."""
    return layers.load_json_gz(DATA / f"v5e_{request.param}_two_chunks_layers.json.gz")


def test_chip_scopes_claim_the_chunk_program(chip_layers):
    share, rest = layers.claimed(chip_layers)
    assert share >= 0.9, rest
    rounds = 8 * layers.chunks(chip_layers)
    chunk_ms = sum(o.dur for o in layers._in_window(chip_layers)
                   if o.program == layers.CHUNK) / 1e6 / rounds
    split = {m: _read(m, chip_layers, _ctx(rounds)) for m in NEW[:3]}
    assert sum(split.values()) == pytest.approx(chunk_ms, rel=0.1)


def test_chip_chunk_program_starts_after_its_dispatch_opens(chip_layers):
    """The host spans and the device's programs share one clock."""
    lo, hi = traces.window(chip_layers)
    runs = [h for h in chip_layers.host if h.name == "dl.run_span"]
    starts = [s for _, m, s, _ in chip_layers.programs if m == layers.CHUNK and lo <= s < hi]
    assert len(starts) == len(runs) == 2
    for start, run in zip(sorted(starts), sorted(runs, key=lambda h: h.start)):
        dispatch = next(h for h in chip_layers.host
                        if h.name == "dl.dispatch" and run.start <= h.start <= run.end)
        assert dispatch.start <= start <= run.end


def test_chip_readers_report_every_new_metric(chip_layers):
    ctx = _ctx(8 * layers.chunks(chip_layers))
    got = {m: _read(m, chip_layers, ctx) for m in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["stage.device_ms_per_chunk"] > 1.0       # the dataset relayout alone is ~5 ms


def test_chip_idle_gaps_between_chunks_name_program_spans(chip_layers):
    gaps = layers.idle_gaps(chip_layers, n=2)
    assert all(name.startswith("dl.") for name, _ in gaps), gaps
