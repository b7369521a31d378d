"""The model's own scopes inside the local step (model_scopes.py) and the
readers ``attention.ms_per_round`` and ``mlp.ms_per_round``: on hand-made
HLO and traces, on the ViT's program compiled here, and on one chunk of
``vits4-cifar10-n16.dynamic-full`` traced on a TPU v5e chip (trimmed)."""
import json
import re
from pathlib import Path

import pytest

from _bench_path import BENCH

import harness  # noqa: E402
import layers  # noqa: E402
import model_scopes  # noqa: E402
import traces  # noqa: E402
from layers import LayerOp, Layers  # noqa: E402
from model_scopes import ScopedRun  # noqa: E402
from test_bench_layers import MS, _f, _instr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CELL = "vits4-cifar10-n16.dynamic-full"
READERS = ("attention.ms_per_round", "mlp.ms_per_round")


def _read(metric, trace, ctx):
    return harness.load_module("metrics", metric).read(trace, ctx)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_chunk_fn)/while/body/closed_call/local_step/vmap(jvp(while/body/checkpoint/"
     "attention))/dot_general", "attention"),
    ("jit(_chunk_fn)/local_step/vmap(transpose(jvp(while/body/rematted_computation/mlp)))/"
     "dot_general", "mlp"),
    ("jit(f)/patch_embed/reshape", "patch_embed"),
    ("jit(f)/local_step/vmap(jvp(head))/dot_general", "head"),
    ("jit(f)/mlp/attention/add", "attention"),      # the innermost wins
    ("jit(f)/local_step/attention_mask/add", ""),   # whole words only
    ("jit(_chunk_fn)/while/body/closed_call/share_mix/gather", ""),
])
def test_scope_of_reads_the_model_words(op_name, scope):
    assert model_scopes.scope_of(op_name) == scope


def test_hlo_scopes_fall_back_to_fused_instructions_then_operands():
    fused = _f(1, "fused_computation") + _f(5, 1) + _instr(1, "dot.1", "jit(f)/mlp/dot_general") \
        + _instr(2, "param_0", "")
    entry = _f(1, "main") + _f(5, 2) + _instr(3, "param.1") \
        + _instr(4, "fusion.7", "", operands=[3], calls=[1]) \
        + _instr(5, "copy.2", "", operands=[4]) \
        + _instr(6, "exp.3", "jit(f)/local_step/attention/exp", operands=[5]) \
        + _instr(7, "add.4", "jit(f)/local_step/add", operands=[3])
    proto = _f(1, _f(1, "jit_f") + _f(3, fused) + _f(3, entry))
    assert model_scopes.hlo_scopes(proto) == {
        "dot.1": "mlp", "param_0": "", "param.1": "", "fusion.7": "mlp", "copy.2": "mlp",
        "exp.3": "attention", "add.4": ""}


def test_hlo_scopes_agree_with_the_vit_program_compiled_here():
    import jax
    import jax.numpy as jnp

    from repro.models.api import cross_entropy
    from repro.models.vit import vit_apply, vit_init

    params = vit_init(jax.random.key(0), width=8)
    x, y = jnp.ones((2, 32, 32, 3)), jnp.zeros((2,), jnp.int32)
    grad = jax.grad(lambda p: cross_entropy(vit_apply(p, x), y))
    compiled = jax.jit(grad).lower(params).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    scopes = model_scopes.hlo_scopes(_f(1, module.as_serialized_hlo_module_proto()))
    assert set(model_scopes.MODEL_SCOPES) <= set(scopes.values())
    named = dict(re.findall(r'%?([\w.\-]+) = [^\n]*op_name="([^"]*)"', compiled.as_text()))
    checked = 0
    for instr, op_name in named.items():
        if model_scopes.scope_of(op_name):
            assert scopes[instr] == model_scopes.scope_of(op_name), instr
            checked += 1
    assert checked > 20


# -- hand-made traces --------------------------------------------------------------


def _handmade():
    """One chunk on one device: local step 0-6 ms (attention 0-2, mlp 2-5,
    an unscoped LayerNorm 5-6), share_mix 6-8, whose kernel the operand
    rule hands to mlp; the mlp instruction's name also runs in the staging
    program, where it is no model scope."""
    c = layers.CHUNK
    ops = [LayerOp(0, "fusion.1", 0, 2 * MS, False, c, "local_step"),
           LayerOp(0, "fusion.2", 2 * MS, 3 * MS, False, c, "local_step"),
           LayerOp(0, "fusion.3", 5 * MS, 1 * MS, False, c, "local_step"),
           LayerOp(0, "gossip_mix_nodes.4", 6 * MS, 2 * MS, False, c, "share_mix"),
           LayerOp(0, "fusion.2", 8 * MS, 1 * MS, False, layers.STAGE, "")]
    run = Layers(ops, [("bench.run_span", 0.0, 9 * MS), ("bench.block", 9 * MS, 10 * MS)],
                 [(0, c, 0.0, 8 * MS), (0, layers.STAGE, 8 * MS, 9 * MS)], [])
    return ScopedRun.of_layers(run, {c: {"fusion.1": "attention", "fusion.2": "mlp",
                                         "gossip_mix_nodes.4": "mlp"}})


def test_handmade_readers():
    run, ctx = _handmade(), {"rounds": 4}
    assert {m: _read(m, run, ctx) for m in READERS} == pytest.approx(
        {"attention.ms_per_round": 2 / 4, "mlp.ms_per_round": 3 / 4})
    assert _read("local_step.ms_per_round", run, ctx) == pytest.approx(6 / 4)
    assert model_scopes.breakdown(run, 4)[:3] == [
        ["local_step", "mlp", pytest.approx(3 / 4)], ["local_step", "attention", 0.5],
        ["share_mix", "mlp", 0.5]]


def test_json_round_trip_keeps_the_scopes_of_the_ops_it_holds():
    run = _handmade()
    run.model_scopes[layers.CHUNK]["fusion.99"] = "head"    # no op of this run
    back = ScopedRun.from_json(json.loads(json.dumps(run.to_json())))
    assert back.ops == run.ops and back.programs == run.programs
    assert back.model_scopes == {layers.CHUNK: {"fusion.1": "attention", "fusion.2": "mlp",
                                                "gossip_mix_nodes.4": "mlp"}}


def test_readers_are_silent_without_the_model_scopes(tmp_path, monkeypatch):
    monkeypatch.setattr(layers, "TRACE_DIR", tmp_path)
    bare = _handmade()
    bare.model_scopes = {}
    assert {m: _read(m, bare, {"rounds": 4}) for m in READERS} == {m: None for m in READERS}
    # the CNN's chunk, traced on the chip: its profile is not kept, and a
    # plain Layers has no model scopes of its own
    cnn = layers.load_json_gz(DATA / "v5e_dynamic_full_two_chunks_layers.json.gz")
    assert model_scopes.of(cnn) is None
    assert {m: _read(m, cnn, {"rounds": 16}) for m in READERS} == {m: None for m in READERS}


# -- one chunk traced on the chip ----------------------------------------------------


@pytest.fixture(scope="module")
def chip_vit():
    """Chunk 1 of a 20 s traced run of the cell (seed 2147600102) on one TPU
    v5e: 8 rounds, the ops of the chunk program with their model scopes."""
    return model_scopes.load_json_gz(DATA / "v5e_vit_dynamic_full_one_chunk_scopes.json.gz")


def test_chip_readers_split_the_local_step(chip_vit):
    ctx = {"rounds": 8 * layers.chunks(chip_vit)}
    got = {m: _read(m, chip_vit, ctx) for m in READERS + ("local_step.ms_per_round",)}
    assert got == pytest.approx({"attention.ms_per_round": 117.36462462501467,
                                 "mlp.ms_per_round": 78.98914950000248,
                                 "local_step.ms_per_round": 205.137516}, rel=1e-12)
    # LayerNorms, residual adds and the loss outside both scopes: 8.4 ms
    rest = got["local_step.ms_per_round"] - got["attention.ms_per_round"] \
        - got["mlp.ms_per_round"]
    assert 0 < rest < 0.05 * got["local_step.ms_per_round"]
    # the mixing and the flat layout, which the operand rule hands to mlp, do not count
    handed = {(a, b): v for a, b, v in model_scopes.breakdown(chip_vit, ctx["rounds"])}
    assert handed[("share_mix", "mlp")] > 50


def test_chip_mfu_counts_the_vit_operations(chip_vit):
    cell = harness.load_cell(CELL)
    lo, hi = traces.window(chip_vit)
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    ctx = {"config": cell.config, "rounds": 8, "window_s": (hi - lo) / 1e9, "n_nodes": 16,
           "chips": 1, "peaks": peaks, "flops": harness.load_module("flops", "vit")}
    # 8.52 GFLOP a sample x 8 rounds x 16 nodes x 32 samples over a 2.19 s chunk
    expected = 100 * 8_519_496_192 * 8 * 16 * 32 / ((hi - lo) / 1e9) / 197e12
    assert _read("mfu", chip_vit, ctx) == pytest.approx(expected, rel=1e-12)
    assert 8.0 < expected < 8.2
