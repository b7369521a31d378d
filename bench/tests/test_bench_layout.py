"""The benchmark finds cells, configurations, mixes and metrics by name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from _bench_path import BENCH, ROOT

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_declared_cell_has_its_files():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        spec = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
        assert (spec["config"], spec["traffic"]) == (w["config"], w["traffic"])
        assert cell.limits, f"{w['name']} has no limits of correct"
    assert sorted(w["name"] for w in SPEC["workloads"]) == harness.cell_names()


def test_every_declared_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert callable(mod.read)
        for cell in m.get("workloads", []):
            assert cell in harness.cell_names()


def test_configs_name_their_reference_and_flops():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg) and set(c["reduced"]) == set(cfg["reduced"])
        harness.load_module("configs", cfg["model"]["reference"])
        harness.load_module("flops", cfg["model"]["flops"])


def test_a_cell_is_added_by_adding_files(tmp_path):
    """A new cell needs its files and nothing else: no code names it."""
    for d in ("configs", "traffic"):
        shutil.copytree(BENCH / d, tmp_path / d)
    (tmp_path / "workloads").mkdir()
    assert harness.cell_names(tmp_path) == []
    (tmp_path / "traffic" / "static-full.json").write_text(json.dumps(
        {"dl": {"topology": "regular", "sharing": "full"}}))
    (tmp_path / "workloads" / "gnlenet-cifar10-n256.static-full.json").write_text(json.dumps(
        {"config": "gnlenet-cifar10-n256", "traffic": "static-full", "chips": 1,
         "limits": {"change_gap": 0.01}}))
    assert harness.cell_names(tmp_path) == ["gnlenet-cifar10-n256.static-full"]
    cell = harness.load_cell("gnlenet-cifar10-n256.static-full", tmp_path)
    assert cell.traffic["dl"]["topology"] == "regular"
    assert cell.config["n_nodes"] == 256 and cell.limits == {"change_gap": 0.01}


def test_peaks_table_holds_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in peaks["source"]


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    cell = SPEC["workloads"][0]["name"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload", cell,
                        "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
