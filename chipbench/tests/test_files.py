"""Every file ``BENCHMARK.json`` names is found by its name and has the
shape the harness reads; a new configuration, traffic mix or metric is
found by adding its file alone."""
from __future__ import annotations

import json
import os
import re
import shutil

import pytest

from chipbench import compare, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {w["name"] for w in bench["workloads"]}
        if m["name"].endswith("_roofline") or "mfu" in m["name"].split("."):
            assert m["unit"] == "%"


@pytest.mark.parametrize("workload", ["shallow-infer"])
def test_cell_files_load_by_name(bench, workload):
    f = run.load_cell(workload, bench)
    cfg = f["config"]
    entry = {c["name"]: c for c in bench["configs"]}[f["cell"]["config"]]
    assert entry["file"] == f"chipbench/configs/{cfg['name']}.json"
    assert entry["reduced"] == cfg["reduced"]
    assert set(f["limits"]) <= set(compare.NUMBERS) and f["limits"]
    assert f["flops"].train_flops(cfg) > f["flops"].forward_flops(cfg) > 0
    env = f["env"].make(**cfg["env_args"])
    assert list(env.image_hw) == cfg["frame"]
    assert f["traffic"]["spmd_devices"] in (0, f["cell"]["chips"])
    assert f["per_layer"], "every cell reports a per-layer metric"
    for m in f["per_layer"]:
        assert callable(run.load_module("metrics", m["name"] + ".py").compute)


def test_new_files_are_found_by_name(bench, tmp_path, monkeypatch):
    """A later cell adds files and entries and edits none: a copy of the
    harness's data with one new traffic mix, configuration and metric
    loads them by the names a new BENCHMARK.json entry gives."""
    for sub in ("configs", "traffic", "limits", "flops", "envs", "metrics"):
        shutil.copytree(os.path.join(run.HERE, sub), tmp_path / sub)
    shutil.copy(os.path.join(run.HERE, "peaks.json"), tmp_path)
    shutil.copy(tmp_path / "traffic" / "inference-8x32.json",
                tmp_path / "traffic" / "unroll-2x32.json")
    shutil.copy(tmp_path / "configs" / "impala-shallow-72x96.json",
                tmp_path / "configs" / "impala-shallow-b.json")
    shutil.copy(tmp_path / "flops" / "impala-shallow-72x96.py",
                tmp_path / "flops" / "impala-shallow-b.py")
    shutil.copy(tmp_path / "limits" / "shallow-infer.json",
                tmp_path / "limits" / "shallow-unroll.json")
    (tmp_path / "metrics" / "queue.put_stalls.py").write_text(
        "def compute(ctx):\n    return 7.0\n")
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [
        {"name": "shallow-unroll", "config": "impala-shallow-b",
         "traffic": "unroll-2x32", "chips": 1, "why": "test"}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "queue.put_stalls", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "transport and queue",
         "moves": "frames_per_s", "workloads": ["shallow-unroll"]}]
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    f = run.load_cell("shallow-unroll", new)
    assert f["config"]["torso"] == "shallow"
    assert f["traffic"]["num_actors"] == 8
    assert [m["name"] for m in f["per_layer"]] == ["queue.put_stalls"]
    mod = run.load_module("metrics", "queue.put_stalls.py")
    assert mod.compute(None) == 7.0
    with pytest.raises(run.Failed, match="no workload"):
        run.load_cell("absent", new)


def test_peaks_table_names_its_source():
    peaks = run.load_json("peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


def test_a_metric_that_reads_nothing_fails_the_run(tmp_path, monkeypatch):
    """A cell that lists a metric gets it in its line, or no line: a
    reader whose pattern matches nothing fails the run."""
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "step.found.py").write_text(
        "def compute(ctx):\n    return 3.0\n")
    (tmp_path / "metrics" / "step.missing.py").write_text(
        "def compute(ctx):\n    return None\n")
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    found = {"name": "step.found", "unit": "ms"}
    assert run.read_metrics([found], None) == {
        "step.found": {"value": 3.0, "unit": "ms"}}
    with pytest.raises(run.Failed, match="step.missing found nothing"):
        run.read_metrics([found, {"name": "step.missing", "unit": "ms"}],
                         None)
