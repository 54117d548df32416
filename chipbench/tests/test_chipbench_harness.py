"""The harness on the CPU: it refuses to measure without a chip, finds its
cells, configurations and metrics by name, and runs every cell end to end at
smoke size with ``correct`` true."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import chipbench_smoke as smoke
from chipbench import common, run

REPO = smoke.REPO
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def _command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *SPEC["command"][1:], *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _checkout(dst: pathlib.Path) -> pathlib.Path:
    """A directory holding only BENCHMARK.json and the files under paths."""
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for p in SPEC["paths"]:
        shutil.copytree(REPO / p, dst / p, ignore=shutil.ignore_patterns(
            "out", ".jax_cache", "__pycache__"))
    return dst


def test_command_without_a_chip_exits_nonzero_and_measures_nothing(tmp_path):
    done = _command(_checkout(tmp_path), "--workload", CELLS[0], "--seed",
                    str(smoke.SEED), "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout
    assert "nothing was measured" in done.stderr or "Error" in done.stderr


def test_command_in_the_repo_without_a_chip_exits_nonzero():
    done = _command(REPO, "--workload", CELLS[-1], "--seed", "1", "--seconds", "1",
                    "--trace", "1")
    assert done.returncode != 0 and "{" not in done.stdout


def test_every_name_has_its_file_and_every_file_its_name():
    assert {c["file"] for c in SPEC["configs"]} == {
        str(p.relative_to(REPO)) for p in (REPO / "chipbench" / "configs").glob("*.json")}
    assert {w["traffic"] for w in SPEC["workloads"]} == {
        p.stem for p in (REPO / "chipbench" / "traffic").glob("*.json")}
    assert {m["name"] for m in SPEC["per_layer"]} == {
        p.stem for p in (REPO / "chipbench" / "metrics").glob("*.py")}
    kinds = {p.stem for p in (REPO / "chipbench" / "kinds").glob("*.py")} - {"__init__"}
    for w in SPEC["workloads"]:
        _, config, traffic = common.cell(SPEC, REPO, w["name"])
        assert traffic["kind"] in kinds
        assert config["name"] == w["config"]


def test_each_cell_reports_setup_another_end_to_end_metric_and_a_layer():
    for w in CELLS:
        e2e = common.end_to_end_names(SPEC, w)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert common.per_layer_entries(SPEC, w)


def test_unknown_device_kind_is_an_error():
    assert common.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        common.peaks("TPU v99")


def test_a_new_cell_and_metric_come_from_new_files_alone(tmp_path):
    root = smoke.build(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "chipbench" / "configs" / "newmodel.json").write_text(
        (root / "chipbench" / "configs" / "granite8b_l16.json").read_text()
        .replace('"granite8b_l16"', '"newmodel"'))
    (root / "chipbench" / "traffic" / "serve.newmix.json").write_text(json.dumps(
        dict(json.loads((root / "chipbench" / "traffic" / "serve.codecomplete.json")
                        .read_text()), waves=[{"prompt": 5, "output": 3}])))
    (root / "chipbench" / "metrics" / "waves_run.py").write_text(
        "def read(trace, inputs, peaks, config):\n    return len(inputs['waves'])\n")
    spec["configs"].append({"name": "newmodel", "source": "x",
                            "file": "chipbench/configs/newmodel.json", "reduced": []})
    spec["workloads"].append({"name": "newmodel.serve.newmix", "config": "newmodel",
                              "traffic": "serve.newmix", "chips": 1, "why": "x"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "granite8b.serve.codecomplete" in m["workloads"]:
            m["workloads"].append("newmodel.serve.newmix")
    spec["per_layer"].append({"name": "waves_run", "unit": "waves", "better": "higher",
                              "source": "program_counter", "layer": "host loop",
                              "moves": "request_latency_p95_ms",
                              "workloads": ["newmodel.serve.newmix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = smoke.run_cell(root, "newmodel.serve.newmix")
    assert out["correct"] and set(out["metrics"]) == set(
        common.end_to_end_names(spec, "granite8b.serve.codecomplete"))
    names = [m["name"] for m in common.per_layer_entries(spec, "newmodel.serve.newmix")]
    assert "waves_run" in names
    assert run.load_metric(root, "waves_run").read(None, {"waves": [1, 2]}, {}, {}) == 2
    # no file the benchmark had was edited, but BENCHMARK.json's new entries
    changed = [p for p, b in before.items() if p.read_bytes() != b]
    assert changed == [root / "BENCHMARK.json"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_is_correct(tmp_path, cell):
    chips = next(w["chips"] for w in SPEC["workloads"] if w["name"] == cell)
    root = smoke.build(tmp_path)
    out = (smoke.run_cell(root, cell) if chips == 1
           else smoke.run_cell_on_devices(root, cell, chips))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == set(common.end_to_end_names(SPEC, cell))
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert out["device"]["count"] == chips
