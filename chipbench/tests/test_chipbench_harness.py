"""The harness on the CPU: it refuses to measure without a chip, finds its
cells, configurations and metrics by name, and runs every cell end to end at
smoke size with ``correct`` true."""

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import chipbench_smoke as smoke
from chipbench import common, flops, run

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


NEW_FAMILY = '''"""A model family of its own: the dense decoder's reference and counts,
reached only through this file, which records each call, and a smoke size
of its own.  The program runs it as its dense decoder."""

from chipbench import common, flops
from chipbench.reference import serve_ref, train_ref

CALLS = []


def _counted(name, f):
    def call(*args, **kw):
        CALLS.append(name)
        return f(*args, **kw)
    return call


def _arch_config(config, **extra):
    program = dict(config["program"], family="dense")
    return common.arch_config(dict(config, program=program), **extra)


arch_config = _counted("arch_config", _arch_config)
train_reference = _counted("train_reference", train_ref.run)
serve_gaps = _counted("serve_gaps", serve_ref.gaps)
train_step_flops = _counted("train_step_flops", flops.train_step_flops)
wave_flops = _counted("wave_flops", flops.wave_flops)
wave_bytes = _counted("wave_bytes", flops.wave_bytes)


def smoke_config(config):
    return dict(config, hidden_size=48, intermediate_size=96, head_dim=12, vocab_size=384,
                num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2)
'''


def _calibrate(monkeypatch):
    monkeypatch.setitem(sys.modules, "run", run)  # calibrate.py's ``import run``
    spec = importlib.util.spec_from_file_location(
        "chipbench_calibrate", REPO / "chipbench" / "calibrate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_new_training_family_comes_from_new_files_alone(tmp_path, monkeypatch):
    """A training cell of a model family the benchmark does not have: the
    family's module, a configuration that names it, a traffic file of kind
    ``train`` and a metric reader are new files in a copy of the benchmark.
    The smoke copy takes the family's own smoke size, the run its arch
    config, counts and reference, and calibration its control and fault."""
    src = tmp_path / "src"
    src.mkdir()
    _checkout(src)
    before = {p: p.read_bytes() for p in src.rglob("*") if p.is_file()}
    chip = src / "chipbench"
    (chip / "families" / "newfamily.py").write_text(NEW_FAMILY)
    config = json.loads((chip / "configs" / "granite8b_l16.json").read_text())
    config.update(name="newmodel", program={"family": "newfamily"})
    (chip / "configs" / "newmodel.json").write_text(json.dumps(config))
    job = json.loads((chip / "traffic" / "train.s2048.json").read_text())
    (chip / "traffic" / "train.newjob.json").write_text(json.dumps(dict(job, why="x")))
    (chip / "metrics" / "flops_per_step.newjob.py").write_text(
        "def read(trace, inputs, peaks, config):\n    return inputs['flops_per_step']\n")
    spec = json.loads((src / "BENCHMARK.json").read_text())
    cell = "newmodel.train.newjob"
    spec["configs"].append({"name": "newmodel", "source": "x",
                            "file": "chipbench/configs/newmodel.json", "reduced": []})
    spec["workloads"].append({"name": cell, "config": "newmodel", "traffic": "train.newjob",
                              "chips": 1, "why": "x"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "minicpm2b.train.s2048" in m["workloads"]:
            m["workloads"].append(cell)
    spec["per_layer"].append({"name": "flops_per_step.newjob", "unit": "FLOP",
                              "better": "higher", "source": "program_counter",
                              "layer": "step builders", "moves": "train_tokens_per_s",
                              "workloads": [cell]})
    (src / "BENCHMARK.json").write_text(json.dumps(spec))

    root = smoke.build(tmp_path / "smoke", src)
    small = json.loads((root / "chipbench" / "configs" / "newmodel.json").read_text())
    assert (small["hidden_size"], small["num_hidden_layers"], small["program"]) == (
        48, 1, {"family": "newfamily"})
    ctx = run.execute(root, cell, smoke.SEED, 0.5, False, require_chip=False,
                      started=common.now())
    out = run.result_of(ctx)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == set(common.end_to_end_names(spec, "minicpm2b.train.s2048"))
    assert set(ctx.family.CALLS) == {"arch_config", "train_step_flops", "train_reference"}
    small_job = smoke.small_traffic(job)
    want = flops.train_step_flops(small, small_job["batch"], small_job["seq"])
    assert run.load_metric(root, "flops_per_step.newjob").read(
        None, ctx.layer_inputs, {}, small) == want
    names = [n for n, _ in _calibrate(monkeypatch).readings(ctx)]
    assert names == ["control_bf16", "fault_half_batch"]
    assert ctx.family.CALLS.count("train_reference") == 3
    # the benchmark's tests of its families pass beside the new one
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               PYTHONDONTWRITEBYTECODE="1")
    done = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
                           str(chip / "tests" / "test_chipbench_families.py")],
                          cwd=src, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stdout[-4000:]
    # no file the benchmark had was edited, but BENCHMARK.json's new entries
    changed = [p for p, b in before.items() if p.read_bytes() != b]
    assert changed == [src / "BENCHMARK.json"]


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
