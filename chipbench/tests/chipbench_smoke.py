"""A copy of the benchmark at smoke size, for tests on the CPU.

Every configuration is cut by its model family's ``smoke_config`` (the dense
decoder keeps its layout, heads per kv head, tying and theta, at tiny
widths); every traffic file keeps its kind, sync and limits with tiny
batches and lengths.  The copy is a root of its own: ``BENCHMARK.json``,
``chipbench/configs``, ``chipbench/traffic``, ``chipbench/metrics`` and
``chipbench/families``.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 2**31 + 11  # past 32 signed bits, as the driver's seeds are


def small_config(config: dict, root: pathlib.Path = REPO) -> dict:
    """``config`` at smoke size, by the rule of its family under ``root``."""
    from chipbench import common

    return common.family(root, config).smoke_config(config)


def small_traffic(traffic: dict) -> dict:
    t = dict(traffic)
    if t["kind"] == "train":
        t["seq"] = 64
    else:
        t["batch"] = 4
        t["waves"] = [{"prompt": 8, "output": 6}, {"prompt": 12, "output": 5}]
        t["check_requests"] = 3
    return t


def build(root: pathlib.Path, src: pathlib.Path = REPO) -> pathlib.Path:
    """The smoke copy under ``root`` of the benchmark under ``src``."""
    spec = json.loads((src / "BENCHMARK.json").read_text())
    (root / "chipbench" / "configs").mkdir(parents=True)
    (root / "chipbench" / "traffic").mkdir()
    for d in ("metrics", "families"):
        shutil.copytree(src / "chipbench" / d, root / "chipbench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for c in spec["configs"]:
        config = json.loads((src / c["file"]).read_text())
        (root / c["file"]).write_text(json.dumps(small_config(config, src)))
    for w in spec["workloads"]:
        t = json.loads((src / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
        (root / "chipbench" / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(small_traffic(t)))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_cell(root: pathlib.Path, workload: str, seconds: float = 0.5, seed: int = SEED):
    from chipbench import run

    return run.run(root, workload, seed, seconds, False, require_chip=False,
                   started=run.common.now())


ON_DEVICES = textwrap.dedent("""
    import json, pathlib, sys
    sys.path[:0] = [{tests!r}]
    import chipbench_smoke as smoke
    print(json.dumps(smoke.run_cell(pathlib.Path({root!r}), {cell!r})))
""")


def run_cell_on_devices(root: pathlib.Path, workload: str, chips: int, prelude: str = ""):
    """``run_cell`` in a child process that sees ``chips`` CPU devices;
    ``prelude`` runs first there (to plant a fault)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    code = prelude + ON_DEVICES.format(tests=str(pathlib.Path(__file__).parent),
                                       root=str(root), cell=workload)
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=600)
    if done.returncode:
        raise RuntimeError(done.stderr[-3000:])
    return json.loads(done.stdout.strip().splitlines()[-1])
