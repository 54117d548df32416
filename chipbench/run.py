#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` on the chips of this machine.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``chipbench/configs/<name>.json``), whose
``program.family`` picks the model family's reference and counts
(``chipbench/families/<family>.py``), and a traffic file
(``chipbench/traffic/<name>.json``) whose ``kind`` picks the runner
(``chipbench/kinds/<kind>.py``).  Set-up makes the weights on the
device from ``--seed`` and warms every shape the window uses; the window
then runs for ``--seconds``; the reference then checks what the window's
program produced.  With ``--trace 0`` the result carries the end-to-end
metrics, with ``--trace 1`` the per-layer metrics
(``chipbench/metrics/<name>.py``), read from a profiler trace of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks``: each compared number beside its limit).  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from chipbench import common  # noqa: E402
from chipbench import trace_reduce  # noqa: E402


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Ctx:
    """One run: what the runner reads, and what it reports."""

    def __init__(self, *, root, spec, workload, config, family, traffic, seed, seconds,
                 trace, devices, started):
        self.root, self.spec, self.workload = root, spec, workload
        self.config, self.family, self.traffic = config, family, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.started = devices, started
        self.setup_s = None
        self.window_s = None
        self.window_lowerings = 0
        self.attempted = self.failed = 0
        self.e2e: dict = {}
        self.layer_inputs: dict = {}
        self.memory_peak_bytes = None
        self.checks: list = []
        self.trace_dir = common.OUT / "trace" / workload["name"]
        self.reference = None  # what the check compared against, for calibrate.py
        self.sampled = None
        self._t0 = None

    @staticmethod
    def clock() -> float:
        return common.now()

    def setup_done(self) -> None:
        # What set-up made is kept for good: the collector's full passes in the
        # window then walk only what the window makes.
        gc.collect()
        gc.freeze()
        self.setup_s = common.now() - self.started

    def elapsed(self) -> float:
        return common.now() - self._t0

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        lowerings = common.LowerCounter()
        try:
            with lowerings, self.span(trace_reduce.WINDOW_SPAN):
                self._t0 = common.now()
                yield
                self.window_s = common.now() - self._t0
        finally:
            self.window_lowerings = lowerings.count
            if self.trace:
                jax.profiler.stop_trace()

    def report(self, *, attempted: int, failed: int, e2e: dict, layer_inputs: dict) -> None:
        self.attempted, self.failed = attempted, failed
        self.e2e, self.layer_inputs = e2e, layer_inputs

    def read_memory(self) -> None:
        self.memory_peak_bytes = common.peak_bytes(self.devices)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))


def load_metric(root: pathlib.Path, name: str):
    return common.load_module(root / "chipbench" / "metrics" / f"{name}.py",
                              f"chipbench_metric_{name}")


def per_layer(ctx: Ctx, reduced) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    kind = ctx.devices[0].device_kind
    out = {}
    for entry in common.per_layer_entries(ctx.spec, ctx.workload["name"]):
        value = load_metric(ctx.root, entry["name"]).read(
            reduced, ctx.layer_inputs, common.peaks(kind), ctx.config)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def devices_for(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is kept, however quick its compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / "chipbench" / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def execute(root: pathlib.Path, workload: str, seed: int, seconds: float, trace: bool,
            *, require_chip: bool = True, started: float | None = None) -> Ctx:
    """Set-up, window and check of one cell -> the run's ``Ctx``."""
    spec = common.load_spec(root)
    entry, config, traffic = common.cell(spec, root, workload)
    family = common.family(root, config)
    enable_compile_cache(root)
    devices = devices_for(entry["chips"], require_chip)
    ctx = Ctx(root=root, spec=spec, workload=entry, config=config, family=family,
              traffic=traffic, seed=seed, seconds=seconds, trace=trace, devices=devices,
              started=STARTED if started is None else started)
    importlib.import_module(f"chipbench.kinds.{traffic['kind']}").run(ctx)
    return ctx


def result_of(ctx: Ctx) -> dict:
    """The run's result line."""
    d = ctx.devices[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(ctx.devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": all(v <= lim for _, v, lim in ctx.checks) and ctx.failed == 0,
              "attempted": ctx.attempted, "failed": ctx.failed}
    if ctx.trace:
        reduced = trace_reduce.reduce_file(trace_reduce.find_xplane(str(ctx.trace_dir)))
        result["metrics"] = per_layer(ctx, reduced)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_gaps}
    else:
        names = common.end_to_end_names(ctx.spec, ctx.workload["name"])
        metrics = {**ctx.e2e, "setup_s": ctx.setup_s}
        units = {m["name"]: m["unit"] for m in ctx.spec["end_to_end"]}
        result["metrics"] = {n: {"value": metrics[n], "unit": units[n]} for n in names}
        result["device"] = device
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in ctx.checks}
    return result


def run(root: pathlib.Path, workload: str, seed: int, seconds: float, trace: bool,
        *, require_chip: bool = True, started: float | None = None) -> dict:
    return result_of(execute(root, workload, seed, seconds, trace,
                             require_chip=require_chip, started=started))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(HERE.parent, args.workload, args.seed, args.seconds, bool(args.trace))
    except (NoChip, common.SpecError) as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
