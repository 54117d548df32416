#!/usr/bin/env python3
"""Records the serving fixture of the trace tests on a chip.

  python chipbench/tests/record_fixture.py <out dir>

A tiny dense decoder (``minicpm-2b-smoke``: 2 layers, width 64) serves one
``generate`` call of a batch of 2 prompts of 4 tokens, 3 tokens out, inside
``cb.window`` and ``cb.generate``, with one garbage collection after it;
profiler options as the benchmark's (host tracer level 1, no Python
tracer).  Writes ``v5e_serve.xplane.pb`` and ``v5e_serve.json`` (what the
tests expect of it) into ``<out dir>``.  Exits non-zero without a TPU.
"""

from __future__ import annotations

import gc
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

BATCH, PROMPT, OUTPUT = 2, 4, 3
NAME = "v5e_serve"


def main(out: pathlib.Path) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import trace_program, trace_reduce
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models import get_model

    if jax.devices()[0].platform != "tpu":
        print("no TPU; nothing was recorded", file=sys.stderr)
        return 2
    cfg = get_config("minicpm-2b-smoke")
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    step = serve.make_step(cfg)
    prompts = np.arange(BATCH * PROMPT, dtype=np.int32).reshape(BATCH, PROMPT) % cfg.vocab
    serve.generate(cfg, params, step, prompts, OUTPUT)  # compiles every program it runs
    gc.collect()

    tmp = pathlib.Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("cb.generate"):
            serve.generate(cfg, params, step, prompts, OUTPUT)
        gc.collect()
    jax.profiler.stop_trace()

    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{NAME}.xplane.pb"
    shutil.copy(trace_reduce.find_xplane(str(tmp)), path)
    r = trace_reduce.reduce_file(str(path))
    p = trace_program.read_file(str(path))
    steps = [x for x in p.executions if x.program.endswith("serve_step")]
    expect = {
        "what": (f"one generate call of minicpm-2b-smoke (2 layers, width 64, bf16), batch "
                 f"{BATCH}, prompt {PROMPT}, output {OUTPUT}, inside cb.window and "
                 "cb.generate, then one gc.collect() in the window; recorded on one "
                 f"{jax.devices()[0].device_kind} with host_tracer_level 1 and the "
                 "python tracer off"),
        "chips": r.chips, "window_s": r.window_s, "busy_s": r.busy_s,
        "program": "serve_step", "executions": r.module("serve_step")[0],
        "collective_s": r.collective_s,
        "first_gap_label": [g[0] for g in r.idle_gaps][:1],
        "serve_steps": PROMPT + OUTPUT,
        "clock_offset_ms": p.clock_offset_ms,
        "launch_paths": sorted({">".join(x.path or ()) for x in steps}),
        "idle_gaps": p.idle_gaps,
        "spans": sorted({s.name for s in p.spans}),
        "scope_ms": {k: {s: 1e3 * v for s, v in d.items()} for k, d in p.scope_s.items()},
        "bytes": path.stat().st_size,
    }
    (out / f"{NAME}.json").write_text(json.dumps(expect, indent=1) + "\n")
    print(json.dumps(expect))
    return 0


if __name__ == "__main__":
    sys.exit(main(pathlib.Path(sys.argv[1])))
