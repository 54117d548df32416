#!/usr/bin/env python3
"""Readings from which a cell's limits are set, on the chip, in one process.

  python chipbench/calibrate.py --workload <cell> --seeds <n> [--first <seed>]
      [--controls 3] [--seconds <s>]

For each of ``--seeds`` seeds the cell runs as a run does, with a short
window (``--seconds``, long enough to serve each wave shape once), and its
compared numbers are read against the reference: the lower readings.  For
the first ``--controls`` seeds the control is read too, the reference one
precision below the configuration's (bfloat16 for float32 training, float8
weights for bfloat16 serving), and the faults planted in the reference: for
training half the batch and, on several chips, the exchange left out; for
serving the last served token of each sampled request altered.  Each
reading is one JSON line on standard output and in
``chipbench/out/calibrate/<cell>.jsonl``; the benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

import run as harness  # chipbench/run.py, which puts the repo on the path

from chipbench import common, compare


def readings(ctx) -> list:
    """The control's and the faults' numbers for the run ``ctx`` checked,
    from the reference of the cell's model family."""
    import jax.numpy as jnp

    out = []
    if ctx.traffic["kind"] == "train":
        chips = len(ctx.devices)
        runs = {"control_bf16": dict(dtype=jnp.bfloat16, precision="default"),
                "fault_half_batch": dict(fault="half_batch")}
        if chips > 1:
            runs["fault_no_exchange"] = dict(fault="no_exchange", chips=chips)
        for name, kw in runs.items():
            got = ctx.family.train_reference(ctx.config, ctx.traffic, ctx.seed,
                                             device=ctx.devices[0], **kw)
            out.append((name, compare.train_numbers(got, ctx.reference)))
    else:
        dtype = jnp.dtype(ctx.config["torch_dtype"])
        gaps = ctx.family.serve_gaps(ctx.config, ctx.seed, dtype, ctx.sampled, control=True,
                                     device=ctx.devices[0])
        out.append(("control_fp8", {"served_gap": compare.served_gap(gaps)}))
        vocab = ctx.config["vocab_size"]
        altered = [(p, np.concatenate([s[:-1], (s[-1:] + 1) % vocab])) for p, s in ctx.sampled]
        gaps = ctx.family.serve_gaps(ctx.config, ctx.seed, dtype, altered,
                                     device=ctx.devices[0])
        out.append(("fault_token_altered", {"served_gap": compare.served_gap(gaps)}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=4_100_000_000)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    out_dir = common.OUT / "calibrate"
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / f"{args.workload}.jsonl", "a") as log:
        def emit(rec):
            line = json.dumps(rec)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()

        for i in range(args.seeds):
            seed = args.first + i
            ctx = harness.execute(harness.HERE.parent, args.workload, seed, args.seconds,
                                  False, started=common.now())
            emit({"seed": seed, "who": "program",
                  "numbers": {n: v for n, v, _ in ctx.checks}, "setup_s": ctx.setup_s,
                  "e2e": ctx.e2e, "memory_peak_bytes": ctx.memory_peak_bytes})
            if i < args.controls:
                for who, numbers in readings(ctx):
                    emit({"seed": seed, "who": who, "numbers": numbers})
            del ctx
            gc.unfreeze()  # the run froze its set-up; let the next seed free it
            gc.collect()
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
