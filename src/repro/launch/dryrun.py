"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. constructs abstract params / optimizer state / batch / cache
     (ShapeDtypeStruct only — nothing is allocated),
  3. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
  4. records memory_analysis / cost_analysis / per-type collective bytes
     parsed from the optimized HLO into a JSON report.

The meshes are 512 fake CPU devices.  ``main`` sets that up before JAX
creates its backends, and keeps the run off any accelerator; importing this
module touches no JAX state.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun.json
"""

import argparse
import json
import os
import re
import time
import traceback

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

COLLECTIVE_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# replica_groups comes in two syntaxes:
#   explicit: replica_groups={{0,16,32,...},{1,17,...},...}
#   iota:     replica_groups=[n_groups,group_size]<=[...]
GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
GROUPS_IOTA_RE = re.compile(r"replica_groups=\[\d+,(\d+)\]<=")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in SHAPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-type counts / result bytes / modeled wire bytes per device.

    Result bytes approximate operand bytes for all-reduce / permute / a2a;
    for all-gather the operand is result/group, for reduce-scatter it is
    result*group.  Wire bytes per device use ring-algorithm models:
      all-reduce 2x, all-gather 1x(result), reduce-scatter 1x(operand),
      permute/a2a 1x.
    """
    stats: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        nbytes = _shape_bytes(type_str)
        gm = GROUPS_RE.search(line)
        if gm:
            group = len(gm.group(1).split(","))
        else:
            gi = GROUPS_IOTA_RE.search(line)
            group = int(gi.group(1)) if gi else 1
        rec = stats.setdefault(op, {"count": 0, "result_bytes": 0, "wire_bytes": 0})
        rec["count"] += 1
        rec["result_bytes"] += nbytes
        if op == "all-reduce":
            wire = 2 * nbytes * max(0, group - 1) / max(1, group)
        elif op == "all-gather":
            wire = nbytes * max(0, group - 1) / max(1, group)
        elif op == "reduce-scatter":
            wire = nbytes * max(0, group - 1)
        else:  # permute, all-to-all
            wire = nbytes
        rec["wire_bytes"] += int(wire)
    return stats


def build_cell(arch: str, shape_name: str, mesh, multi_pod: bool, options, smoke=False,
               cfg_override=None, layout: str = "2d", moe_mode: str = "tp",
               vocab_pad: int = 0):
    """Returns (jitted_fn, example_args) ready to lower."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import (SHAPES, abstract_cache, abstract_params,
                               get_config, input_specs)
    from repro.parallel import sharding as shard_lib
    from repro.train import optimizer as opt_lib
    from repro.train import steps as steps_lib

    cfg = cfg_override if cfg_override is not None else get_config(arch, smoke=smoke)
    if moe_mode != "tp" and cfg.family == "moe":
        cfg = dataclasses.replace(cfg, moe_mode=moe_mode)
    if vocab_pad:
        cfg = dataclasses.replace(cfg, vocab_pad_to=vocab_pad)
    shape = SHAPES[shape_name]
    policy = shard_lib.default_policy(cfg, multi_pod=multi_pod, layout=layout)
    params_abs = abstract_params(cfg)
    pspecs = shard_lib.param_specs(cfg, params_abs, policy)
    pspecs = shard_lib.sanitize_specs(params_abs, pspecs, mesh)
    pshard = shard_lib.to_shardings(mesh, pspecs)
    bspecs = shard_lib.batch_specs(cfg, policy, mesh, shape.global_batch)
    batch_abs = input_specs(cfg, shape)
    bshard = {k: NamedSharding(mesh, bspecs.get(k, P())) for k in batch_abs}
    act_specs = shard_lib.activation_specs(cfg, policy, mesh, shape.global_batch)
    act_specs["mesh"] = mesh

    if shape.kind == "train":
        ocfg = opt_lib.AdamWConfig(schedule=cfg.schedule)
        train_step = steps_lib.make_train_step(cfg, ocfg, options, policy, mesh,
                                               act_specs=act_specs)
        opt_abs = jax.eval_shape(opt_lib.init, params_abs)
        ospecs = opt_lib.AdamWState(step=P(), m=pspecs, v=pspecs,
                                    buffers=jax.tree.map(lambda _: P(), opt_abs.buffers))
        oshard = jax.tree.map(
            lambda s: NamedSharding(mesh, s), ospecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        fn = jax.jit(
            train_step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
        )
        return fn, (params_abs, opt_abs, batch_abs)

    if shape.kind == "prefill":
        prefill = steps_lib.make_prefill_step(cfg, options, act_specs=act_specs)
        fn = jax.jit(prefill, in_shardings=(pshard, bshard),
                     out_shardings=NamedSharding(mesh, P(policy.dp if shape.global_batch % _dp(mesh, policy) == 0 else None, None, None)))
        return fn, (params_abs, batch_abs)

    # decode
    serve = steps_lib.make_decode_step(cfg)
    cache_abs = abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cspecs = shard_lib.cache_specs(cfg, cache_abs, policy, mesh, shape.global_batch)
    cshard = shard_lib.to_shardings(mesh, cspecs)
    tok_dp = policy.dp if shape.global_batch % _dp(mesh, policy) == 0 else None
    tshard = NamedSharding(mesh, P(tok_dp, None))
    fn = jax.jit(serve, in_shardings=(pshard, cshard, tshard),
                 out_shardings=(tshard, cshard))
    tokens_abs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return fn, (params_abs, cache_abs, tokens_abs)


def _dp(mesh, policy):
    n = 1
    for ax in policy.data_axes:
        n *= mesh.shape[ax]
    return n


def _units(cfg):
    """(unit_layers, n_units) for layer-count extrapolation."""
    if cfg.family == "hybrid":
        period = max(1, cfg.attention_period)
        return period, cfg.n_layers // period
    return 1, cfg.n_layers


def calibrate_cost(arch, shape_name, mesh, multi_pod, options, smoke=False,
                   **variant):
    """FLOP/bytes/wire calibration: XLA costs a while-loop body once, so the
    scanned-layers numbers undercount.  Lower 1-unit and 2-unit variants with
    every scan unrolled and extrapolate linearly to the full depth."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import layers as L

    cfg = get_config(arch, smoke=smoke)
    unit, n_units = _units(cfg)
    L.set_scan_unroll(True)
    try:
        vals = {}
        for k in (1, 2):
            sub = dataclasses.replace(cfg, n_layers=unit * k)
            fn, args = build_cell(arch, shape_name, mesh, multi_pod, options,
                                  smoke=smoke, cfg_override=sub, **variant)
            compiled = fn.lower(*args).compile()
            ca = compiled.cost_analysis()
            stats = collective_stats(compiled.as_text())
            vals[k] = (
                ca.get("flops", 0.0),
                ca.get("bytes accessed", 0.0),
                sum(s["wire_bytes"] for s in stats.values()),
            )
    finally:
        L.set_scan_unroll(False)
    out = {}
    for i, name in enumerate(("flops", "bytes_accessed", "collective_wire_bytes")):
        delta = vals[2][i] - vals[1][i]
        # clamp: tiny models can compile the 2-unit variant *cheaper* per op
        out[name + "_extrap"] = max(vals[1][i], vals[1][i] + delta * (n_units - 1))
    return out


def run_cell(arch, shape_name, multi_pod, options, smoke=False, variant_name="",
             **variant):
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "sync": options.sync,
        "variant": variant_name,
    }
    t0 = time.time()
    try:
        fn, args = build_cell(arch, shape_name, mesh, multi_pod, options, smoke,
                              **variant)
        lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ca = compiled.cost_analysis()
        ma = compiled.memory_analysis()
        stats = collective_stats(compiled.as_text())
        rec.update({
            "ok": True,
            "lower_s": round(t1 - t0, 1),
            "compile_s": round(t2 - t1, 1),
            "flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0),
            "arg_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "peak_bytes_per_device": (
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
            ),
            "collectives": stats,
            "collective_wire_bytes": sum(s["wire_bytes"] for s in stats.values()),
        })
        try:
            rec.update(calibrate_cost(arch, shape_name, mesh, multi_pod, options,
                                      smoke, **variant))
        except Exception as e:  # noqa: BLE001
            rec["calibration_error"] = f"{type(e).__name__}: {e}"
    except Exception as e:  # noqa: BLE001 — report and continue
        rec.update({
            "ok": False,
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-2000:],
        })
    return rec


def main():
    # before JAX creates its backends: 512 fake host devices, never a chip
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.configs import list_archs, valid_cells
    from repro.train import steps as steps_lib

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--sync", default="auto")
    ap.add_argument("--layout", default="2d", choices=["2d", "fsdp"])
    ap.add_argument("--moe", default="tp", choices=["tp", "ep", "gshard"])
    ap.add_argument("--pad-vocab", type=int, default=0)
    ap.add_argument("--ce-chunk", type=int, default=0)
    ap.add_argument("--variant", default="", help="label stored in the JSON")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    options = steps_lib.TrainOptions(sync=args.sync, ce_chunk=args.ce_chunk)

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"], r.get("sync", "auto"),
             r.get("variant", ""))
            for r in results if r.get("ok")}
    variant = dict(layout=args.layout, moe_mode=args.moe, vocab_pad=args.pad_vocab)

    for arch in archs:
        shapes = valid_cells(arch) if args.shape == "all" else args.shape.split(",")
        for shape_name in shapes:
            if shape_name not in valid_cells(arch):
                print(f"SKIP {arch} x {shape_name} (inapplicable)", flush=True)
                continue
            for multi_pod in meshes:
                key = (arch, shape_name, "2x16x16" if multi_pod else "16x16",
                       args.sync, args.variant)
                if key in done:
                    continue
                rec = run_cell(arch, shape_name, multi_pod, options, args.smoke,
                               variant_name=args.variant, **variant)
                status = "OK " if rec["ok"] else "FAIL"
                extra = (
                    f"flops={rec['flops']:.3e} peakGB/dev={rec['peak_bytes_per_device']/1e9:.2f} "
                    f"coll={rec['collective_wire_bytes']/1e9:.2f}GB "
                    f"compile={rec['compile_s']}s"
                    if rec["ok"] else rec["error"][:160]
                )
                print(f"{status} {arch:22s} {shape_name:12s} {rec['mesh']:8s} {extra}",
                      flush=True)
                results.append(rec)
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} cells OK -> {args.out}")


if __name__ == "__main__":
    main()
