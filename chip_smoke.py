#!/usr/bin/env python3
"""Bring-up smoke test of the training and serving path on a TPU.

  python chip_smoke.py            # one chip: kernels, serve, train
  python chip_smoke.py --chips 4  # four chips: the paper's gradient sync only

Everything runs in this one process, through the entry points a user calls,
on minicpm-2b at its published widths (``configs/minicpm_2b.py``) with random
weights made from ``--seed``:

* kernels: the Pallas flash-attention kernel, compiled and not interpreted,
  at minicpm-2b's head layout (36 heads, d=64) and granite-8b's GQA layout
  (32 heads, 8 kv heads, d=128), causal, S=2048, bf16; and the rmsnorm
  kernel at d=2304.  Each is checked against its jnp reference.
* serve: all 40 layers in bf16 (``launch/serve.py``), a 128-token prompt,
  then 64 greedy tokens for each of 8 sequences, the cache donated.
* train: ``n_layers`` cut to 2, f32 params, batch 2 x 2048, 5 AdamW steps
  (``launch/train.py``), params and optimizer state donated.  The loss must
  be finite and fall.
* ``--chips 4``: one train step on a (4,) data mesh with the gradients
  summed by the paper's ring and bidirectional ring, against XLA's own sum
  (``sync="auto"``); ``n_layers`` is cut to 1 so that the ring's f32
  gradient bucket fits beside the replicated AdamW state.  Then the four
  allreduce algorithms on a 2x2 mesh against ``psum``, 16 MiB per chip.

Findings are printed as they come: compile and step seconds (ending in
``block_until_ready``), losses, the largest kernel-to-reference difference
and the device's ``peak_bytes_in_use`` after each phase.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase fails,
the script exits non-zero and does not print it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import collectives as coll  # noqa: E402
from repro.data.pipeline import make_batch  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.models.layers import rmsnorm as jnp_rmsnorm  # noqa: E402

MINICPM = get_config("minicpm-2b")

# Tolerances (elementwise: |got - want| <= atol + rtol * |want|); the kernel
# and allreduce bounds use atol = rtol = the number given.
# flash: the outputs are bf16, whose spacing is 2^-7 relative, so two correct
# roundings of one value may differ by one spacing; and the kernel may feed
# its f32 probabilities to the MXU as bf16, another 2^-8 relative.  3e-2
# covers both, as tests/test_kernels.py does for bf16.
FLASH_TOL = 3e-2
# rmsnorm: one f32 reduction, then the same bf16 rounding: one spacing.
RMS_TOL = 2e-2
# gradient sync: the synced gradient is read from AdamW's first moment after
# one step (m = (1 - b1) * clipped gradient).  The parameters cannot show it:
# the first AdamW step moves each by lr * g / (|g| + eps), which keeps only
# the sign of g, and a near-zero gradient summed in another order may change
# sign, a move of 2 * lr (0.02% of the entries on four chips).  The paths add
# the same f32 terms in another order and fuse the per-chip backward
# differently: 1e-5 relative on the gradient norm, but up to 12% on single
# entries of the attention weights, whose gradients are near zero at this
# random init.  So m is compared as one vector, by its relative L2 distance; a
# missing or doubled chip's share would move it by about a quarter.
MOMENT_TOL = 1e-3
GNORM_RTOL = 1e-4
LOSS_RTOL = 1e-5
# allreduce: four f32 addends in another order, a few ulp of sums below ~10.
ALLREDUCE_TOL = 1e-5


def report(phase: str, **found) -> None:
    print(f"[{phase}] {json.dumps(found)}", flush=True)


def peak_bytes() -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.local_devices())


def compiled(fn, *args):
    """Ahead-of-time compile of a jitted ``fn`` -> (executable, seconds)."""
    t = time.perf_counter()
    exe = fn.lower(*args).compile()
    return exe, time.perf_counter() - t


def timed(exe, *args):
    t = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    return out, time.perf_counter() - t


def max_diff(got, want, what: str, rtol: float, atol: float) -> float:
    """Asserts ``|got - want| <= atol + rtol * |want|``; returns the largest
    ``|got - want|``."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)
    return float(np.max(np.abs(got - want)))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def check_kernels(*, seed: int, batch: int = 2, seq: int = 2048) -> None:
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    layouts = {"minicpm-2b": (36, 36, 64), "granite-8b": (32, 8, 128)}
    for name, (h, kv, d) in layouts.items():
        q = jax.random.normal(keys[0], (batch, seq, h, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (batch, seq, kv, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (batch, seq, kv, d), jnp.bfloat16)
        flash, compile_s = compiled(
            jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, True, 0)), q, k, v)
        assert "tpu_custom_call" in flash.as_text(), f"flash {name}: no TPU kernel"
        timed(flash, q, k, v)  # warm-up
        out, call_s = timed(flash, q, k, v)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.flash_attention_ref, static_argnums=(3, 4))(
                q, k, v, True, 0)
        report("kernels", kernel="flash_attention", layout=name,
               shape=[batch, seq, h, kv, d], compile_s=compile_s, call_s=call_s,
               max_abs_diff=max_diff(out, want, f"flash {name}", FLASH_TOL, FLASH_TOL),
               tol=FLASH_TOL, peak_bytes_in_use=peak_bytes())

    x = jax.random.normal(keys[3], (batch, seq, MINICPM.d_model), jnp.bfloat16)
    g = 0.1 * jax.random.normal(keys[4], (MINICPM.d_model,), jnp.float32)
    rms, compile_s = compiled(jax.jit(ops.rmsnorm), x, g)
    assert "tpu_custom_call" in rms.as_text(), "rmsnorm: no TPU kernel"
    timed(rms, x, g)
    out, call_s = timed(rms, x, g)
    want = jax.jit(jnp_rmsnorm)(x, g)
    report("kernels", kernel="rmsnorm", shape=list(x.shape), compile_s=compile_s,
           call_s=call_s, max_abs_diff=max_diff(out, want, "rmsnorm", RMS_TOL, RMS_TOL),
           tol=RMS_TOL, peak_bytes_in_use=peak_bytes())


def check_serve(cfg, *, batch: int, prompt_len: int, n_decode: int,
                seed: int) -> None:
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(seed))  # bf16
    prompts = make_batch(cfg, prompt_len, batch, seed=seed)["tokens"]
    cache = jax.eval_shape(
        lambda: model.init_cache(cfg, batch, prompt_len + n_decode))
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    step, compile_s = compiled(serve.make_step(cfg), params, cache, tok)
    tokens, prefill_s, decode_s = serve.generate(cfg, params, step, prompts,
                                                 n_decode)
    assert tokens.shape == (batch, n_decode), tokens.shape
    assert ((tokens >= 0) & (tokens < cfg.vocab)).all(), "token out of vocab"
    report("serve", arch=cfg.name, n_layers=cfg.n_layers, batch=batch,
           prompt_len=prompt_len, decoded_per_seq=tokens.shape[1],
           compile_s=compile_s, prefill_s=prefill_s, decode_s=decode_s,
           decode_step_s=decode_s / n_decode, first_tokens=tokens[0, :8].tolist(),
           peak_bytes_in_use=peak_bytes())


def check_train(cfg, *, batch: int, seq: int, steps: int, seed: int) -> None:
    mesh, params, opt_state, step_fn = train.build(cfg, steps=steps, seed=seed)
    first = train.place_batch(make_batch(cfg, seq, batch, seed=seed), mesh)
    step_fn, compile_s = compiled(step_fn, params, opt_state, first)
    del first
    losses, step_s = [], []
    t = time.perf_counter()
    for _, params, opt_state, metrics in train.train_loop(
            cfg, mesh, step_fn, params, opt_state, start=0, stop=steps,
            seq=seq, batch=batch, seed=seed):
        jax.block_until_ready((params, opt_state, metrics))
        step_s.append(time.perf_counter() - t)
        losses.append(float(metrics["loss"]))
        t = time.perf_counter()
    report("train", arch=cfg.name, n_layers=cfg.n_layers, batch=batch, seq=seq,
           compile_s=compile_s, step_s=step_s, losses=losses,
           peak_bytes_in_use=peak_bytes())
    assert all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def check_spread(params, batch, devices) -> None:
    """Params replicated on every chip, the batch split across them: nothing
    is left on device 0 alone."""
    for leaf in jax.tree.leaves(params):
        assert leaf.sharding.device_set == devices, leaf.sharding
    for x in batch.values():
        shards = x.addressable_shards
        assert {s.device for s in shards} == devices, x.sharding
        assert all(s.data.shape[0] == x.shape[0] // len(devices) for s in shards)


def check_gradient_sync(cfg, *, batch: int, seq: int, seed: int) -> None:
    devices = set(jax.devices())
    found = {}
    for sync in ("auto", "ring", "bidir"):
        mesh, params, opt_state, step_fn = train.build(cfg, steps=1, seed=seed,
                                                       sync=sync)
        b = train.place_batch(make_batch(cfg, seq, batch, seed=seed), mesh)
        check_spread(params, b, devices)
        step_fn, compile_s = compiled(step_fn, params, opt_state, b)
        (params, opt_state, metrics), step_s = timed(step_fn, params, opt_state, b)
        metrics = {k: float(v) for k, v in metrics.items()}
        found[sync] = (metrics, jax.device_get((params, opt_state.m)))
        report("gradient_sync", sync=sync, arch=cfg.name, n_layers=cfg.n_layers,
               batch=batch, seq=seq, chips=len(devices), compile_s=compile_s,
               step_s=step_s, loss=metrics["loss"], grad_norm=metrics["grad_norm"],
               peak_bytes_in_use=peak_bytes())
        del params, opt_state, b
    ref_metrics, (ref_params, ref_m) = found.pop("auto")
    for sync, (metrics, (params, m)) in found.items():
        loss_diff = metrics["loss"] - ref_metrics["loss"]
        gnorm_diff = metrics["grad_norm"] - ref_metrics["grad_norm"]
        assert abs(loss_diff) <= LOSS_RTOL * ref_metrics["loss"], loss_diff
        assert abs(gnorm_diff) <= GNORM_RTOL * ref_metrics["grad_norm"], gnorm_diff
        m_pairs = list(zip(jax.tree.leaves(m), jax.tree.leaves(ref_m)))
        m_dist = math.sqrt(sum(float(np.sum((g - r) ** 2)) for g, r in m_pairs)
                           / sum(float(np.sum(r ** 2)) for _, r in m_pairs))
        assert m_dist <= MOMENT_TOL, f"{sync} gradient: {m_dist}"
        leaf_worst = max(float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
                         for g, r in m_pairs)
        p_diff = [np.abs(p - r) for p, r in zip(jax.tree.leaves(params),
                                                jax.tree.leaves(ref_params))]
        max_p = max(float(np.max(d)) for d in p_diff)
        assert max_p <= 2 * ref_metrics["lr"] * (1 + 1e-3), max_p
        report("gradient_sync", sync=sync, against="auto", loss_diff=loss_diff,
               grad_norm_diff=gnorm_diff, gradient_rel_l2=m_dist, tol=MOMENT_TOL,
               worst_leaf_max_diff_of_largest=leaf_worst,
               max_param_diff=max_p, lr=ref_metrics["lr"],
               params_moved_differently=int(sum(np.sum(d > 1e-5) for d in p_diff)),
               n_params=int(sum(d.size for d in p_diff)))


def check_allreduce(*, seed: int, n_floats: int = 4 << 20) -> None:
    mesh = jax.make_mesh((2, 2), ("r", "c"), axis_types=(AxisType.Auto,) * 2)
    spec = P(("r", "c"))
    x = jax.device_put(jax.random.normal(jax.random.PRNGKey(seed), (4, n_floats)),
                       NamedSharding(mesh, spec))
    results = {}
    for algo in ("psum", "ring", "bidir", "torus", "hamiltonian"):
        fn = jax.jit(jax.shard_map(
            lambda v, a=algo: coll.allreduce(v, a, ("r", "c"), (2, 2)),
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
        exe, compile_s = compiled(fn, x)
        timed(exe, x)
        results[algo], call_s = timed(exe, x)
        found = dict(algo=algo, mesh=[2, 2], bytes_per_chip=4 * n_floats,
                     compile_s=compile_s, call_s=call_s)
        if algo != "psum":
            found.update(max_abs_diff=max_diff(results[algo], results["psum"], algo,
                                               ALLREDUCE_TOL, ALLREDUCE_TOL),
                         tol=ALLREDUCE_TOL)
        report("allreduce", **found)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip gradient-sync phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
                 f"JAX found {len(devices)}")
    report("setup", compile_cache=enable_compile_cache(), jax=jax.__version__,
           device_kind=devices[0].device_kind, count=len(devices))

    if args.chips == 4:
        check_gradient_sync(dataclasses.replace(MINICPM, n_layers=1), batch=4,
                            seq=2048, seed=args.seed)
        check_allreduce(seed=args.seed)
    else:
        check_kernels(seed=args.seed)
        check_serve(MINICPM, batch=8, prompt_len=128, n_decode=64,
                    seed=args.seed)
        check_train(dataclasses.replace(MINICPM, n_layers=2), batch=2, seq=2048,
                    steps=5, seed=args.seed)

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
