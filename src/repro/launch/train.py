"""End-to-end training driver with checkpoint/restart and failure simulation.

Runs a real (CPU-sized) training loop through the full stack: config → data
pipeline → sharded train step (optionally with the paper's HxMesh gradient
collectives) → periodic checkpointing → simulated board failure →
allocation-layer remap → restore-and-continue.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b-smoke --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b-smoke \
      --steps 60 --simulate-failure 25 --checkpoint-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import get_config
from repro.core import allocation as alloc_lib
from repro.data.pipeline import make_batch
from repro.launch.cache import enable_compile_cache
from repro.models import get_model
from repro.obs import device as OD
from repro.parallel.sharding import Policy
from repro.train import optimizer as opt_lib
from repro.train import steps as steps_lib


def make_step(cfg, *, steps, lr=3e-3, sync="auto", remat=True, compress_k=0,
              mesh=None):
    """The jitted train step.  Params and optimizer state are donated: the
    step's outputs take their buffers, so each is held once."""
    ocfg = opt_lib.AdamWConfig(
        lr=lr, warmup_steps=max(1, steps // 10), total_steps=steps,
        schedule=cfg.schedule,
    )
    options = steps_lib.TrainOptions(sync=sync, remat=remat,
                                     compress_k=compress_k)
    return jax.jit(steps_lib.make_train_step(
        cfg, ocfg, options, Policy(data_axes=("data",)), mesh),
        donate_argnums=(0, 1))


def build(cfg, *, steps, lr=3e-3, seed=0, sync="auto", remat=True,
          compress_k=0):
    """f32 params and their AdamW state, made in place replicated over a 1-D
    ``data`` mesh of every device (one data-parallel replica each), and the
    step -> (mesh, params, opt_state, step_fn)."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",),
                         axis_types=(AxisType.Auto,))

    def init(key):
        params = get_model(cfg).init_params(cfg, key, dtype=jnp.float32)
        return params, opt_lib.init(params)

    params, opt_state = jax.jit(init, out_shardings=NamedSharding(mesh, P()))(
        jax.random.PRNGKey(seed))
    step_fn = make_step(cfg, steps=steps, lr=lr, sync=sync, remat=remat,
                        compress_k=compress_k, mesh=mesh)
    return mesh, params, opt_state, step_fn


def place_batch(batch, mesh):
    """A host batch split over the mesh's data axis."""
    return jax.device_put(batch, NamedSharding(mesh, P("data")))


def train_loop(cfg, mesh, step_fn, params, opt_state, *, start, stop, seq,
               batch, seed=0):
    """Steps ``start`` .. ``stop - 1`` on the synthetic batches; yields
    ``(steps_done, params, opt_state, metrics)`` after each step.  Each step
    is a profiler step, and its batch, placement and dispatch are host spans
    (``repro.obs.device``)."""
    OD.trace_gc()
    for step in range(start, stop):
        with jax.profiler.StepTraceAnnotation(OD.TRAIN_STEPS, step_num=step):
            with OD.span(OD.TRAIN_BATCH, step=step):
                rows = make_batch(cfg, seq, batch, step=step, seed=seed)
            with OD.span(OD.TRAIN_PLACE):
                b = place_batch(rows, mesh)
            with OD.span(OD.TRAIN_STEP):
                params, opt_state, metrics = step_fn(params, opt_state, b)
        yield step + 1, params, opt_state, metrics


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b-smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync", default="auto")
    ap.add_argument("--compress-k", type=int, default=0)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--simulate-failure", type=int, default=0,
                    help="board failure at this step (needs --checkpoint-dir)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)

    def fresh():
        return build(cfg, steps=args.steps, lr=args.lr, seed=args.seed,
                     sync=args.sync, remat=not args.no_remat,
                     compress_k=args.compress_k)

    mesh, params, opt_state, step_fn = fresh()
    start = 0
    if args.checkpoint_dir:
        restored, rstep = ckpt_lib.restore_latest(
            args.checkpoint_dir, {"p": params, "o": opt_state})
        if restored is not None:
            params, opt_state = restored["p"], restored["o"]
            start = rstep
            print(f"[train] resumed from step {start}")

    # the job's boards on a small HxMesh (the paper's allocation layer)
    allocator = alloc_lib.HxMeshAllocator(8, 8)
    placement = allocator.allocate(alloc_lib.Job(0, 2, 4), transpose=True)
    print(f"[train] job placed on boards rows={placement.rows} cols={placement.cols}")

    t0 = time.time()
    step = start
    while step < args.steps:
        for step, params, opt_state, metrics in train_loop(
                cfg, mesh, step_fn, params, opt_state, start=step,
                stop=args.steps, seq=args.seq, batch=args.batch,
                seed=args.seed):
            if step % 10 == 0 or step == args.steps:
                # experts' imbalance: the busiest expert's pairs over the mean
                load = metrics.get("load")
                imbalance = ("" if load is None else
                             f"load max/mean {float(load.max() / load.mean()):.2f} ")
                print(f"[train] step {step:4d} loss {float(metrics['loss']):.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} {imbalance}"
                      f"({(time.time() - t0):.1f}s)")
            if args.checkpoint_dir and step % args.checkpoint_every == 0:
                ckpt_lib.save_step(args.checkpoint_dir,
                                   {"p": params, "o": opt_state}, step)

            if args.simulate_failure and step == args.simulate_failure:
                # -- the paper's fault-tolerance loop (§III-E, §IV) ----------
                r, c = placement.boards[0]
                print(f"[failure] board ({r},{c}) failed — evicting job")
                allocator.fail_board(r, c)
                new_pl = alloc_lib.remap_after_failure(
                    allocator, alloc_lib.Job(0, 2, 4), transpose=True, aspect=True)
                assert new_pl is not None, "no spare virtual sub-HxMesh"
                assert alloc_lib.is_virtual_subhxmesh(new_pl.boards)
                placement = new_pl
                print(f"[failure] remapped to rows={new_pl.rows} cols={new_pl.cols}")
                assert args.checkpoint_dir, "failure simulation needs checkpoints"
                mesh, params, opt_state, step_fn = fresh()
                restored, rstep = ckpt_lib.restore_latest(
                    args.checkpoint_dir, {"p": params, "o": opt_state})
                params, opt_state = restored["p"], restored["o"]
                step = rstep
                print(f"[failure] restarted from checkpoint step {rstep}")
                args.simulate_failure = 0  # only once
                break

    print(f"[train] done: {args.steps} steps in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
