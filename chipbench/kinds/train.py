"""A training cell: the program's own train step, driven through its own loop.

Set-up builds one object, the compiled step with its state, and drives it
through its first three steps with ``launch/train.train_loop``, the window's
own call and feed; that same generator then runs the window.  The readings
of those three steps are compared with the reference once the window has
closed and the program's state is freed.
"""

from __future__ import annotations

import math

from chipbench import common, compare
from chipbench.reference import weights as W

CHECK_STEPS = 3
NEVER = 1 << 40  # the loop's stop: the window ends it


def run(ctx) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.launch import train as T
    from repro.models import get_model
    from repro.train import optimizer as opt_lib

    job, config, family = ctx.traffic, ctx.config, ctx.family
    cfg = family.arch_config(config, schedule=job["lr_schedule"])
    devices = ctx.devices
    mesh = jax.make_mesh((len(devices),), ("data",), axis_types=(AxisType.Auto,),
                         devices=devices)
    key = W.root_key(common.key_words(ctx.seed))
    template = jax.eval_shape(
        lambda: get_model(cfg).init_params(cfg, jax.random.PRNGKey(0),
                                           dtype=jnp.dtype(job["dtype"])))

    def init(k):
        params = W.make_params(template, k, tied=cfg.tie_embeddings)
        return params, opt_lib.init(params)

    params, opt_state = jax.jit(init, out_shardings=NamedSharding(mesh, P()))(key)
    step = T.make_step(cfg, steps=job["horizon_steps"], lr=job["lr"], sync=job["sync"],
                       mesh=mesh)
    batch, seq = job["batch"], job["seq"]
    rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                sharding=NamedSharding(mesh, P("data")))
    step = step.lower(params, opt_state, {"tokens": rows, "labels": rows}).compile()

    names = [W.leaf_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    b1 = job["b1"]

    @jax.jit
    def grad_norms(m):  # the first gradient, as AdamW's first moment keeps it
        return [jnp.sqrt(jnp.sum(jnp.square(x))) / (1 - b1) for x in jax.tree.leaves(m)]

    @jax.jit
    def change_norms(p, k):
        p0 = W.make_params(template, k, tied=cfg.tie_embeddings)
        return [jnp.sqrt(jnp.sum(jnp.square(a - b)))
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]

    loop = T.train_loop(cfg, mesh, step, params, opt_state, start=0, stop=NEVER,
                        seq=seq, batch=batch, seed=ctx.seed)
    del params, opt_state
    losses, first_grad = [], None
    for i in range(CHECK_STEPS):
        _, params, opt_state, metrics = next(loop)
        losses.append(float(metrics["loss"]))
        if i == 0:
            first_grad = dict(zip(names, map(float, grad_norms(opt_state.m))))
    change = dict(zip(names, map(float, change_norms(params, key))))
    del params, opt_state, metrics
    ctx.setup_done()

    n, in_flight, window_losses = 0, None, []
    with ctx.window():
        while True:
            with ctx.span("cb.step"):
                _, params, opt_state, metrics = next(loop)
            n += 1
            window_losses.append(metrics["loss"])
            if in_flight is not None:
                with ctx.span("cb.wait"):
                    in_flight.block_until_ready()
            in_flight = metrics["loss"]
            if ctx.elapsed() >= ctx.seconds:
                break
        jax.block_until_ready((params, opt_state, metrics))
    window_s = ctx.window_s
    window_losses = [float(x) for x in window_losses]
    failed = sum(not math.isfinite(x) for x in window_losses)
    tokens = n * batch * seq
    ctx.report(attempted=n, failed=failed,
               e2e={"train_tokens_per_s": tokens / window_s},
               layer_inputs={"steps": n,
                             "flops_per_step": family.train_step_flops(config, batch, seq)})
    ctx.read_memory()
    loop.close()
    del loop, step, params, opt_state, metrics
    jax.clear_caches()

    ref = family.train_reference(config, job, ctx.seed, steps=CHECK_STEPS,
                                 dtype=jnp.dtype(job["dtype"]), chips=len(devices),
                                 device=devices[0])
    ctx.reference = ref
    got = {"losses": losses, "grad_norms": first_grad, "change_norms": change}
    for name, value in compare.train_numbers(got, ref).items():
        ctx.check(name, value, job["limits"][name])
    ctx.check("compiles_in_window", ctx.window_lowerings, 0)
