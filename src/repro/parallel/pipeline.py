"""Pipeline parallelism (GPipe-style) via shard_map + ppermute.

The paper models the pipeline dimension as rings with nearest-neighbor
volume ``V_P`` per hop (§II-B, §V-B1-b) and overlaps hop communication with
stage compute (Fig 14).  Here the P dimension is a mesh axis: each device
holds one stage's parameters, microbatches flow stage-to-stage with
``lax.ppermute`` — on an HxMesh/TPU torus these are exactly neighbor-link
transfers.

``pipeline_forward`` runs M microbatches through P stages in M + P - 1 ticks
(the GPipe schedule with its (P-1)/M bubble).  It is jax.grad-compatible
(the transpose of ppermute is the reverse ppermute), so the same schedule
serves the backward pass.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_forward(stage_fn, stage_params, x_micro, axis: str):
    """Run inside shard_map (manual over ``axis``).

    stage_fn(params, x) -> y            one stage's computation
    stage_params                        this device's stage parameters
    x_micro: (M, mb, ...)               microbatches (same array on every
                                        stage; only stage 0 reads it)
    Returns (M, mb, ...) outputs valid on the LAST stage (others zeros).
    """
    p = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    m = x_micro.shape[0]
    fwd = [(i, i + 1) for i in range(p - 1)]

    def tick(t, carry):
        state, outputs = carry
        # stage 0 injects microbatch t (if t < M); others use the handoff
        mb = lax.dynamic_index_in_dim(x_micro, jnp.minimum(t, m - 1), 0, False)
        x_in = jnp.where(idx == 0, mb, state)
        y = stage_fn(stage_params, x_in)
        # last stage records output for microbatch t-(p-1)
        oi = jnp.clip(t - (p - 1), 0, m - 1)
        write = jnp.logical_and(idx == p - 1, t >= p - 1)
        cur = lax.dynamic_index_in_dim(outputs, oi, 0, False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, y, cur), oi, 0
        )
        state = lax.ppermute(y, axis, fwd)
        return state, outputs

    state0 = jnp.zeros(x_micro.shape[1:], x_micro.dtype)
    outputs0 = jnp.zeros_like(x_micro)
    _, outputs = lax.fori_loop(0, m + p - 1, tick, (state0, outputs0))
    return outputs


def make_pipelined_loss(stage_fn, final_fn, axis: str):
    """loss over pipelined stages; final_fn maps last-stage output to loss.

    Returns f(stage_params, x_micro, labels_micro) usable under shard_map with
    stage_params sharded over ``axis`` (leading stage dim consumed by the
    shard_map spec).
    """

    def f(stage_params, x_micro, labels_micro):
        p = lax.axis_size(axis)
        idx = lax.axis_index(axis)
        outs = pipeline_forward(stage_fn, stage_params, x_micro, axis)
        loss = final_fn(outs, labels_micro)
        # only the last stage's loss is real; broadcast it
        loss = jnp.where(idx == p - 1, loss, 0.0)
        return lax.psum(loss, axis)

    return f
