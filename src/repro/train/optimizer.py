"""Optimizer substrate (from scratch — no optax): AdamW, schedules, clipping.

Includes the WSD (warmup-stable-decay) schedule used by MiniCPM
[arXiv:2404.06395] alongside the standard warmup-cosine.
Optimizer moments inherit the parameter sharding (ZeRO-style when FSDP is on).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import transformer


class AdamWState(NamedTuple):
    step: jax.Array
    m: dict
    v: dict
    # the model's state without gradient (``transformer.init_buffers``: the
    # routers' correction biases), kept beside the moments and checkpointed
    # with them; no gradient, no decay: the train step moves it
    buffers: dict


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"  # cosine | wsd | constant
    wsd_stable_frac: float = 0.8  # fraction of post-warmup steps held stable


def schedule_lr(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, step / jnp.maximum(1.0, cfg.warmup_steps))
    if cfg.schedule == "constant":
        return cfg.lr * warm
    rest = jnp.maximum(0.0, step - cfg.warmup_steps)
    horizon = jnp.maximum(1.0, cfg.total_steps - cfg.warmup_steps)
    if cfg.schedule == "cosine":
        frac = jnp.clip(rest / horizon, 0.0, 1.0)
        return cfg.lr * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    # WSD: stable plateau then linear decay to 10% (MiniCPM)
    stable = cfg.wsd_stable_frac
    frac = jnp.clip(rest / horizon, 0.0, 1.0)
    decay_frac = jnp.clip((frac - stable) / jnp.maximum(1e-6, 1.0 - stable), 0.0, 1.0)
    return cfg.lr * warm * (1.0 - 0.9 * decay_frac)


def init(params) -> AdamWState:
    """Zero moments in the parameters' tree, and the model's own buffers."""
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamWState(step=jnp.zeros((), jnp.int32), m=zeros,
                      v=jax.tree.map(jnp.copy, zeros),
                      buffers=transformer.init_buffers(params))


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


def apply(cfg: AdamWConfig, state: AdamWState, params, grads):
    """One AdamW step -> (new_params, new_state, metrics)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    step = state.step + 1
    lr = schedule_lr(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m2 = cfg.b1 * m + (1 - cfg.b1) * g32
        v2 = cfg.b2 * v + (1 - cfg.b2) * g32 * g32
        mh = m2 / b1c
        vh = v2 / b2c
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m2, v2

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.m)
    flat_v = jax.tree.leaves(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    return new_p, AdamWState(step, new_m, new_v, state.buffers), {"lr": lr, "grad_norm": gnorm}
