"""Seeded weights, made on the device, for the program and the reference alike.

Each leaf is named by its path in the parameter tree (``layers/wq``,
``embed``, ...).  Its values are ``normal(key(seed, name[, layer])) * std``,
rounded to the dtype the cell serves in, with ``std = 1/sqrt(fan_in)`` for a
matrix (its input width, not the layer count), 0.1 for a norm's ``scale``
(the model multiplies by ``1 + scale``), and 1 for an untied embedding.  A
tied embedding is also the unembedding, so it takes ``1/sqrt(d_model)``.
Stacked layer leaves draw each layer from a key of its own, so the
reference can make one layer at a time.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def root_key(words) -> jax.Array:
    return jnp.asarray(words, jnp.uint32)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def stacked(name: str) -> bool:
    return name.startswith("layers/")


def leaf_std(name: str, shape, tied: bool) -> float:
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        return NORM_STD
    if name == "embed":
        return 1.0 / math.sqrt(shape[-1]) if tied else 1.0
    return 1.0 / math.sqrt(shape[-2])


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def layer_leaf(key, name: str, layer, shape, std: float, dtype) -> jax.Array:
    """Layer ``layer`` of stacked leaf ``name``; ``shape`` leaves out the layer
    axis.  ``layer`` may be traced."""
    k = jax.random.fold_in(_leaf_key(key, name), layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def whole_leaf(key, name: str, shape, std: float, dtype) -> jax.Array:
    if stacked(name):
        return jax.vmap(lambda l: layer_leaf(key, name, l, shape[1:], std, dtype))(
            jnp.arange(shape[0]))
    k = _leaf_key(key, name)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def make_params(template, key, *, tied: bool):
    """Fills ``template`` (a tree of ``ShapeDtypeStruct``, such as
    ``jax.eval_shape`` of the program's ``init_params``).  Call under
    ``jax.jit``, so the weights are made on the device in one program."""
    def fill(path, leaf):
        name = leaf_name(path)
        return whole_leaf(key, name, leaf.shape, leaf_std(name, leaf.shape, tied),
                          leaf.dtype)

    return jax.tree_util.tree_map_with_path(fill, template)
