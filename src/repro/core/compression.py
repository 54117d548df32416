"""Gradient compression for the data-parallel dimension (paper Appendix A).

Top-k gradient sparsification with local error feedback (accumulating the
unsent residual), in the style of SparCML [18] / Renggli et al.  The sparse
reduction is implemented as an allgather of (index, value) pairs over the
data-parallel axis followed by a scatter-add — the "fill-in tolerant" scheme
the paper describes for moderate k.

All functions are jit-compatible and usable inside ``jax.shard_map``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class CompressionState(NamedTuple):
    """Error-feedback residual, one entry per parameter leaf."""

    residual: jax.Array


def init_state(grad: jax.Array) -> CompressionState:
    return CompressionState(residual=jnp.zeros_like(grad))


def topk_compress(
    grad: jax.Array, state: CompressionState, k: int
) -> tuple[jax.Array, jax.Array, CompressionState]:
    """Select the k largest-magnitude entries; bank the rest as residual.

    Returns (values[k], indices[k], new_state).
    """
    flat = grad.reshape(-1) + state.residual.reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    vals = flat[idx]
    residual = flat.at[idx].set(0.0)
    return vals, idx, CompressionState(residual=residual.reshape(grad.shape))


def decompress(vals: jax.Array, idx: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    out = jnp.zeros(int(jnp.prod(jnp.array(shape))), vals.dtype)
    return out.at[idx].add(vals).reshape(shape)


def sparse_allreduce(
    grad: jax.Array, state: CompressionState, k: int, axis_name: str
) -> tuple[jax.Array, CompressionState]:
    """Sparse allreduce over ``axis_name`` inside shard_map.

    Communication volume: ``D * k * (4 + itemsize)`` bytes per device instead
    of the dense ``2 * N * itemsize`` ring volume — a win for k << N/D.
    """
    vals, idx, new_state = topk_compress(grad, state, k)
    all_vals = jax.lax.all_gather(vals, axis_name)  # (D, k)
    all_idx = jax.lax.all_gather(idx, axis_name)
    n = grad.size
    dense = jnp.zeros((n,), grad.dtype)
    dense = dense.at[all_idx.reshape(-1)].add(all_vals.reshape(-1))
    d = lax.axis_size(axis_name)
    return (dense / d).reshape(grad.shape), new_state


def compression_ratio(n_params: int, k: int, d: int, itemsize: int = 4) -> float:
    """Dense-ring bytes / sparse bytes per device (paper App. A economics)."""
    dense = 2 * n_params * itemsize
    sparse = d * k * (4 + itemsize)
    return dense / sparse
