"""Fused RMSNorm Pallas kernel (TPU target, interpret-validated).

One VMEM tile of (block_rows, d) per grid step; the mean-square reduction and
scale are fused in one pass (the jnp version reads x twice after XLA's
fusion boundaries on CPU).  d is expected to be lane-aligned (multiple of
128) for TPU; arbitrary d works in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (rows, d)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + g_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6,
            block_rows: int = 128, *, interpret: bool) -> jax.Array:
    """x: (..., d); gamma: (d,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    n = flat.shape[0]
    block_rows = min(block_rows, n)
    pad = (-n) % block_rows
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    grid = (flat.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat, gamma)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape)
