"""Jit-ready wrappers around the Pallas kernels.

The kernels run compiled on a TPU and in interpret mode anywhere else; this
module is the one place that chooses (``_on_tpu``).

``flash_attention`` exposes a jax.custom_vjp op: the forward runs the Pallas
kernel; the backward rematerializes through the jnp reference (exact same
math), so models can train with the kernel enabled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import flash_attention as fa
from repro.kernels import ref
from repro.kernels import rmsnorm as rms


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=True, window=0):
    return fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, interpret=not _on_tpu()
    )


def _fwd(q, k, v, causal, window):
    out = flash_attention(q, k, v, causal, window)
    return out, (q, k, v)


def _bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(q_, k_, v_, causal, window), q, k, v
    )
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)


def rmsnorm(x, gamma, eps=1e-6):
    return rms.rmsnorm(x, gamma, eps, interpret=not _on_tpu())


def grouped_matmul(lhs, rhs, group_sizes, tiling):
    """Rows of ``lhs`` (M, K) in consecutive groups of ``group_sizes``, each
    times its own matrix of ``rhs`` (G, K, N): megablox's ``gmm``, with its
    backward.  Only the first G groups are computed; rows past them (further
    groups, padding) come out 0.  ``tiling(m, k, n)`` gives the tiles."""
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None, False,
                        not _on_tpu())
