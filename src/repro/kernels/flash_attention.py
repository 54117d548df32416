"""Flash attention forward kernel for TPU (pl.pallas_call + BlockSpec).

Design (TPU-native, not a CUDA port — see DESIGN.md hardware adaptation):

* Grid = (batch × q-heads, Sq/BQ, Sk/BK).  The last grid dimension iterates
  sequentially on TPU, so the online-softmax running state (m, l, acc) lives
  in VMEM scratch and persists across KV blocks of the same (head, q-block).
* The kernel works on a heads-major (B, H, S, D) layout, so every block's
  last two dimensions are (BQ or BK, D): a sublane multiple of 8 by the full
  head width, the tiling the TPU compiler requires.  The (B, S, H, D) layout
  of the callers would put a block of 1 head in the sublane dimension, which
  the compiler rejects.
* BlockSpecs stream one (BQ, D) query tile and one (BK, D) key/value tile
  into VMEM per step; the (BQ, BK) score tile hits the MXU via jnp.dot with
  fp32 accumulation.  BQ = BK = 128 keeps every matmul dimension
  MXU-aligned (multiples of 128 / the lane width).
* GQA is folded into the K/V index_map (query head h reads kv head
  h // group) — no KV repetition in memory.
* Causal and sliding-window masks prune whole KV blocks via ``pl.when``
  (skipped blocks cost no MXU work), matching the HammingMesh evaluation
  models (GPT-3 causal LM, RecurrentGemma local attention).

Backward is provided by ops.flash_attention via jax.custom_vjp with a
rematerializing reference backward (standard practice when only the forward
kernel is hand-written).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,  # output tile
    m_scr, l_scr, acc_scr,  # VMEM scratch, persists over the kv grid dim
    *, scale: float, causal: bool, window: int, bq: int, bk: int,
    sk_valid: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    # block-level pruning: causal (block entirely above diagonal) and window
    # (block entirely left of the band)
    needed = True
    if causal:
        needed = k_start <= q_start + bq - 1
    if window:
        in_band = k_start + bk - 1 > q_start - window
        needed = jnp.logical_and(needed, in_band) if causal else in_band

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < sk_valid
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    *,
    interpret: bool,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    group = h // kv
    scale = 1.0 / math.sqrt(d)

    bq = min(bq, pl.cdiv(sq, 8) * 8)
    bk = min(bk, pl.cdiv(sk, 8) * 8)
    # heads-major, sequence padded to whole blocks: (B, H, S, D)
    q = jnp.pad(q.swapaxes(1, 2), ((0, 0), (0, 0), (0, (-sq) % bq), (0, 0)))
    k = jnp.pad(k.swapaxes(1, 2), ((0, 0), (0, 0), (0, (-sk) % bk), (0, 0)))
    v = jnp.pad(v.swapaxes(1, 2), ((0, 0), (0, 0), (0, (-sk) % bk), (0, 0)))
    sq_p, sk_p = q.shape[2], k.shape[2]
    grid = (b * h, sq_p // bq, sk_p // bk)

    q_spec = pl.BlockSpec(
        (1, 1, bq, d), lambda bh, qi, ki: (bh // h, bh % h, qi, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda bh, qi, ki: (bh // h, (bh % h) // group, ki, 0)
    )

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sk_valid=sk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq].swapaxes(1, 2)
