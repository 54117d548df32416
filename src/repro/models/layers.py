"""Shared neural-network layers (pure JAX, param pytrees, no flax).

Conventions:
* params are nested dicts of jnp arrays; layer-stacked params carry a leading
  layer dimension and are consumed by ``lax.scan``.
* activations default to bfloat16, reductions/softmax in float32.
* attention supports GQA, causal masks, sliding windows, chunked
  (online-softmax) evaluation for long sequences, and single-token decode
  against a KV cache.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

DType = jnp.dtype

# Analysis mode: when True, lax.scan loops are fully unrolled so that
# compiled.cost_analysis() counts every iteration (XLA costs a while-loop
# body once).  Set by launch/dryrun.py for the FLOP-calibration lowerings.
SCAN_UNROLL = False


def set_scan_unroll(flag: bool) -> None:
    global SCAN_UNROLL
    SCAN_UNROLL = flag


def scan_unroll(length: int):
    return length if SCAN_UNROLL else 1


# ---------------------------------------------------------------------------
# activation sharding constraints
#
# FSDP shards weights over the same mesh axis as the batch; without explicit
# anchors GSPMD sometimes resolves the contraction conflict by replicating the
# *batch* (observed on llama3.2 train_4k: 67 GB/device logits).  Models call
# ``constrain(x, spec)`` at layer boundaries with the batch-sharded spec the
# trainer provides; the weights then get the ZeRO-3-style per-layer all-gather.
# ---------------------------------------------------------------------------


def constrain(x: jax.Array, spec) -> jax.Array:
    """with_sharding_constraint if a spec is provided (else no-op)."""
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, scale: float | None = None, dtype=jnp.bfloat16):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, shape, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * lax.rsqrt(var + eps)) * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def layernorm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


def apply_norm(x, p, norm_type: str):
    if norm_type == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def norm_params(d: int, norm_type: str, dtype=jnp.float32):
    if norm_type == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and Qwen2-VL's M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0):
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,
    sections: tuple[int, int, int],
    theta: float = 10_000.0,
):
    """Qwen2-VL multimodal RoPE: positions (3, B, S) for (t, h, w).

    The head-dim frequency bands are split into three sections rotated by the
    temporal / height / width position respectively (text tokens carry
    t == h == w so M-RoPE degenerates to RoPE for them).
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    # build per-band position: section i uses positions[i]
    sec = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )  # (d/2,)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),  # (3, B, S)
        jnp.zeros((1,) + positions.shape[1:], jnp.int32),
        axis=0,
    )
    # gather per-band: angle[b,s,k] = positions[sec[k], b, s] * freqs[k]
    pos_bands = positions[sec, :, :]  # (d/2, B, S)
    angles = jnp.moveaxis(pos_bands, 0, -1).astype(jnp.float32) * freqs  # (B,S,d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


NEG_INF = -1e30


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, KV, D) -> (B, S, KV*groups, D) for GQA."""
    if groups == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, d)).reshape(
        b, s, kv * groups, d
    )


def attention_dense(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jax.Array:
    """Materialized-scores attention. q:(B,Sq,H,D), k/v:(B,Sk,KV,D)."""
    b, sq, h, d = q.shape
    kv = k.shape[2]
    k = _repeat_kv(k, h // kv)
    v = _repeat_kv(v, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    sk = k.shape[1]
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax (flash-style) attention in pure jnp.

    Queries go in blocks of ``chunk`` rows, and each block scans over the KV
    chunks it can see (causal: up to its last row; window: from its first
    row's window on), keeping running (max, sum, acc): memory O(chunk²) a
    step instead of O(Sq·Sk), and no work on a chunk a block cannot see.
    The backward recomputes each chunk's scores from the carries rather than
    keeping them.  v may be narrower than q and k (latent attention).  This
    is the CPU/compile-safe long-sequence path; the Pallas kernel
    (repro.kernels.flash_attention) is the TPU-target twin.
    """
    b, sq, h, d = q.shape
    dv = v.shape[-1]
    kv = k.shape[2]
    kvalid = k.shape[1]
    pad = (-kvalid) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    k = _repeat_kv(k, h // kv)
    v = _repeat_kv(v, h // kv)
    n_chunks = k.shape[1] // chunk
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, h, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, h, dv), 1, 0)
    qf = (q / math.sqrt(d)).astype(q.dtype)

    def rows(lo, hi):
        """Query rows [lo, hi) -> (B, hi - lo, H, dv) over the chunks they see."""
        qb, qpos = qf[:, lo:hi], jnp.arange(lo, hi)
        first = max(0, lo - window + 1) // chunk if window else 0
        last = -(-min(kvalid, hi) // chunk) if causal else n_chunks

        def body(carry, inp):
            m, s, acc = carry
            kb, vb, ci = inp
            scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32)
            kpos = ci * chunk + jnp.arange(chunk)
            mask = kpos[None, :] < kvalid
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > (qpos[:, None] - window)
            scores = jnp.where(mask[None, None], scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])
            s_new = s * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(q.dtype), vb
            ).astype(jnp.float32)
            return (m_new, s_new, acc_new), None

        n = hi - lo
        m0 = jnp.full((b, h, n), NEG_INF, jnp.float32)
        s0 = jnp.zeros((b, h, n), jnp.float32)
        a0 = jnp.zeros((b, h, n, dv), jnp.float32)
        (m, s, acc), _ = lax.scan(
            jax.checkpoint(body),
            (m0, s0, a0),
            (kc[first:last], vc[first:last], jnp.arange(first, last)),
            unroll=scan_unroll(last - first),
        )
        return jnp.moveaxis(acc / jnp.maximum(s, 1e-30)[..., None], 1, 2)

    out = [rows(lo, min(lo + chunk, sq)) for lo in range(0, sq, chunk)]
    return jnp.concatenate(out, axis=1).astype(q.dtype)  # (B,Sq,H,dv)


def attention_decode(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, Sk, KV, D)
    v_cache: jax.Array,
    length: jax.Array | int,  # valid cache length (scalar)
    window: int = 0,
) -> jax.Array:
    """Single-token decode against a KV cache."""
    b, _, h, d = q.shape
    kv = k_cache.shape[2]
    sk = k_cache.shape[1]
    k = _repeat_kv(k_cache, h // kv)
    v = _repeat_kv(v_cache, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q / math.sqrt(d), k).astype(jnp.float32)
    kpos = jnp.arange(sk)
    mask = kpos < length
    if window:
        mask &= kpos >= (length - window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(
    q, k, v, *, causal=True, window=0, chunk_threshold=2048, chunk=1024, use_kernel=False
):
    """Dispatch dense vs chunked attention by sequence length.

    Chunked (flash-style) is the default beyond 2k: materializing
    (B, H, S, S) scores at training shapes is the dominant memory term
    (e.g. llama3.2-3b train_4k: 100+ GB/device with dense scores).
    """
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.flash_attention(q, k, v, causal=causal, window=window)
    if k.shape[1] > chunk_threshold:
        return attention_chunked(q, k, v, causal=causal, window=window, chunk=chunk)
    return attention_dense(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w_gate)) * jnp.einsum(
        "bsd,df->bsf", x, w_up
    )
    return jnp.einsum("bsf,fd->bsd", h, w_down)


def gelu_mlp(x, w_up, b_up, w_down, b_down):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, w_up) + b_up)
    return jnp.einsum("bsf,fd->bsd", h, w_down) + b_down


# ---------------------------------------------------------------------------
# temporal conv (mamba2 / recurrentgemma)
# ---------------------------------------------------------------------------


def causal_conv1d(x: jax.Array, w: jax.Array, state: jax.Array | None = None):
    """Depthwise causal conv. x:(B,S,C), w:(W,C). Returns (y, new_state)."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(width))
    new_state = xp[:, -(width - 1) :] if width > 1 else pad
    return y.astype(x.dtype), new_state
