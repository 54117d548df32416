"""A plain dense decoder in float32 ``jax.numpy``: forward, loss, gradients
and AdamW, written from the published description (Llama-style: RMSNorm,
rotary positions on both halves of each head, causal grouped-query
attention, SwiGLU, untied or tied unembedding).  It imports nothing of the
program.  Its weights come from :mod:`chipbench.reference.weights`, by the
leaf names of the published layout, rounded to the dtype the cell runs in.

Matrix products run at ``precision`` (``highest`` for the reference, so that
a float32 product is a float32 product on a TPU).  Attention runs one
sequence at a time and the unembedding in blocks of rows, so that the
published widths fit one chip.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import weights as W

LAYER_LEAVES = {  # name -> shape without the layer axis, from the dims
    "layers/attn_norm/scale": lambda d: (d.d,),
    "layers/mlp_norm/scale": lambda d: (d.d,),
    "layers/wq": lambda d: (d.d, d.h * d.hd),
    "layers/wk": lambda d: (d.d, d.kv * d.hd),
    "layers/wv": lambda d: (d.d, d.kv * d.hd),
    "layers/wo": lambda d: (d.h * d.hd, d.d),
    "layers/w_gate": lambda d: (d.d, d.f),
    "layers/w_up": lambda d: (d.d, d.f),
    "layers/w_down": lambda d: (d.f, d.d),
}
CE_BLOCK = 512  # rows of logits made at once


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d: int
    h: int
    kv: int
    hd: int
    f: int
    vocab: int
    theta: float
    eps: float
    tied: bool

    @classmethod
    def of(cls, config: dict) -> "Dims":
        """The dims of a configuration file, with the values the program
        runs in place of published ones (its ``as_run`` group)."""
        config = {**config, **config.get("as_run", {})}
        return cls(config["num_hidden_layers"], config["hidden_size"],
                   config["num_attention_heads"], config["num_key_value_heads"],
                   config["head_dim"], config["intermediate_size"],
                   config["vocab_size"], float(config["rope_theta"]),
                   float(config["rms_norm_eps"]), bool(config["tie_word_embeddings"]))


def top_shapes(dims: Dims) -> dict:
    shapes = {"embed": (dims.vocab, dims.d), "final_norm/scale": (dims.d,)}
    if not dims.tied:
        shapes["unembed"] = (dims.d, dims.vocab)
    return shapes


def param_shapes(dims: Dims) -> dict:
    """Every leaf of the published layout, with stacked layers."""
    return {**top_shapes(dims),
            **{n: (dims.n_layers,) + s(dims) for n, s in LAYER_LEAVES.items()}}


def _as_run(x, dtype):
    return x.astype(dtype).astype(jnp.float32)


def make_leaf(key, dims: Dims, name: str, dtype, layer=None) -> jax.Array:
    """The float32 value of leaf ``name`` as the cell's ``dtype`` holds it;
    one layer of a stacked leaf where ``layer`` is given."""
    if layer is not None:
        shape = LAYER_LEAVES[name](dims)
        return _as_run(W.layer_leaf(key, name, layer, shape,
                                    W.leaf_std(name, shape, dims.tied), jnp.float32), dtype)
    shape = param_shapes(dims)[name]
    return _as_run(W.whole_leaf(key, name, shape, W.leaf_std(name, shape, dims.tied),
                                jnp.float32), dtype)


def make_params(key, dims: Dims, dtype) -> dict:
    return {n: make_leaf(key, dims, n, dtype) for n in param_shapes(dims)}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def rope(x, pos, theta):
    """x (B, S, H, D); the first and second halves of D form the pairs."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, 2 * half, 2, dtype=jnp.float32) / (2 * half))
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend_one(q, k, v):
    """One sequence: q (S, KV, G, D), k/v (S, KV, D) -> (S, KV, G, D)."""
    s = q.shape[0]
    scores = jnp.einsum("qkgd,skd->kgqs", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v)


def layer(dims: Dims, p: dict, x, pos, dtype=jnp.float32):
    """One decoder layer; ``p`` holds the layer's leaves by name."""
    b, s, _ = x.shape
    a = rmsnorm(x, p["layers/attn_norm/scale"], dims.eps).astype(dtype)
    q = (a @ p["layers/wq"].astype(dtype)).reshape(b, s, dims.h, dims.hd)
    k = (a @ p["layers/wk"].astype(dtype)).reshape(b, s, dims.kv, dims.hd)
    v = (a @ p["layers/wv"].astype(dtype)).reshape(b, s, dims.kv, dims.hd)
    q, k = rope(q, pos, dims.theta).astype(dtype), rope(k, pos, dims.theta).astype(dtype)
    q = q.reshape(b, s, dims.kv, dims.h // dims.kv, dims.hd)
    o = lax.map(jax.checkpoint(lambda t: _attend_one(*t)), (q, k, v))
    x = x + (o.reshape(b, s, dims.h * dims.hd).astype(dtype)
             @ p["layers/wo"].astype(dtype)).astype(x.dtype)
    m = rmsnorm(x, p["layers/mlp_norm/scale"], dims.eps).astype(dtype)
    hdn = jax.nn.silu(m @ p["layers/w_gate"].astype(dtype)) * (m @ p["layers/w_up"].astype(dtype))
    return x + (hdn @ p["layers/w_down"].astype(dtype)).astype(x.dtype)


def unembedding(params: dict):
    return params["unembed"] if "unembed" in params else params["embed"].T


def hidden(dims: Dims, params: dict, tokens, dtype=jnp.float32):
    """Final-normed hidden states (B, S, d) of the whole stacked model."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[1])
    for li in range(dims.n_layers):
        p = {n: params[n][li] for n in LAYER_LEAVES}
        x = jax.checkpoint(lambda p, x: layer(dims, p, x, pos, dtype))(p, x)
    return rmsnorm(x, params["final_norm/scale"], dims.eps)


def _row_blocks(x, *ys):
    n = x.shape[0]
    blk = math.gcd(n, CE_BLOCK)
    return (x.reshape(n // blk, blk, -1),) + tuple(y.reshape(n // blk, blk) for y in ys)


def loss(dims: Dims, params: dict, tokens, labels, dtype=jnp.float32):
    """Mean next-token cross-entropy over all rows."""
    h = hidden(dims, params, tokens, dtype)
    w = unembedding(params).astype(dtype)

    def block(t):
        hb, lb = t
        logits = (hb.astype(dtype) @ w).astype(jnp.float32)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0])

    hs, ls = _row_blocks(h.reshape(-1, dims.d), labels.reshape(-1))
    return jnp.sum(lax.map(jax.checkpoint(block), (hs, ls))) / labels.size


# ---------------------------------------------------------------------------
# AdamW, as the training configuration states it
# ---------------------------------------------------------------------------


def lr_at(job: dict, step: int) -> float:
    """Learning rate of optimizer step ``step`` (1-based): linear warm-up over
    a tenth of the horizon, then cosine or warmup-stable-decay (flat for
    ``wsd_stable_frac`` of the rest, then linear to a tenth)."""
    lr, horizon = job["lr"], job["horizon_steps"]
    warmup = max(1, horizon // 10)
    warm = min(1.0, step / warmup)
    frac = min(1.0, max(0.0, step - warmup) / max(1.0, horizon - warmup))
    if job["lr_schedule"] == "cosine":
        return lr * warm * 0.5 * (1.0 + math.cos(math.pi * frac))
    stable = job["wsd_stable_frac"]
    decay = min(1.0, max(0.0, (frac - stable) / max(1e-6, 1.0 - stable)))
    return lr * warm * (1.0 - 0.9 * decay)


def adamw(job: dict, lr, t, params, grads, m, v, param_dtype=jnp.float32):
    """One AdamW step at learning rate ``lr``, optimizer step ``t`` (1-based),
    with clipping by the global norm and decoupled weight decay on every leaf
    -> (params, m, v, clipped grads)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, job["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2, eps, wd = job["b1"], job["b2"], job["eps"], job["weight_decay"]
    new_p, new_m, new_v, clipped = {}, {}, {}, {}
    for n, g in grads.items():
        g = g * scale
        clipped[n] = g
        new_m[n] = b1 * m[n] + (1 - b1) * g
        new_v[n] = b2 * v[n] + (1 - b2) * g * g
        mh = new_m[n] / (1 - b1 ** t)
        vh = new_v[n] / (1 - b2 ** t)
        p = params[n].astype(jnp.float32)
        new_p[n] = (p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)).astype(param_dtype)
    return new_p, new_m, new_v, clipped
