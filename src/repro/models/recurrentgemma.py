"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local attention
in a 2-recurrent : 1-attention repeating pattern [arXiv:2402.19427].

The RG-LRU linear recurrence is computed with ``lax.associative_scan`` for
training/prefill and as a single fused step for decode.  Decode state is
constant-size (LRU hidden + conv tail + a bounded local-attention window
cache), which makes the ``long_500k`` shape runnable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L

C_RGLRU = 8.0  # Griffin's fixed recurrence-sharpness constant


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16):
    d = cfg.d_model
    period = max(1, cfg.attention_period)
    n_blocks = cfg.n_layers // period
    tail = cfg.n_layers % period
    keys = jax.random.split(key, 8)
    params = {
        "embed": L.embed_init(keys[0], (cfg.vocab, d), dtype=dtype),
        "blocks": {
            "rec": _rec_params(keys[1], cfg, n_blocks * (period - 1), dtype),
            "attn": _attn_layer_params(keys[2], cfg, n_blocks, dtype),
        },
        "final_norm": L.norm_params(d, cfg.norm_type),
    }
    if tail:
        params["tail"] = _rec_params(keys[3], cfg, tail, dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(keys[4], (d, cfg.vocab), dtype=dtype)
    return params


def _rec_params(key, cfg: ArchConfig, n: int, dtype):
    """n stacked recurrent layers (temporal block + MLP block)."""
    d = cfg.d_model
    dr = d  # lru width = d_model
    ks = jax.random.split(key, 8)
    return {
        "norm": _stack_norm(cfg, n),
        "w_gate_in": L.dense_init(ks[0], (n, d, dr), dtype=dtype),
        "w_x_in": L.dense_init(ks[1], (n, d, dr), dtype=dtype),
        "conv_w": (jax.random.normal(ks[2], (n, cfg.conv_width, dr)) * 0.1).astype(dtype),
        "w_a": L.dense_init(ks[3], (n, dr, dr), dtype=dtype),
        "w_i": L.dense_init(ks[4], (n, dr, dr), dtype=dtype),
        "lambda_p": jnp.full((n, dr), 0.5, jnp.float32),  # recurrence gate param
        "w_out": L.dense_init(ks[5], (n, dr, d), dtype=dtype),
        "mlp_norm": _stack_norm(cfg, n),
        **_mlp(ks[6], cfg, n, dtype),
    }


def _attn_layer_params(key, cfg: ArchConfig, n: int, dtype):
    d, hd = cfg.d_model, cfg.kq_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    return {
        "norm": _stack_norm(cfg, n),
        "wq": L.dense_init(ks[0], (n, d, h * hd), dtype=dtype),
        "wk": L.dense_init(ks[1], (n, d, kv * hd), dtype=dtype),
        "wv": L.dense_init(ks[2], (n, d, kv * hd), dtype=dtype),
        "wo": L.dense_init(ks[3], (n, h * hd, d), dtype=dtype),
        "mlp_norm": _stack_norm(cfg, n),
        **_mlp(ks[4], cfg, n, dtype),
    }


def _mlp(key, cfg: ArchConfig, n: int, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": L.dense_init(ks[0], (n, d, f), dtype=dtype),
        "w_up": L.dense_init(ks[1], (n, d, f), dtype=dtype),
        "w_down": L.dense_init(ks[2], (n, f, d), dtype=dtype),
    }


def _stack_norm(cfg, n):
    base = L.norm_params(cfg.d_model, cfg.norm_type)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), base)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------


def rglru(x, lp, h0=None):
    """x: (B, S, Dr) conv output. Returns (y, final_state).

    a_t = exp(-c·softplus(Λ)·σ(W_a x_t));  gated input i_t = σ(W_i x_t)
    h_t = a_t ⊙ h_{t-1} + sqrt(1-a_t²) ⊙ (i_t ⊙ x_t)
    """
    r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, lp["w_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, lp["w_i"]).astype(jnp.float32))
    log_a = -C_RGLRU * jax.nn.softplus(lp["lambda_p"]) * r  # (B,S,Dr) ≤ 0
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * x.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    if h0 is not None:
        # fold initial state into the first step
        gated = gated.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))
        # note: h0 already includes its own decay chain
    a_sc, h = lax.associative_scan(combine, (a, gated), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rglru_step(x, lp, h0):
    """Single decode step: x (B, 1, Dr), h0 (B, Dr)."""
    r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, lp["w_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, lp["w_i"]).astype(jnp.float32))
    a = jnp.exp(-C_RGLRU * jax.nn.softplus(lp["lambda_p"]) * r)[:, 0]
    gated = (jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i[:, 0] * x[:, 0].astype(jnp.float32)))
    h = a * h0.astype(jnp.float32) + gated
    return h[:, None].astype(x.dtype), h


def _rec_layer(cfg, lp, x, conv_state=None, lru_state=None, single_step=False):
    a = L.apply_norm(x, lp["norm"], cfg.norm_type)
    gate = jax.nn.gelu(jnp.einsum("bsd,de->bse", a, lp["w_gate_in"]))
    xin = jnp.einsum("bsd,de->bse", a, lp["w_x_in"])
    conv, new_conv = L.causal_conv1d(xin, lp["conv_w"], conv_state)
    if single_step:
        y, new_lru = rglru_step(conv, lp, lru_state)
    else:
        y, new_lru = rglru(conv, lp, lru_state)
    h = x + jnp.einsum("bse,ed->bsd", y * gate, lp["w_out"])
    m = L.apply_norm(h, lp["mlp_norm"], cfg.norm_type)
    h = h + L.swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
    return h, new_conv, new_lru


def _attn_layer(cfg, lp, x, positions):
    a = L.apply_norm(x, lp["norm"], cfg.norm_type)
    b, s, d = a.shape
    hd = cfg.kq_head_dim
    h_, kv = cfg.n_heads, cfg.n_kv_heads
    q = jnp.einsum("bsd,dq->bsq", a, lp["wq"]).reshape(b, s, h_, hd)
    k = jnp.einsum("bsd,dq->bsq", a, lp["wk"]).reshape(b, s, kv, hd)
    v = jnp.einsum("bsd,dq->bsq", a, lp["wv"]).reshape(b, s, kv, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    o = L.attention(q, k, v, causal=True, window=cfg.local_window,
                    chunk_threshold=cfg.attn_chunk * 2, chunk=cfg.attn_chunk)
    h = x + jnp.einsum("bsq,qd->bsd", o.reshape(b, s, h_ * hd), lp["wo"])
    m = L.apply_norm(h, lp["mlp_norm"], cfg.norm_type)
    return h + L.swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(cfg: ArchConfig, params, tokens, remat: bool = True, act_specs=None, **_):
    act = (act_specs or {}).get("act")
    period = max(1, cfg.attention_period)
    n_blocks = cfg.n_layers // period
    x = L.constrain(params["embed"][tokens], act)
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
    )
    rec = params["blocks"]["rec"]
    # regroup rec params: (n_blocks*(period-1), ...) -> (n_blocks, period-1, ...)
    rec_g = jax.tree.map(
        lambda v: v.reshape((n_blocks, period - 1) + v.shape[1:]), rec
    )

    def block_fn(h, bp):
        rp, ap = bp
        for r in range(period - 1):
            lp = jax.tree.map(lambda v: v[r], rp)
            h, _, _ = _rec_layer(cfg, lp, h)
            h = L.constrain(h, act)
        return L.constrain(_attn_layer(cfg, ap, h, positions), act), None

    body = jax.checkpoint(block_fn) if remat else block_fn
    x, _ = lax.scan(body, x, (rec_g, params["blocks"]["attn"]),
                    unroll=L.scan_unroll(n_blocks))
    if "tail" in params:
        tail_n = jax.tree.leaves(params["tail"])[0].shape[0]
        for t in range(tail_n):
            lp = jax.tree.map(lambda v: v[t], params["tail"])
            x, _, _ = _rec_layer(cfg, lp, x)
    x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
    unembed = params.get("unembed", params["embed"].T)
    logits = jnp.einsum("bsd,dv->bsv", x, unembed)
    logits = L.constrain(logits, (act_specs or {}).get("logits"))
    return logits, {"balance": jnp.float32(0.0), "load": None}


# ---------------------------------------------------------------------------
# decode (constant-size state: LRU + conv + bounded attention window)
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    period = max(1, cfg.attention_period)
    n_blocks = cfg.n_layers // period
    n_rec = n_blocks * (period - 1) + cfg.n_layers % period
    dr = cfg.d_model
    hd = cfg.kq_head_dim
    win = min(cfg.local_window, max_len)
    return {
        "conv": jnp.zeros((n_rec, batch, cfg.conv_width - 1, dr), dtype),
        "lru": jnp.zeros((n_rec, batch, dr), jnp.float32),
        "k": jnp.zeros((n_blocks, batch, win, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((n_blocks, batch, win, cfg.n_kv_heads, hd), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def decode_step(cfg: ArchConfig, params, cache, tokens, positions=None, **_):
    period = max(1, cfg.attention_period)
    n_blocks = cfg.n_layers // period
    b = tokens.shape[0]
    hd = cfg.kq_head_dim
    h_, kv = cfg.n_heads, cfg.n_kv_heads
    win = cache["k"].shape[2]
    pos = cache["len"]
    positions = jnp.broadcast_to(pos.astype(jnp.int32), (b, 1))
    x = params["embed"][tokens]
    rec = params["blocks"]["rec"]
    rec_g = jax.tree.map(lambda v: v.reshape((n_blocks, period - 1) + v.shape[1:]), rec)
    conv_g = cache["conv"][: n_blocks * (period - 1)].reshape(
        (n_blocks, period - 1) + cache["conv"].shape[1:]
    )
    lru_g = cache["lru"][: n_blocks * (period - 1)].reshape(
        (n_blocks, period - 1) + cache["lru"].shape[1:]
    )
    slot = jnp.mod(pos, win)  # rolling window write position

    def block_fn(h, inp):
        rp, ap, conv_st, lru_st, kc, vc = inp
        new_conv, new_lru = [], []
        for r in range(period - 1):
            lp = jax.tree.map(lambda v: v[r], rp)
            h, nc, nl = _rec_layer(cfg, lp, h, conv_st[r], lru_st[r], single_step=True)
            new_conv.append(nc)
            new_lru.append(nl)
        # local attention with rolling cache
        a = L.apply_norm(h, ap["norm"], cfg.norm_type)
        q = jnp.einsum("bsd,dq->bsq", a, ap["wq"]).reshape(b, 1, h_, hd)
        k = jnp.einsum("bsd,dq->bsq", a, ap["wk"]).reshape(b, 1, kv, hd)
        v = jnp.einsum("bsd,dq->bsq", a, ap["wv"]).reshape(b, 1, kv, hd)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        kc = lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), slot, axis=1)
        vc = lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), slot, axis=1)
        length = jnp.minimum(pos + 1, win)
        o = L.attention_decode(q, kc, vc, length)
        h = h + jnp.einsum("bsq,qd->bsd", o.reshape(b, 1, h_ * hd), ap["wo"])
        m = L.apply_norm(h, ap["mlp_norm"], cfg.norm_type)
        h = h + L.swiglu(m, ap["w_gate"], ap["w_up"], ap["w_down"])
        return h, (jnp.stack(new_conv), jnp.stack(new_lru), kc, vc)

    x, (nconv, nlru, nk, nv) = lax.scan(
        block_fn, x,
        (rec_g, params["blocks"]["attn"], conv_g, lru_g, cache["k"], cache["v"]),
    )
    new_conv = nconv.reshape(cache["conv"][: n_blocks * (period - 1)].shape)
    new_lru = nlru.reshape(cache["lru"][: n_blocks * (period - 1)].shape)
    tail_conv = [new_conv]
    tail_lru = [new_lru]
    if "tail" in params:
        tail_n = jax.tree.leaves(params["tail"])[0].shape[0]
        base = n_blocks * (period - 1)
        tc, tl = [], []
        for t in range(tail_n):
            lp = jax.tree.map(lambda v: v[t], params["tail"])
            x, nc, nl = _rec_layer(cfg, lp, x, cache["conv"][base + t],
                                   cache["lru"][base + t], single_step=True)
            tc.append(nc)
            tl.append(nl)
        tail_conv.append(jnp.stack(tc))
        tail_lru.append(jnp.stack(tl))
    x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
    unembed = params.get("unembed", params["embed"].T)
    logits = jnp.einsum("bsd,dv->bsv", x, unembed)
    new_cache = dict(
        cache,
        conv=jnp.concatenate(tail_conv, axis=0),
        lru=jnp.concatenate(tail_lru, axis=0),
        k=nk, v=nv, len=pos + 1,
    )
    return logits, new_cache
