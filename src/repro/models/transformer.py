"""Decoder-only and encoder-decoder transformer LMs (dense / MoE / VLM / audio).

Covers the assigned families:
* dense GQA decoders (llama3.2, granite, internlm2, minicpm, qwen2-vl)
* MoE decoders (dbrx, moonshot) via :mod:`repro.models.moe`
* encoder-decoder with conv-frontend stub (whisper-tiny)

Layer stacks are parameterized for ``lax.scan`` (params carry a leading L
dim); remat policy is applied by the training layer.  ``decode_step`` scans
the layers with the stacked KV cache read in place, not carried, and writes
the step's new rows into it once, after the scan.

DeepSeek-V3-style MoE (moonshot) adds two things: multi-head latent
attention (``cfg.kv_lora_rank``), whose cache holds the normed latent and the
shared rotary key, and leading dense layers (``params["dense_layers"]``,
scanned before the sparse ``params["layers"]``).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.obs import device as OD


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def _attn_params(key, cfg: ArchConfig, n_layers: int, dtype):
    d, hd = cfg.d_model, cfg.kq_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], (n_layers, d, h * hd), dtype=dtype),
        "wk": L.dense_init(ks[1], (n_layers, d, kv * hd), dtype=dtype),
        "wv": L.dense_init(ks[2], (n_layers, d, kv * hd), dtype=dtype),
        "wo": L.dense_init(ks[3], (n_layers, h * hd, d), dtype=dtype),
    }


def _mlp_params(key, cfg: ArchConfig, n_layers: int, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "w_gate": L.dense_init(ks[0], (n_layers, d, f), dtype=dtype),
            "w_up": L.dense_init(ks[1], (n_layers, d, f), dtype=dtype),
            "w_down": L.dense_init(ks[2], (n_layers, f, d), dtype=dtype),
        }
    return {
        "w_up": L.dense_init(ks[0], (n_layers, d, f), dtype=dtype),
        "b_up": jnp.zeros((n_layers, f), dtype),
        "w_down": L.dense_init(ks[1], (n_layers, f, d), dtype=dtype),
        "b_down": jnp.zeros((n_layers, d), dtype),
    }


def _mla_params(key, cfg: ArchConfig, n_layers: int, dtype):
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], (n_layers, d, h * (nope + rope)), dtype=dtype),
        "wkv_a": L.dense_init(ks[1], (n_layers, d, r + rope), dtype=dtype),
        "kv_norm": jax.tree.map(lambda a: jnp.broadcast_to(a, (n_layers,) + a.shape),
                                L.norm_params(r, "rmsnorm")),
        "wkv_b": L.dense_init(ks[2], (n_layers, r, h * (nope + vd)), dtype=dtype),
        "wo": L.dense_init(ks[3], (n_layers, h * vd, d), dtype=dtype),
    }


def _moe_params(key, cfg: ArchConfig, n_layers: int, dtype):
    d, f, e, held = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.held_experts
    ks = jax.random.split(key, 7)
    p = {
        "router": L.dense_init(ks[0], (n_layers, d, e), dtype=jnp.float32),
        "w_gate": L.dense_init(ks[1], (n_layers, held, d, f), dtype=dtype),
        "w_up": L.dense_init(ks[2], (n_layers, held, d, f), dtype=dtype),
        "w_down": L.dense_init(ks[3], (n_layers, held, f, d), dtype=dtype),
    }
    if cfg.shared_d_ff:
        fs = cfg.shared_d_ff
        p["shared_gate"] = L.dense_init(ks[4], (n_layers, d, fs), dtype=dtype)
        p["shared_up"] = L.dense_init(ks[5], (n_layers, d, fs), dtype=dtype)
        p["shared_down"] = L.dense_init(ks[6], (n_layers, fs, d), dtype=dtype)
    return p


def _layer_params(k_attn, k_mlp, cfg: ArchConfig, n_layers: int, dtype, sparse: bool):
    attn = _mla_params if cfg.kv_lora_rank else _attn_params
    layer = {
        "attn_norm": _stack_norm(cfg, n_layers),
        "mlp_norm": _stack_norm(cfg, n_layers),
        **attn(k_attn, cfg, n_layers, dtype),
    }
    if sparse:
        layer["moe"] = _moe_params(k_mlp, cfg, n_layers, dtype)
    else:
        d_ff = cfg.dense_d_ff if cfg.family == "moe" else cfg.d_ff
        layer.update(_mlp_params(k_mlp, dataclasses.replace(cfg, d_ff=d_ff), n_layers,
                                 dtype))
    return layer


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16):
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    params = {
        "embed": L.embed_init(keys[2], (cfg.vocab, d), dtype=dtype),
        "layers": _layer_params(keys[0], keys[1], cfg, cfg.n_layers - cfg.n_dense_layers,
                                dtype, sparse=cfg.family == "moe"),
        "final_norm": L.norm_params(d, cfg.norm_type),
    }
    if cfg.n_dense_layers:
        params["dense_layers"] = _layer_params(
            *jax.random.split(jax.random.fold_in(key, 1)), cfg, cfg.n_dense_layers, dtype,
            sparse=False)
    if not cfg.tie_embeddings:
        v_out = _padded_vocab(cfg)
        params["unembed"] = L.dense_init(keys[3], (d, v_out), dtype=dtype)
    if cfg.rope_type == "learned":
        params["pos_embed"] = L.embed_init(keys[4], (cfg.max_pos, d), dtype=dtype)
    if cfg.enc_layers:
        params["encoder"] = {
            "layers": {
                "attn_norm": _stack_norm(cfg, cfg.enc_layers),
                "mlp_norm": _stack_norm(cfg, cfg.enc_layers),
                **_attn_params(keys[5], cfg, cfg.enc_layers, dtype),
                **_mlp_params(keys[6], cfg, cfg.enc_layers, dtype),
            },
            "final_norm": L.norm_params(d, cfg.norm_type),
            "pos_embed": L.embed_init(keys[7], (cfg.enc_seq, d), dtype=dtype),
        }
        params["layers"]["xattn_norm"] = _stack_norm(cfg, cfg.n_layers)
        params["layers"].update(
            {f"x{k}": v for k, v in _attn_params(keys[4], cfg, cfg.n_layers, dtype).items()}
        )
    return params


def _padded_vocab(cfg: ArchConfig) -> int:
    if not cfg.vocab_pad_to:
        return cfg.vocab
    p = cfg.vocab_pad_to
    return (cfg.vocab + p - 1) // p * p


def _stack_norm(cfg: ArchConfig, n: int):
    base = L.norm_params(cfg.d_model, cfg.norm_type)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), base)


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------


def _positions_default(tokens):
    b, s = tokens.shape[:2]
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


def _apply_pos(cfg, q, k, positions):
    if cfg.rope_type == "rope":
        return (
            L.apply_rope(q, positions, cfg.rope_theta),
            L.apply_rope(k, positions, cfg.rope_theta),
        )
    if cfg.rope_type == "mrope":
        return (
            L.apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta),
            L.apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta),
        )
    return q, k


def _norm(cfg: ArchConfig, x, p):
    with jax.named_scope(OD.NORM):
        return L.apply_norm(x, p, cfg.norm_type)


@jax.named_scope(OD.ATTENTION)
def _attn_block(cfg: ArchConfig, p, x, positions, causal, window, kv_seq=None,
                use_kernel=False):
    """p holds per-layer (unstacked) attention params."""
    b, s, d = x.shape
    hd = cfg.kq_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    src = x if kv_seq is None else kv_seq
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,dq->bsq", src, p["wk"]).reshape(b, src.shape[1], kv, hd)
    v = jnp.einsum("bsd,dq->bsq", src, p["wv"]).reshape(b, src.shape[1], kv, hd)
    if kv_seq is None and cfg.rope_type in ("rope", "mrope"):
        q, k = _apply_pos(cfg, q, k, positions)
    o = L.attention(
        q, k, v, causal=causal, window=window,
        chunk_threshold=cfg.attn_chunk * 2, chunk=cfg.attn_chunk,
        use_kernel=use_kernel,
    )
    return jnp.einsum("bsq,qd->bsd", o.reshape(b, s, h * hd), p["wo"])


def _mla_q_latent(cfg: ArchConfig, p, x, positions):
    """Latent attention's inputs: q (B, S, H, nope + rope), the normed latent
    c_kv (B, S, r) and the rotary key k_pe (B, S, 1, rope) that all heads
    share; rope on q's and k_pe's rotary dims."""
    b, s, _ = x.shape
    h, nope, rope, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(b, s, h, nope + rope)
    with jax.named_scope(OD.LATENT):
        kv_a = jnp.einsum("bsd,dq->bsq", x, p["wkv_a"])
        c_kv = L.rmsnorm(kv_a[..., :r], p["kv_norm"]["scale"])
    q_pe, k_pe = _apply_pos(cfg, q[..., nope:], kv_a[..., None, r:], positions)
    return jnp.concatenate([q[..., :nope], q_pe], axis=-1), c_kv, k_pe


def _mla_kv(cfg: ArchConfig, p, c_kv, k_pe):
    """Keys (B, S, H, nope + rope) and values (B, S, H, v) of every position,
    from the latent and the shared rotary key."""
    b, s, _ = c_kv.shape
    h, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope(OD.LATENT):
        kv = jnp.einsum("bsr,rq->bsq", c_kv, p["wkv_b"]).reshape(b, s, h, -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope))], axis=-1)
    return k, kv[..., nope:]


@jax.named_scope(OD.ATTENTION)
def _mla_block(cfg: ArchConfig, p, x, positions, causal, window, use_kernel=False):
    """Multi-head latent attention (DeepSeek-V2/V3, no q compression): q.k
    over nope + rope dims, scaled by 1/sqrt(nope + rope); values v wide."""
    b, s, _ = x.shape
    q, c_kv, k_pe = _mla_q_latent(cfg, p, x, positions)
    k, v = _mla_kv(cfg, p, c_kv, k_pe)
    o = L.attention(
        q, k, v, causal=causal, window=window,
        chunk_threshold=cfg.attn_chunk * 2, chunk=cfg.attn_chunk,
        use_kernel=use_kernel,
    )
    return jnp.einsum("bsq,qd->bsd", o.reshape(b, s, -1), p["wo"])


def _moe_ep(cfg: ArchConfig, mp, x, mesh):
    """Expert-parallel MoE: experts live on the ``model`` axis, token slabs
    move with lax.all_to_all — the paper's GPT-3-MoE traffic pattern (§V-B5).
    Wrapped in a partial-manual shard_map (manual over ``model`` only)."""
    from jax.sharding import PartitionSpec as P

    def f(x_l, w):
        return moe_lib.moe_apply_ep(
            x_l, w, cfg.top_k, cfg.capacity_factor, axis="model")

    w_specs = {
        "router": P(),
        "w_gate": P("model"), "w_up": P("model"), "w_down": P("model"),
    }
    return jax.shard_map(
        f, mesh=mesh, in_specs=(P(), w_specs), out_specs=(P(), P()),
        axis_names={"model"}, check_vma=False,
    )(x, mp)


@jax.named_scope(OD.MLP)
def _mlp_block(cfg: ArchConfig, p, x):
    if cfg.act == "swiglu":
        return L.swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    return L.gelu_mlp(x, p["w_up"], p["b_up"], p["w_down"], p["b_down"])


def init_buffers(params) -> dict:
    """The model's state that takes no gradient, made from its parameters:
    ``router_bias``, the sparse layers' correction biases (sparse layers,
    n_experts), zero at the start as DeepSeek-V3's are; {} without experts.
    Training keeps the buffers beside the moments
    (``optimizer.AdamWState.buffers``, checkpointed with them) and moves them
    with ``update_buffers``; ``forward`` and ``decode_step`` take them."""
    layers = params.get("layers")
    if not isinstance(layers, dict) or "moe" not in layers:
        return {}
    return {"router_bias": jnp.zeros(layers["moe"]["router"].shape[::2], jnp.float32)}


def update_buffers(cfg: ArchConfig, buffers: dict, aux: dict) -> dict:
    """After a training step: the sigmoid routers' correction biases move by
    the step's load (``moe.balance_bias``); other buffers stay as they are."""
    if cfg.router != "sigmoid" or not buffers:
        return buffers
    return dict(buffers, router_bias=moe_lib.balance_bias(
        buffers["router_bias"], aux["load"], cfg.bias_rate))


def _routed_layers(cfg: ArchConfig, layers, buffers):
    """The sparse layers' parameters with each layer's correction bias
    beside its router, for the scan (zeros where no buffers are given)."""
    if cfg.router != "sigmoid":
        return layers
    bias = (buffers or {}).get("router_bias")
    if bias is None:
        bias = init_buffers({"layers": layers})["router_bias"]
    return dict(layers, moe=dict(layers["moe"], bias=bias))


def forward(
    cfg: ArchConfig,
    params,
    tokens,
    positions=None,
    encoder_frames=None,
    remat: bool = True,
    use_kernel: bool = False,
    act_specs=None,
    return_hidden: bool = False,
    buffers=None,
) -> tuple[jax.Array, dict]:
    """Full forward pass -> (logits, aux).

    ``aux`` is ``{"balance": the balance loss, "load": (sparse layers,
    n_experts) pairs given to each expert, or None}``: for ``moe`` the sum
    of the sparse layers' losses, for the families without experts a 0.
    ``buffers``: ``init_buffers``'s state (the correction biases); zeros
    where not given.

    tokens: (B, S) int32 — or, for audio, decoder tokens with
    ``encoder_frames`` (B, enc_seq, D) from the (stubbed) conv frontend.
    For VLM (mrope) ``positions`` is (3, B, S).
    """
    if positions is None:
        positions = (
            _positions_default(tokens)
            if cfg.rope_type != "mrope"
            else jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32),
                (3, tokens.shape[0], tokens.shape[1]),
            )
        )
    act = (act_specs or {}).get("act")
    with jax.named_scope(OD.EMBED):
        x = L.constrain(params["embed"][tokens], act)
        if cfg.rope_type == "learned":
            x = x + params["pos_embed"][: x.shape[1]][None]

    enc_out = None
    if cfg.enc_layers:
        assert encoder_frames is not None, "audio family needs encoder frames"
        enc_out = _encoder_forward(cfg, params["encoder"], encoder_frames, remat)
    attn_block = _mla_block if cfg.kv_lora_rank else _attn_block

    def layer_fn(sparse, carry, lp):
        h, aux = carry
        h = h + attn_block(cfg, lp, _norm(cfg, h, lp["attn_norm"]), positions,
                           causal=True, window=0, use_kernel=use_kernel)
        if enc_out is not None:
            xa = _norm(cfg, h, lp["xattn_norm"])
            xp = {k[1:]: v for k, v in lp.items() if k.startswith("x") and k != "xattn_norm"}
            h = h + _attn_block(cfg, xp, xa, positions, causal=False, window=0,
                                kv_seq=enc_out)
        m = _norm(cfg, h, lp["mlp_norm"])
        if not sparse:
            return (L.constrain(h + _mlp_block(cfg, lp, m), act), aux), None
        with jax.named_scope(OD.MLP):
            y, a_loss, load = _moe_block(cfg, lp["moe"], m, act_specs)
        return (L.constrain(h + y, act), aux + a_loss), load

    def scan(carry, layers, sparse):
        body = partial(layer_fn, sparse)
        body = jax.checkpoint(body) if remat else body
        n = jax.tree.leaves(layers)[0].shape[0]
        return lax.scan(body, carry, layers, unroll=L.scan_unroll(n))

    carry = (x, jnp.float32(0.0))
    with jax.named_scope(OD.LAYERS):
        if cfg.n_dense_layers:
            carry, _ = scan(carry, params["dense_layers"], False)
        (x, balance), load = scan(carry, _routed_layers(cfg, params["layers"], buffers),
                                  cfg.family == "moe")

    with jax.named_scope(OD.UNEMBED):
        x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
        if return_hidden:
            return x, _aux(cfg, balance, load)
        unembed = params.get("unembed", params["embed"].T)
        logits = jnp.einsum("bsd,dv->bsv", x, unembed)
        if logits.shape[-1] != cfg.vocab:  # TP-padded vocab: mask the tail
            keep = jnp.arange(logits.shape[-1]) < cfg.vocab
            logits = jnp.where(keep, logits, jnp.asarray(-1e30, logits.dtype))
        logits = L.constrain(logits, (act_specs or {}).get("logits"))
    return logits, _aux(cfg, balance, load)


def _aux(cfg: ArchConfig, balance, load) -> dict:
    """``forward``'s aux: the balance loss summed over the sparse layers (for
    the families without experts the mean over layers of nothing, 0) and
    each expert's load per sparse layer (None without experts)."""
    return {"balance": balance if cfg.family == "moe" else balance / cfg.n_layers,
            "load": load}


def _moe_block(cfg: ArchConfig, mp, m, act_specs):
    """The expert layer of one sparse layer -> (y, balance loss, load)."""
    if cfg.moe_mode == "tp":
        return moe_lib.moe_block(cfg, mp, m, mp.get("bias"))
    if cfg.router != "softmax" or cfg.shared_d_ff:
        raise ValueError(f"moe_mode {cfg.moe_mode!r} takes the softmax router alone")
    if cfg.moe_mode == "ep":
        y, a_loss = _moe_ep(cfg, mp, m, (act_specs or {}).get("mesh"))
    else:
        y, a_loss = moe_lib.moe_apply_gshard(
            m, mp, cfg.top_k, cfg.capacity_factor,
            expert_spec=(act_specs or {}).get("experts"))
    return y, a_loss, None


def _encoder_forward(cfg: ArchConfig, enc, frames, remat):
    x = frames.astype(enc["pos_embed"].dtype) + enc["pos_embed"][: frames.shape[1]][None]
    pos = _positions_default(frames[..., 0].astype(jnp.int32))

    def layer_fn(h, lp):
        h = h + _attn_block(cfg, lp, _norm(cfg, h, lp["attn_norm"]), pos, causal=False,
                            window=0)
        return h + _mlp_block(cfg, lp, _norm(cfg, h, lp["mlp_norm"])), None

    body = jax.checkpoint(layer_fn) if remat else layer_fn
    with jax.named_scope(OD.LAYERS):
        x, _ = lax.scan(body, x, enc["layers"], unroll=L.scan_unroll(cfg.enc_layers))
    return L.apply_norm(x, enc["final_norm"], cfg.norm_type)


# ---------------------------------------------------------------------------
# KV-cache serving path
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    hd = cfg.kq_head_dim
    if cfg.kv_lora_rank:
        lat = (cfg.n_layers, batch, max_len)
        return {"c_kv": jnp.zeros(lat + (cfg.kv_lora_rank,), dtype),
                "k_pe": jnp.zeros(lat + (cfg.qk_rope_head_dim,), dtype),
                "len": jnp.zeros((), jnp.int32)}
    cache = {
        "k": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd), dtype),
        "len": jnp.zeros((), jnp.int32),
    }
    if cfg.enc_layers:
        cache["xk"] = jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, cfg.n_kv_heads, hd), dtype)
        cache["xv"] = jnp.zeros_like(cache["xk"])
    return cache


def decode_step(cfg: ArchConfig, params, cache, tokens, positions=None, buffers=None):
    """One-token decode: tokens (B, 1) -> (logits (B,1,V), new_cache).

    The stacked cache is read inside the layer scan but never carried
    through it or scanned over: each layer's attention reads its layer of
    ``cache["k"]``/``cache["v"]`` straight from the stacked buffer, with the
    layer's new row selected in at position ``len``, and the scan returns the
    new rows, which one ``dynamic_update_slice`` at ``(0, 0, len, 0, 0)``
    writes after it.  Under donation (``launch/serve.make_step``) that write
    is in place, so a step reads the cache once and writes only the new rows
    (though with a positions-minor cache layout, which the TPU compiler picks
    for 64-wide heads, writing one position touches every tile).  Carrying the cache through the scan, or scanning over it as ``xs``, makes
    the compiler copy layers or the whole cache between layouts every step.

    Latent attention caches the normed latent and the rotary key
    (``c_kv``, ``k_pe``) and expands every position's keys and values from
    them each step.  ``buffers`` as for ``forward``: a trained model decodes
    with the correction biases it was trained with.
    """
    b = tokens.shape[0]
    hd = cfg.kq_head_dim
    h_, kv = cfg.n_heads, cfg.n_kv_heads
    pos_scalar = cache["len"]
    if positions is None:
        if cfg.rope_type == "mrope":
            positions = jnp.broadcast_to(pos_scalar.astype(jnp.int32), (3, b, 1))
        else:
            positions = jnp.broadcast_to(pos_scalar.astype(jnp.int32), (b, 1))
    with jax.named_scope(OD.EMBED):
        x = params["embed"][tokens]
        if cfg.rope_type == "learned":
            x = x + lax.dynamic_slice_in_dim(params["pos_embed"], pos_scalar, 1)[None]

    kn, vn = ("c_kv", "k_pe") if cfg.kv_lora_rank else ("k", "v")
    kc, vc = cache[kn], cache[vn]
    at_len = (jnp.arange(kc.shape[2]) == pos_scalar)[None, :, None, None]
    window = cfg.local_window if cfg.family == "vlm" else 0

    def mla(lp, a, li):
        q, c, k_pe = _mla_q_latent(cfg, lp, a, positions)
        c, k_pe = c.astype(kc.dtype), k_pe[:, :, 0].astype(vc.dtype)
        lc = jnp.where(at_len[..., 0], c, lax.dynamic_index_in_dim(kc, li, keepdims=False))
        lk = jnp.where(at_len[..., 0], k_pe, lax.dynamic_index_in_dim(vc, li, keepdims=False))
        k, v = _mla_kv(cfg, lp, lc, lk[:, :, None, :])
        o = L.attention_decode(q, k, v, pos_scalar + 1, window=window)
        return jnp.einsum("bsq,qd->bsd", o.reshape(b, 1, -1), lp["wo"]), (c, k_pe)

    def layer_fn(sparse, h, lp_and_index):
        lp, li = lp_and_index
        a = _norm(cfg, h, lp["attn_norm"])
        with jax.named_scope(OD.ATTENTION):
            if cfg.kv_lora_rank:
                attn, (k, v) = mla(lp, a, li)
            else:
                q = jnp.einsum("bsd,dq->bsq", a, lp["wq"]).reshape(b, 1, h_, hd)
                k = jnp.einsum("bsd,dq->bsq", a, lp["wk"]).reshape(b, 1, kv, hd)
                v = jnp.einsum("bsd,dq->bsq", a, lp["wv"]).reshape(b, 1, kv, hd)
                if cfg.rope_type in ("rope", "mrope"):
                    q, k = _apply_pos(cfg, q, k, positions)
                k, v = k.astype(kc.dtype), v.astype(vc.dtype)
                lk = jnp.where(at_len, k, lax.dynamic_index_in_dim(kc, li, keepdims=False))
                lv = jnp.where(at_len, v, lax.dynamic_index_in_dim(vc, li, keepdims=False))
                o = L.attention_decode(q, lk, lv, pos_scalar + 1, window=window)
                attn = jnp.einsum("bsq,qd->bsd", o.reshape(b, 1, h_ * hd), lp["wo"])
        h = h + attn
        if cfg.enc_layers:
            xa = _norm(cfg, h, lp["xattn_norm"])
            with jax.named_scope(OD.ATTENTION):
                xk = lax.dynamic_index_in_dim(cache["xk"], li, keepdims=False)
                xv = lax.dynamic_index_in_dim(cache["xv"], li, keepdims=False)
                qx = jnp.einsum("bsd,dq->bsq", xa, lp["xwq"]).reshape(b, 1, h_, hd)
                o = L.attention_decode(qx, xk, xv, xk.shape[1])
                attn = jnp.einsum("bsq,qd->bsd", o.reshape(b, 1, h_ * hd), lp["xwo"])
            h = h + attn
        m = _norm(cfg, h, lp["mlp_norm"])
        if sparse:
            with jax.named_scope(OD.MLP):
                y, _, _ = moe_lib.moe_block(cfg, lp["moe"], m, lp["moe"].get("bias"))
        else:
            y = _mlp_block(cfg, lp, m)
        return h + y, (k, v)

    n_dense = cfg.n_dense_layers
    with jax.named_scope(OD.LAYERS):
        if n_dense:
            x, dense_rows = lax.scan(partial(layer_fn, False), x,
                                     (params["dense_layers"], jnp.arange(n_dense)))
        x, (k_rows, v_rows) = lax.scan(
            partial(layer_fn, cfg.family == "moe"), x,
            (_routed_layers(cfg, params["layers"], buffers),
             jnp.arange(n_dense, cfg.n_layers)))
        if n_dense:
            k_rows = jnp.concatenate([dense_rows[0], k_rows])
            v_rows = jnp.concatenate([dense_rows[1], v_rows])
        with jax.named_scope(OD.ATTENTION):
            at = (0, 0, pos_scalar) + (0,) * (kc.ndim - 3)
            kc = lax.dynamic_update_slice(kc, k_rows, at)
            vc = lax.dynamic_update_slice(vc, v_rows, at)
    with jax.named_scope(OD.UNEMBED):
        x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
        unembed = params.get("unembed", params["embed"].T)
        logits = jnp.einsum("bsd,dv->bsv", x, unembed)
    new_cache = dict(cache, **{kn: kc, vn: vc}, len=pos_scalar + 1)
    return logits, new_cache
