"""A plain DeepSeek-V3-style decoder in float32 ``jax.numpy``: its forward
pass, loss, gradients, AdamW and the routers' bias update, written from the
published description (DeepSeek-V3, arXiv:2412.19437; Moonlight's
config.json).  It imports nothing of the program.  Its weights come from
:mod:`chipbench.reference.weights`, by the leaf names of the program's tree.

* Multi-head latent attention with no q compression: q = x W_q gives each
  head ``nope`` + ``rope`` dims; x W_kv_a gives the latent c_kv (RMS-normed)
  and one rotary key k_pe that all heads share; c_kv W_kv_b gives each
  head's k_nope and v.  Rotary positions turn q's and k_pe's rope dims (the
  first and second halves form the pairs); the softmax scale is
  1/sqrt(nope + rope).
* The leading dense layers have a SwiGLU MLP.
* A sparse layer's router scores s = sigmoid(x W_r) over all its experts;
  the top k of s + b (the correction bias) are chosen; the gates are the
  chosen s over their sum, times the routed scale.  The routed experts held
  here (``expert_first`` and the ``n_routed_experts`` after) are computed
  for every token and weighted by its gate for each, which is zero where the
  token did not choose it: a pair that chose an expert held elsewhere adds
  nothing.  The shared experts (one SwiGLU MLP) are added for every token.
* The loss adds alpha x the sequence-wise balance loss of every sparse layer:
  per sequence, sum_i f_i P_i, with f_i = E / (k S) x the pairs that chose
  expert i, and P_i the sequence's mean of s_i / sum_j s_j.
* After each step every bias moves by gamma x sign(mean load - load_i), the
  load being the pairs that chose each expert in the step.

Matrix products run at ``precision``.  Attention runs one sequence at a time,
in blocks of query rows under ``jax.checkpoint``, and the unembedding in
blocks of rows, so that three AdamW steps at the published widths fit one
chip.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import common
from chipbench.reference import decoder as D
from chipbench.reference import weights as W

ATTN_BLOCK = 1024  # query rows of one attention block


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    n_dense: int
    d: int
    h: int
    nope: int
    rope: int
    vd: int
    r: int
    f_dense: int
    f: int
    f_shared: int
    experts: int  # the router's width
    held: int
    first: int
    top_k: int
    scale: float
    alpha: float
    gamma: float
    vocab: int
    theta: float
    eps: float
    tied: bool

    @classmethod
    def of(cls, config: dict) -> "Dims":
        """The dims of a configuration file, with the values the program runs
        in place of published ones (its ``as_run`` group)."""
        c = {**config, **config.get("as_run", {})}
        return cls(c["num_hidden_layers"], c["first_k_dense_replace"], c["hidden_size"],
                   c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                   c["v_head_dim"], c["kv_lora_rank"], c["intermediate_size"],
                   c["moe_intermediate_size"], c["n_shared_experts"] * c["moe_intermediate_size"],
                   c["published"]["n_routed_experts"], c["n_routed_experts"],
                   c["program"]["expert_first"], c["num_experts_per_tok"],
                   float(c["routed_scaling_factor"]), float(c["aux_loss_alpha"]),
                   float(c["bias_update_speed"]), c["vocab_size"], float(c["rope_theta"]),
                   float(c["rms_norm_eps"]), bool(c["tie_word_embeddings"]))


def layer_shapes(dims: Dims, sparse: bool) -> dict:
    """One layer's leaves by the name under its stack, without the layer axis."""
    d, h = dims.d, dims.h
    shapes = {"attn_norm/scale": (d,), "mlp_norm/scale": (d,),
              "wq": (d, h * (dims.nope + dims.rope)), "wkv_a": (d, dims.r + dims.rope),
              "kv_norm/scale": (dims.r,), "wkv_b": (dims.r, h * (dims.nope + dims.vd)),
              "wo": (h * dims.vd, d)}
    if not sparse:
        return {**shapes, "w_gate": (d, dims.f_dense), "w_up": (d, dims.f_dense),
                "w_down": (dims.f_dense, d)}
    return {**shapes, "moe/router": (d, dims.experts),
            "moe/w_gate": (dims.held, d, dims.f), "moe/w_up": (dims.held, d, dims.f),
            "moe/w_down": (dims.held, dims.f, d), "moe/shared_gate": (d, dims.f_shared),
            "moe/shared_up": (d, dims.f_shared), "moe/shared_down": (dims.f_shared, d)}


def param_shapes(dims: Dims) -> dict:
    """Every leaf of the program's tree: ``dense_layers/...`` stacked over the
    leading dense layers, ``layers/...`` over the sparse ones."""
    shapes = {"embed": (dims.vocab, dims.d), "final_norm/scale": (dims.d,)}
    if not dims.tied:
        shapes["unembed"] = (dims.d, dims.vocab)
    n_sparse = dims.n_layers - dims.n_dense
    for stack, n, sparse in (("dense_layers", dims.n_dense, False), ("layers", n_sparse, True)):
        if n:
            shapes.update({f"{stack}/{k}": (n,) + s for k, s in layer_shapes(dims, sparse).items()})
    return shapes


def make_leaf(key, dims: Dims, name: str, dtype) -> jax.Array:
    """The float32 value of leaf ``name`` as the cell's ``dtype`` holds it."""
    shape = param_shapes(dims)[name]
    x = W.whole_leaf(key, name, shape, W.leaf_std(name, shape, dims.tied), jnp.float32)
    return x.astype(dtype).astype(jnp.float32)


def make_params(key, dims: Dims, dtype) -> dict:
    return {n: make_leaf(key, dims, n, dtype) for n in param_shapes(dims)}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _attend_one(q, k, v, pos):
    """One sequence, all heads, in blocks of query rows: q, k (S, H, nope +
    rope), v (S, H, vd) -> (S, H, vd)."""
    s, h, dq = q.shape
    blk = math.gcd(s, ATTN_BLOCK)

    def block(t):
        start, qb = t
        scores = jnp.einsum("qhd,shd->hqs", qb, k) / math.sqrt(dq)
        causal = pos[None, :] <= (start + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, v)

    out = lax.map(jax.checkpoint(block),
                  (jnp.arange(0, s, blk), q.reshape(s // blk, blk, h, dq)))
    return out.reshape(s, h, -1)


def attention(dims: Dims, p: dict, x, pos, dtype):
    """Latent attention of one layer; ``p`` holds the layer's leaves."""
    b, s, _ = x.shape
    h, nope, r = dims.h, dims.nope, dims.r
    q = (x @ p["wq"].astype(dtype)).reshape(b, s, h, nope + dims.rope)
    kv_a = x @ p["wkv_a"].astype(dtype)
    c_kv = D.rmsnorm(kv_a[..., :r].astype(jnp.float32), p["kv_norm/scale"], dims.eps)
    k_pe = D.rope(kv_a[..., None, r:].astype(jnp.float32), pos, dims.theta)
    q = jnp.concatenate(
        [q[..., :nope], D.rope(q[..., nope:].astype(jnp.float32), pos, dims.theta).astype(dtype)],
        axis=-1)
    kv = (c_kv.astype(dtype) @ p["wkv_b"].astype(dtype)).reshape(b, s, h, nope + dims.vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe.astype(dtype), (b, s, h, dims.rope))], axis=-1)
    o = lax.map(lambda t: _attend_one(*t, pos), (q, k, kv[..., nope:]))
    return o.reshape(b, s, h * dims.vd) @ p["wo"].astype(dtype)


def swiglu(x, w_gate, w_up, w_down, dtype):
    return (jax.nn.silu(x @ w_gate.astype(dtype)) * (x @ w_up.astype(dtype))) @ w_down.astype(dtype)


def experts(dims: Dims, p: dict, x, bias, dtype):
    """One sparse layer's expert part: x (B, S, d) -> (y, balance loss,
    load (experts,))."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    scores = jax.nn.sigmoid((xt @ p["moe/router"].astype(dtype)).astype(jnp.float32))
    _, chosen = lax.top_k(scores + bias, dims.top_k)
    g = jnp.take_along_axis(scores, chosen, axis=-1)
    g = g / (g.sum(-1, keepdims=True) + 1e-20) * dims.scale
    picked = jax.nn.one_hot(chosen, dims.experts)  # (T, k, E)
    gate = jnp.einsum("tke,tk->te", picked, g)  # each expert's gate, 0 where not chosen
    y = swiglu(xt, p["moe/shared_gate"], p["moe/shared_up"], p["moe/shared_down"], dtype)
    for j in range(dims.held):
        def expert(xt, j=j):
            return swiglu(xt, p["moe/w_gate"][j], p["moe/w_up"][j], p["moe/w_down"][j], dtype)
        y = y + gate[:, dims.first + j, None].astype(dtype) * jax.checkpoint(expert)(xt)
    chose = picked.sum(1).reshape(b, s, dims.experts)
    share = (scores / scores.sum(-1, keepdims=True)).reshape(b, s, dims.experts)
    f = chose.sum(1) * dims.experts / (dims.top_k * s)
    balance = jnp.mean(jnp.sum(f * share.mean(1), axis=-1))
    return y.reshape(b, s, d), balance, chose.sum((0, 1))


def layer(dims: Dims, p: dict, x, pos, bias, dtype):
    """One layer; ``p`` holds its leaves by the name under its stack, ``bias``
    is None for a dense layer -> (x, balance loss, load)."""
    a = D.rmsnorm(x, p["attn_norm/scale"], dims.eps).astype(dtype)
    x = x + attention(dims, p, a, pos, dtype).astype(x.dtype)
    m = D.rmsnorm(x, p["mlp_norm/scale"], dims.eps).astype(dtype)
    if bias is None:
        return x + swiglu(m, p["w_gate"], p["w_up"], p["w_down"], dtype).astype(x.dtype), 0.0, None
    y, balance, load = experts(dims, p, m, bias, dtype)
    return x + y.astype(x.dtype), balance, load


def hidden(dims: Dims, params: dict, tokens, bias, dtype=jnp.float32):
    """Final-normed hidden states (B, S, d), the balance losses' sum and the
    loads (sparse layers, experts).  ``bias``: (sparse layers, experts)."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[1])
    balance, loads = 0.0, []
    stacks = (("dense_layers", dims.n_dense), ("layers", dims.n_layers - dims.n_dense))
    for stack, n in stacks:
        names = [k for k in params if k.startswith(stack + "/")]
        for li in range(n):
            p = {k[len(stack) + 1:]: params[k][li] for k in names}
            b = None if stack == "dense_layers" else lax.stop_gradient(bias[li])
            x, bal, load = jax.checkpoint(
                lambda p, x, b: layer(dims, p, x, pos, b, dtype))(p, x, b)
            balance = balance + bal
            if load is not None:
                loads.append(load)
    return D.rmsnorm(x, params["final_norm/scale"], dims.eps), balance, jnp.stack(loads)


def loss(dims: Dims, params: dict, tokens, labels, bias, dtype=jnp.float32):
    """-> (cross-entropy + alpha x balance, (cross-entropy, loads))."""
    h, balance, loads = hidden(dims, params, tokens, bias, dtype)
    w = D.unembedding(params).astype(dtype)

    def block(t):
        hb, lb = t
        logits = (hb.astype(dtype) @ w).astype(jnp.float32)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0])

    hs, ls = D._row_blocks(h.reshape(-1, dims.d), labels.reshape(-1))
    ce = jnp.sum(lax.map(jax.checkpoint(block), (hs, ls))) / labels.size
    return ce + dims.alpha * balance, (ce, loads)


def logits(dims: Dims, params: dict, tokens, bias=None, dtype=jnp.float32):
    """Every position's logits (B, S, vocab), products in ``dtype``."""
    if bias is None:
        bias = jnp.zeros((dims.n_layers - dims.n_dense, dims.experts), jnp.float32)
    h, _, _ = hidden(dims, params, tokens, bias, dtype)
    return (h.astype(dtype) @ D.unembedding(params).astype(dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# training steps
# ---------------------------------------------------------------------------


def _first(x, share):
    """The first ``share`` of a batch's tokens (B, S): whole rows where they
    make it up, else the first positions of each row (a batch of one row)."""
    rows = x.shape[0] * share
    if rows >= 1 and rows == int(rows):
        return x[:int(rows)]
    return x[:, :int(x.shape[1] * share)]


def leaf_norms(tree: dict) -> dict:
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for n, x in tree.items()}


def run(config: dict, job: dict, seed: int, *, steps: int = 3, dtype=jnp.float32,
        precision: str = "highest", fault: str | None = None, chips: int = 1,
        device=None) -> dict:
    """The reference's first training steps: ``losses`` (cross-entropy),
    ``grad_norms`` of the first clipped gradient and ``change_norms`` after
    the steps, per leaf.  ``dtype``/``precision`` give the control; ``fault``
    is ``half_batch`` or ``no_exchange``, as for the dense decoder."""
    dims = Dims.of(config)
    key = W.root_key(common.key_words(seed))
    batch, seq = job["batch"], job["seq"]
    grad_share = {None: 1, "half_batch": 1 / 2, "no_exchange": 1 / chips}[fault]
    loss_share = 1 / 2 if fault == "half_batch" else 1
    device = device or jax.devices()[0]

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def step(params, m, v, bias, tokens, labels, lr, t):
        def loss_of(p, share):
            return loss(dims, p, _first(tokens, share), _first(labels, share), bias, dtype)

        (_, (ce, load)), grads = jax.value_and_grad(loss_of, has_aux=True)(params, grad_share)
        if loss_share != grad_share:
            ce = loss_of(params, loss_share)[1][0]
        grads = {n: g.astype(jnp.float32) for n, g in grads.items()}
        params, m, v, clipped = D.adamw(job, lr, t, params, grads, m, v, dtype)
        bias = bias + dims.gamma * jnp.sign(load.mean(-1, keepdims=True) - load)
        return params, m, v, bias, ce, leaf_norms(clipped)

    with jax.default_device(device), jax.default_matmul_precision(precision):
        params = jax.jit(lambda k: make_params(k, dims, dtype))(key)
        params = {n: p.astype(dtype) for n, p in params.items()}
        m = {n: jnp.zeros(p.shape, jnp.float32) for n, p in params.items()}
        v = {n: jnp.zeros(p.shape, jnp.float32) for n, p in params.items()}
        bias = jnp.zeros((dims.n_layers - dims.n_dense, dims.experts), jnp.float32)
        losses, first_grad = [], None
        for t in range(1, steps + 1):
            tokens, labels = common.train_rows(seed, t - 1, batch, seq, dims.vocab)
            params, m, v, bias, ce, gnorms = step(params, m, v, bias, tokens, labels,
                                                  np.float32(D.lr_at(job, t)), np.float32(t))
            losses.append(float(ce))
            if first_grad is None:
                first_grad = {n: float(x) for n, x in gnorms.items()}
        del m, v
        change = jax.jit(lambda p: {
            n: jnp.sqrt(jnp.sum(jnp.square(p[n].astype(jnp.float32)
                                           - make_leaf(key, dims, n, dtype))))
            for n in p})(params)
        return {"losses": losses, "grad_norms": first_grad,
                "change_norms": {n: float(x) for n, x in change.items()}}
