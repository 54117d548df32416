"""The reference's logits over served requests, layer by layer.

For each request the input is its prompt and the tokens it was served but
the last; the reference reads, at each position that produced a served
token, how far that token's logit lies below the reference's best (its
``gap``; 0 where the reference would have served the same token).

The control is the same model with every matrix held in float8 (e4m3, one
scale per output column) and computed in bfloat16, the step below the
bfloat16 the serving configuration states.  Its ``gap`` is that of the token
the control puts first, read under the reference's logits.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common
from chipbench.reference import decoder as D
from chipbench.reference import weights as W

FP8_MAX = 448.0  # largest finite float8_e4m3fn


def fp8(w):
    """Float8 e4m3 with one scale per output column, back in float32."""
    if w.ndim < 2:
        return w
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-30) / FP8_MAX
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def gaps(config: dict, seed: int, dtype, requests, *, control: bool = False,
         device=None) -> list:
    """``requests``: [(prompt (P,), served (O,)), ...].  Returns one
    ``(O,)`` array of gaps per request, in order (with ``control`` the gaps
    of the control's first choices)."""
    dims = D.Dims.of(config)
    key = W.root_key(common.key_words(seed))
    device = device or jax.devices()[0]
    groups: dict = {}
    for i, (prompt, served) in enumerate(requests):
        groups.setdefault((len(prompt), len(served)), []).append(i)
    out = [None] * len(requests)
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        for (p, o), idx in sorted(groups.items()):
            toks = np.stack([np.concatenate([requests[i][0], requests[i][1][:-1]])
                             for i in idx]).astype(np.int32)
            served = np.stack([requests[i][1] for i in idx]).astype(np.int32)
            g = _group_gaps(dims, key, dtype, toks, served, p, control)
            for j, i in enumerate(idx):
                out[i] = g[j]
    return out


def _group_gaps(dims, key, dtype, toks, served, prompt_len, control):
    top = {n: D.make_leaf(key, dims, n, dtype) for n in D.top_shapes(dims)}
    run = _stack(dims, key, dtype, toks, top, quant=False)
    ref_logits = _logits(dims, top, run, prompt_len, quant=False)
    if not control:
        best = jnp.max(ref_logits, axis=-1)
        picked = jnp.take_along_axis(ref_logits, served[..., None], axis=-1)[..., 0]
        return np.asarray(best - picked)
    ctl = _stack(dims, key, dtype, toks, top, quant=True)
    choice = jnp.argmax(_logits(dims, top, ctl, prompt_len, quant=True), axis=-1)
    best = jnp.max(ref_logits, axis=-1)
    picked = jnp.take_along_axis(ref_logits, choice[..., None], axis=-1)[..., 0]
    return np.asarray(best - picked)


@partial(jax.jit, static_argnums=(0, 2, 4))
def _layer_weights(dims, key, dtype, layer, quant):
    p = {n: D.make_leaf(key, dims, n, dtype, layer=layer) for n in D.LAYER_LEAVES}
    return {n: fp8(w) for n, w in p.items()} if quant else p


@partial(jax.jit, static_argnums=(0, 3))
def _layer(dims, p, x, quant):
    pos = jnp.arange(x.shape[1])
    if quant:
        return D.layer(dims, p, x.astype(jnp.bfloat16), pos, jnp.bfloat16)
    return D.layer(dims, p, x, pos)


def _stack(dims, key, dtype, toks, top, quant):
    embed = fp8(top["embed"].T).T if quant else top["embed"]
    x = embed[toks]
    for li in range(dims.n_layers):
        x = _layer(dims, _layer_weights(dims, key, dtype, li, quant), x, quant)
    return x


@partial(jax.jit, static_argnums=(0, 3, 4))
def _logits(dims, top, x, prompt_len, quant):
    h = D.rmsnorm(x[:, prompt_len - 1:].astype(jnp.float32), top["final_norm/scale"],
                  dims.eps)
    w = D.unembedding(top)
    if quant:
        return (h.astype(jnp.bfloat16) @ fp8(w).astype(jnp.bfloat16)).astype(jnp.float32)
    return h @ w
