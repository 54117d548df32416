"""Mamba-2 (SSD — state-space duality) language model [arXiv:2405.21060].

Chunked SSD following the paper's minimal listing: within-chunk quadratic
("attention-like") term + inter-chunk linear state recurrence.  Decode keeps a
constant-size recurrent state (B, H, P, N) — this is what makes the
``long_500k`` shape runnable for this family.

Validated in tests against a sequential recurrence reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L


def dims(cfg: ArchConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_head_dim, cfg.ssm_state


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16):
    d = cfg.d_model
    di, h, p, n = dims(cfg)
    keys = jax.random.split(key, 6)
    lshape = (cfg.n_layers,)
    conv_ch = di + 2 * n  # conv over x, B, C
    layer = {
        "norm": _stack_norm(cfg, cfg.n_layers),
        # in_proj: d -> [z(di), x(di), B(n), C(n), dt(h)]
        "w_in": L.dense_init(keys[0], lshape + (d, 2 * di + 2 * n + h), dtype=dtype),
        "conv_w": (jax.random.normal(keys[1], lshape + (cfg.conv_width, conv_ch)) * 0.1
                   ).astype(dtype),
        "A_log": jnp.zeros(lshape + (h,), jnp.float32) + jnp.log(jnp.arange(1, h + 1)),
        "D": jnp.ones(lshape + (h,), jnp.float32),
        "dt_bias": jnp.zeros(lshape + (h,), jnp.float32),
        "w_out": L.dense_init(keys[2], lshape + (di, d), dtype=dtype),
    }
    return {
        "embed": L.embed_init(keys[3], (cfg.vocab, d), dtype=dtype),
        "layers": layer,
        "final_norm": L.norm_params(d, cfg.norm_type),
        "unembed": L.dense_init(keys[4], (d, cfg.vocab), dtype=dtype),
    }


def _stack_norm(cfg, n):
    base = L.norm_params(cfg.d_model, cfg.norm_type)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), base)


def _segsum(x):
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k]."""
    t = x.shape[-1]
    c = jnp.cumsum(x, axis=-1)
    out = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int, init_state=None):
    """SSD scan. x:(b,s,h,p), dt:(b,s,h) (post-softplus), A:(h,) (negative),
    B,C:(b,s,n).  Returns (y (b,s,h,p), final_state (b,h,p,n))."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = x.shape[1]
    nc = sp // chunk
    xr = x.reshape(b, nc, chunk, h, p).astype(jnp.float32)
    dtr = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    Br = B.reshape(b, nc, chunk, n).astype(jnp.float32)
    Cr = C.reshape(b, nc, chunk, n).astype(jnp.float32)

    dA = dtr * A  # (b,nc,q,h)  negative
    dA_cs = jnp.cumsum(dA, axis=2)  # (b,nc,q,h)

    # 1) intra-chunk (quadratic) term
    Lmat = jnp.exp(_segsum(jnp.moveaxis(dA, -1, -2)))  # (b,nc,h,q,q)
    xdt = xr * dtr[..., None]
    y_diag = jnp.einsum("bcln,bcsn,bchls,bcshp->bclhp", Cr, Br, Lmat, xdt)

    # 2) chunk states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)  # (b,nc,q,h)
    states = jnp.einsum("bcln,bclh,bclhp->bchpn", Br, decay_states * dtr, xr)

    # 3) inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])  # (b,nc,h)

    def scan_fn(prev, inp):
        st, dec = inp
        cur = prev * dec[..., None, None] + st
        return cur, prev

    init = (
        jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
        else init_state.astype(jnp.float32)
    )
    final, prev_states = lax.scan(
        scan_fn,
        init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
        unroll=L.scan_unroll(nc),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (b,nc,h,p,n)

    # 4) off-chunk contribution
    state_decay = jnp.exp(dA_cs)  # (b,nc,q,h)
    y_off = jnp.einsum("bcln,bchpn,bclh->bclhp", Cr, prev_states, state_decay)

    y = (y_diag + y_off).reshape(b, sp, h, p)
    if pad:
        y = y[:, :s]
    return y.astype(x.dtype), final


def _mix(cfg, lp, x, conv_state=None, ssm_state=None, single_step=False):
    """One mamba2 mixing layer. Returns (y, new_conv_state, new_ssm_state)."""
    b, s, d = x.shape
    di, h, p, n = dims(cfg)
    proj = jnp.einsum("bsd,de->bse", x, lp["w_in"])
    z, xin, Bm, Cm, dt = jnp.split(proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], -1)
    conv_in = jnp.concatenate([xin, Bm, Cm], axis=-1)
    conv_out, new_conv = L.causal_conv1d(conv_in, lp["conv_w"], conv_state)
    conv_out = jax.nn.silu(conv_out)
    xc, Bc, Cc = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])
    xh = xc.reshape(b, s, h, p)
    if single_step:
        # recurrent step: state' = exp(dt*A) state + dt * B ⊗ x
        dA = jnp.exp(dt[:, 0] * A)  # (b,h)
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0], Bc[:, 0], xh[:, 0])
        new_state = ssm_state * dA[..., None, None] + upd
        y = jnp.einsum("bn,bhpn->bhp", Cc[:, 0], new_state)[:, None]
    else:
        y, new_state = ssd_chunked(xh, dt, A, Bc, Cc, cfg.ssm_chunk, ssm_state)
    y = y + lp["D"][None, None, :, None] * xh[:, :s]
    y = y.reshape(b, s, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    return jnp.einsum("bse,ed->bsd", y, lp["w_out"]), new_conv, new_state


def forward(cfg: ArchConfig, params, tokens, remat: bool = True, act_specs=None, **_):
    act = (act_specs or {}).get("act")
    x = L.constrain(params["embed"][tokens], act)

    def layer_fn(h, lp):
        a = L.apply_norm(h, lp["norm"], cfg.norm_type)
        y, _, _ = _mix(cfg, lp, a)
        return L.constrain(h + y, act), None

    body = jax.checkpoint(layer_fn) if remat else layer_fn
    x, _ = lax.scan(body, x, params["layers"], unroll=L.scan_unroll(cfg.n_layers))
    x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
    logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    logits = L.constrain(logits, (act_specs or {}).get("logits"))
    return logits, {"balance": jnp.float32(0.0), "load": None}


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Constant-size state: conv tail + SSM state per layer."""
    di, h, p, n = dims(cfg)
    conv_ch = di + 2 * n
    return {
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.conv_width - 1, conv_ch), dtype),
        "ssm": jnp.zeros((cfg.n_layers, batch, h, p, n), jnp.float32),
        "len": jnp.zeros((), jnp.int32),
    }


def decode_step(cfg: ArchConfig, params, cache, tokens, positions=None, **_):
    x = params["embed"][tokens]

    def layer_fn(h, inp):
        lp, conv_st, ssm_st = inp
        a = L.apply_norm(h, lp["norm"], cfg.norm_type)
        y, new_conv, new_ssm = _mix(cfg, lp, a, conv_st, ssm_st, single_step=True)
        return h + y, (new_conv, new_ssm)

    x, (new_conv, new_ssm) = lax.scan(
        layer_fn, x, (params["layers"], cache["conv"], cache["ssm"])
    )
    x = L.apply_norm(x, params["final_norm"], cfg.norm_type)
    logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    return logits, dict(cache, conv=new_conv, ssm=new_ssm, len=cache["len"] + 1)
