"""Model correctness: SSD vs sequential recurrence, RG-LRU scan vs step,
decode-vs-forward consistency, MoE no-drop equivalence."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.models import get_model, transformer
from repro.models.mamba2 import ssd_chunked
from repro.models.recurrentgemma import rglru, rglru_step


def _ssd_sequential(x, dt, A, B, C):
    b, s, h, p = x.shape
    n = B.shape[-1]
    hstate = np.zeros((b, h, p, n))
    ys = []
    x, dt, A, B, C = map(lambda a: np.asarray(a, np.float64), (x, dt, A, B, C))
    for t in range(s):
        decay = np.exp(dt[:, t] * A)
        upd = np.einsum("bh,bn,bhp->bhpn", dt[:, t], B[:, t], x[:, t])
        hstate = hstate * decay[..., None, None] + upd
        ys.append(np.einsum("bn,bhpn->bhp", C[:, t], hstate))
    return np.stack(ys, 1), hstate


@pytest.mark.parametrize("chunk", [4, 7, 8, 24])
def test_ssd_chunked_matches_sequential(chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, s, h, p, n = 2, 24, 3, 4, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y, st = ssd_chunked(x, dt, A, B, C, chunk=chunk)
    yr, str_ = _ssd_sequential(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y, np.float64), yr, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st, np.float64), str_, rtol=1e-4, atol=1e-5)


def test_rglru_scan_matches_step():
    b, s, d = 2, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, d), jnp.float32)
    lp = {
        "w_a": jax.random.normal(jax.random.PRNGKey(1), (d, d)) * 0.1,
        "w_i": jax.random.normal(jax.random.PRNGKey(2), (d, d)) * 0.1,
        "lambda_p": jnp.full((d,), 0.5),
    }
    y_full, hfin = rglru(x, lp)
    h = jnp.zeros((b, d))
    ys = []
    for t in range(s):
        yt, h = rglru_step(x[:, t : t + 1], lp, h)
        ys.append(yt[:, 0])
    np.testing.assert_allclose(
        np.asarray(y_full), np.asarray(jnp.stack(ys, 1)), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(np.asarray(hfin), np.asarray(h), rtol=1e-5, atol=1e-6)


CONSISTENCY_CASES = [
    ArchConfig("dense", "dense", 2, 64, 4, 2, 128, 256),
    ArchConfig("ssm", "ssm", 2, 64, 0, 0, 0, 256, ssm_state=16, ssm_head_dim=16,
               ssm_chunk=4, rope_type="none"),
    ArchConfig("hybrid", "hybrid", 5, 64, 4, 1, 128, 256, local_window=16,
               attention_period=3),
    ArchConfig("moe", "moe", 2, 64, 4, 2, 96, 256, n_experts=4, top_k=2,
               capacity_factor=8.0),  # no-drop capacity
]


@pytest.mark.parametrize("cfg", CONSISTENCY_CASES, ids=lambda c: c.name)
def test_decode_matches_forward(cfg):
    m = get_model(cfg)
    params = m.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    full, _ = m.forward(cfg, params, toks, remat=False)
    cache = m.init_cache(cfg, 2, 16, dtype=jnp.float32)
    outs = []
    for t in range(8):
        lg, cache = m.decode_step(cfg, params, cache, toks[:, t : t + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(
        np.asarray(full, np.float32), np.asarray(dec, np.float32), rtol=2e-2, atol=2e-4
    )


DONATED_CASES = CONSISTENCY_CASES + [
    # the window is as long as the steps: the first position sits on its edge
    ArchConfig("vlm", "vlm", 2, 64, 4, 2, 128, 256, head_dim=16, rope_type="mrope",
               mrope_sections=(4, 2, 2), local_window=8),
    ArchConfig("audio", "audio", 2, 64, 4, 4, 128, 256, head_dim=16, enc_layers=2,
               enc_seq=16, rope_type="learned", norm_type="layernorm", act="gelu"),
]


@pytest.mark.parametrize("cfg", DONATED_CASES, ids=lambda c: c.name)
def test_donated_decode_matches_forward(cfg):
    """The decode step jitted with its cache donated, as ``launch/serve.make_step``
    runs it, over a cache longer than the steps: each step writes its rows into
    the buffers of the last, and the logits still match ``forward``."""
    m = get_model(cfg)
    params = m.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    cache = m.init_cache(cfg, 2, 24, dtype=jnp.float32)
    extra = {}
    if cfg.enc_layers:
        frames = jax.random.normal(jax.random.PRNGKey(2), (2, cfg.enc_seq, cfg.d_model))
        extra["encoder_frames"] = frames
        enc = transformer._encoder_forward(cfg, params["encoder"], frames, remat=False)
        lp = params["layers"]
        for name in ("k", "v"):
            x = jnp.einsum("bsd,ldq->lbsq", enc, lp[f"xw{name}"])
            cache[f"x{name}"] = x.reshape(cache[f"x{name}"].shape)
    full, _ = m.forward(cfg, params, toks, remat=False, **extra)
    step = jax.jit(partial(m.decode_step, cfg), donate_argnums=(1,))
    outs = []
    for t in range(8):
        last = cache
        lg, cache = step(params, cache, toks[:, t : t + 1])
        assert all(a.is_deleted() for a in jax.tree.leaves(last))
        outs.append(lg[:, 0])
    assert int(cache["len"]) == 8
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(
        np.asarray(full, np.float32), np.asarray(dec, np.float32), rtol=2e-2, atol=2e-4
    )


def test_chunked_attention_matches_dense():
    from repro.models import layers as L

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    for causal, window in [(True, 0), (True, 16), (False, 0)]:
        dense = L.attention_dense(q, k, v, causal=causal, window=window)
        chunked = L.attention_chunked(q, k, v, causal=causal, window=window, chunk=16)
        np.testing.assert_allclose(
            np.asarray(dense, np.float32), np.asarray(chunked, np.float32),
            rtol=2e-5, atol=2e-5,
        )
    # a length that is no multiple of the chunk, and values narrower than keys
    q, k, v = q[:, :50], k[:, :50], v[:, :50, :, :8]
    for causal, window in [(True, 0), (True, 20), (False, 0)]:
        dense = L.attention_dense(q, k, v, causal=causal, window=window)
        chunked = L.attention_chunked(q, k, v, causal=causal, window=window, chunk=16)
        np.testing.assert_allclose(
            np.asarray(dense, np.float32), np.asarray(chunked, np.float32),
            rtol=2e-5, atol=2e-5,
        )
