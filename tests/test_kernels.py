"""Pallas kernels vs the pure-jnp oracles (interpret mode off the TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

CASES = [
    # b, sq, sk, h, kv, d, causal, window, dtype, rtol
    (1, 128, 128, 4, 4, 64, True, 0, jnp.float32, 2e-5),
    (2, 256, 256, 4, 2, 64, True, 0, jnp.float32, 2e-5),
    (1, 128, 384, 4, 1, 64, False, 0, jnp.float32, 2e-5),  # cross-attn, MQA
    (1, 256, 256, 8, 2, 32, True, 64, jnp.float32, 2e-5),  # sliding window
    (1, 200, 200, 2, 2, 64, True, 0, jnp.float32, 2e-5),   # non-block-multiple
    (1, 128, 128, 4, 4, 128, True, 0, jnp.float32, 2e-5),  # d=128 (MXU width)
    (1, 128, 128, 4, 4, 64, True, 0, jnp.bfloat16, 3e-2),
    (2, 128, 128, 2, 1, 64, False, 32, jnp.bfloat16, 3e-2),
    # published head layouts, at the edges of the heads-major blocks
    (1, 256, 256, 36, 36, 64, True, 0, jnp.float32, 2e-5),   # minicpm-2b, d=64
    (1, 256, 256, 36, 36, 64, True, 0, jnp.bfloat16, 3e-2),
    (1, 256, 256, 32, 8, 128, True, 0, jnp.float32, 2e-5),   # granite-8b GQA
]


@pytest.mark.parametrize("case", CASES, ids=[str(c[:8]) for c in CASES])
def test_flash_vs_oracle(case):
    b, sq, sk, h, kv, d, causal, window, dtype, rtol = case
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, d), dtype)
    out = ops.flash_attention(q, k, v, causal, window)
    want = ref.flash_attention_ref(q, k, v, causal, window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=rtol, atol=rtol
    )


def test_flash_gradients_match_reference():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))

    def k_loss(q_, k_, v_):
        return ops.flash_attention(q_, k_, v_).sum()

    def r_loss(q_, k_, v_):
        return ref.flash_attention_ref(q_, k_, v_).sum()

    gk = jax.grad(k_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_flash_inside_model_forward():
    """use_kernel=True path through the transformer."""
    from repro.configs.base import ArchConfig
    from repro.models import get_model

    cfg = ArchConfig("k", "dense", 2, 64, 4, 2, 128, 256, head_dim=16)
    m = get_model(cfg)
    params = m.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, cfg.vocab)
    with_k, _ = m.forward(cfg, params, toks, remat=False, use_kernel=True)
    without, _ = m.forward(cfg, params, toks, remat=False, use_kernel=False)
    np.testing.assert_allclose(
        np.asarray(with_k, np.float32), np.asarray(without, np.float32),
        rtol=5e-3, atol=5e-3,
    )


# ---------------------------------------------------------------------------
# fused RMSNorm kernel
# ---------------------------------------------------------------------------


RMS_CASES = [
    ((4, 128), jnp.float32),
    ((2, 200, 64), jnp.float32),   # non-multiple rows
    ((1, 64, 256), jnp.bfloat16),
]


@pytest.mark.parametrize("case", RMS_CASES, ids=[str(c) for c in RMS_CASES])
def test_rmsnorm_kernel_vs_oracle(case):
    shape, dtype = case
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), jnp.float32) * 0.1
    out = ops.rmsnorm(x, g)
    want = ref.rmsnorm_ref(x, g)
    rtol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=rtol, atol=rtol,
    )
