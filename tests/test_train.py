"""Training substrate: loss descent, schedules, checkpoint/restart, elastic."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import ArchConfig
from repro.data.pipeline import SyntheticLM, DataConfig, make_batch
from repro.models import get_model
from repro.parallel.sharding import Policy
from repro.train import optimizer as opt
from repro.train import steps as steps_lib

CFG = ArchConfig("tiny", "dense", 2, 64, 4, 2, 128, 256)


def _setup():
    model = get_model(CFG)
    params = model.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    ocfg = opt.AdamWConfig(lr=1e-2, warmup_steps=5, total_steps=100)
    step = jax.jit(steps_lib.make_train_step(
        CFG, ocfg, steps_lib.TrainOptions(remat=False), Policy()))
    return params, opt.init(params), step


def test_loss_descends():
    params, ostate, step = _setup()
    losses = []
    for s in range(20):
        batch = {k: jnp.asarray(v) for k, v in make_batch(CFG, 16, 4, step=s).items()}
        params, ostate, metrics = step(params, ostate, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.3


def test_data_pipeline_deterministic():
    gen = SyntheticLM(DataConfig(vocab=256, seq_len=16, global_batch=4, seed=3))
    a = gen.batch(7)
    b = gen.batch(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = gen.batch(8)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # labels are next-token shifted
    full = SyntheticLM(DataConfig(256, 16, 4, 3))
    d = full.batch(0)
    np.testing.assert_array_equal(d["tokens"][:, 1:], d["labels"][:, :-1])


def test_checkpoint_restart_resumes_identically():
    params, ostate, step = _setup()
    for s in range(5):
        batch = {k: jnp.asarray(v) for k, v in make_batch(CFG, 16, 4, step=s).items()}
        params, ostate, _ = step(params, ostate, batch)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_step(d, {"p": params, "o": ostate}, 5)
        # continue original
        cont_p, cont_o = params, ostate
        for s in range(5, 8):
            batch = {k: jnp.asarray(v) for k, v in make_batch(CFG, 16, 4, step=s).items()}
            cont_p, cont_o, _ = step(cont_p, cont_o, batch)
        # restart from checkpoint (simulated failure) and replay
        restored, start = ckpt.restore_latest(d, {"p": params, "o": ostate})
        rp, ro = restored["p"], restored["o"]
        assert start == 5
        for s in range(5, 8):
            batch = {k: jnp.asarray(v) for k, v in make_batch(CFG, 16, 4, step=s).items()}
            rp, ro, _ = step(rp, ro, batch)
        for a, b in zip(jax.tree.leaves(cont_p), jax.tree.leaves(rp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_checkpoint_retention():
    params, ostate, _ = _setup()
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4, 5):
            ckpt.save_step(d, {"p": params}, s, keep=2)
        assert ckpt.latest_step(d) == 5
        import os

        steps = sorted(n for n in os.listdir(d) if n.startswith("step_"))
        assert steps == ["step_4", "step_5"]


def test_schedules():
    cos = opt.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110, schedule="cosine")
    wsd = opt.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110, schedule="wsd")
    assert float(opt.schedule_lr(cos, jnp.int32(0))) == 0.0
    assert float(opt.schedule_lr(cos, jnp.int32(10))) == 1.0
    assert float(opt.schedule_lr(cos, jnp.int32(110))) < 0.01
    assert float(opt.schedule_lr(wsd, jnp.int32(60))) == 1.0  # stable plateau
    assert float(opt.schedule_lr(wsd, jnp.int32(110))) < 0.2  # decayed


def test_grad_clip():
    g = {"w": jnp.ones((4,)) * 100.0}
    clipped, norm = opt.clip_by_global_norm(g, 1.0)
    assert float(norm) == 200.0
    np.testing.assert_allclose(float(jnp.linalg.norm(clipped["w"])), 1.0, rtol=1e-5)


# ---------------------------------------------------------------------------
# launch entry points (the path chip_smoke.py drives at real widths)
# ---------------------------------------------------------------------------


def test_launch_train_loop_descends_and_donates():
    from repro.launch import train

    mesh, params, ostate, step_fn = train.build(CFG, steps=6, lr=1e-2)
    first = jax.tree.leaves(params)[0]
    losses = []
    for n, params, ostate, m in train.train_loop(
            CFG, mesh, step_fn, params, ostate, start=0, stop=6, seq=32, batch=4):
        losses.append(float(m["loss"]))
    assert n == 6 and all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert first.is_deleted(), "params were not donated to the step"


def test_launch_serve_generate_donates_cache():
    from repro.launch import serve

    model = get_model(CFG)
    params = model.init_params(CFG, jax.random.PRNGKey(0))
    prompts = make_batch(CFG, 8, 3)["tokens"]
    step = serve.make_step(CFG)
    tokens, prefill_s, decode_s = serve.generate(CFG, params, step, prompts, 5)
    assert tokens.shape == (3, 5) and ((tokens >= 0) & (tokens < CFG.vocab)).all()
    cache = model.init_cache(CFG, 3, 13)
    step(params, cache, jnp.asarray(prompts[:, :1]))
    assert cache["k"].is_deleted(), "the cache was not donated to the step"


def test_compile_cache_dir(monkeypatch, tmp_path):
    from pathlib import Path

    from repro.launch import cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cache.enable_compile_cache() == str(cache.REPO_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(cache.REPO_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert cache.REPO_CACHE == Path(__file__).resolve().parents[1] / ".jax_cache"
