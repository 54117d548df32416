"""The benchmark's plain reference against the program, at smoke size on the
CPU, on the same seeded weights; and the weight maker against the program's
own parameter tree."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_smoke as smoke
from chipbench import common, compare
from chipbench.reference import decoder as D
from chipbench.reference import serve_ref, train_ref
from chipbench.reference import weights as W
from repro.data.pipeline import make_batch
from repro.launch import train as T
from repro.models import transformer
from repro.train import optimizer as opt_lib

CONFIGS = ["minicpm2b", "granite8b_l16"]


def config_of(name):
    return smoke.small_config(json.loads((smoke.REPO / "chipbench" / "configs"
                                          / f"{name}.json").read_text()))


def program_params(config, seed, dtype):
    cfg = common.arch_config(config)
    template = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype))
    key = W.root_key(common.key_words(seed))
    return cfg, jax.jit(lambda k: W.make_params(template, k, tied=cfg.tie_embeddings))(key)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weight_maker_fills_the_programs_tree(name, dtype):
    config = config_of(name)
    cfg = common.arch_config(config)
    want = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0),
                                                          dtype=dtype))
    _, got = program_params(config, smoke.SEED, dtype)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
    # the reference makes the same values, leaf by leaf and layer by layer,
    # to one rounding of the dtype (XLA fuses the scaling differently)
    dims = D.Dims.of(config)
    key = W.root_key(common.key_words(smoke.SEED))
    flat = {W.leaf_name(p): x for p, x in jax.tree_util.tree_flatten_with_path(got)[0]}
    assert set(flat) == set(D.param_shapes(dims))
    rtol = 2 * float(jnp.finfo(dtype).eps)
    for n, x in flat.items():
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(D.make_leaf(key, dims, n, dtype)), rtol=rtol)
    np.testing.assert_allclose(
        np.asarray(flat["layers/wq"][1], np.float32),
        np.asarray(D.make_leaf(key, dims, "layers/wq", dtype, layer=1)), rtol=rtol)


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_do_not_saturate_attention(name):
    """Unit-variance activations at 1/sqrt(fan-in): the program's first
    layer's attention scores spread over a few units, not thousands."""
    config = config_of(name)
    cfg, params = program_params(config, smoke.SEED, jnp.float32)
    toks = common.lm_rows(smoke.SEED, 0, 2, 32, config["vocab_size"])
    dims = D.Dims.of(config)
    ref = {n: D.make_leaf(W.root_key(common.key_words(smoke.SEED)), dims, n, jnp.float32)
           for n in D.param_shapes(dims)}
    x = D.rmsnorm(ref["embed"][toks], ref["layers/attn_norm/scale"][0], dims.eps)
    q = (x @ ref["layers/wq"][0]).reshape(2, 32, dims.h, dims.hd)
    k = (x @ ref["layers/wk"][0]).reshape(2, 32, dims.kv, dims.hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, :dims.kv], k) / np.sqrt(dims.hd)
    assert 0.3 < float(jnp.std(scores)) < 3.0


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_forward_matches_program(name):
    config = config_of(name)
    cfg, params = program_params(config, smoke.SEED, jnp.float32)
    toks = common.lm_rows(smoke.SEED, 1, 2, 24, config["vocab_size"])
    want, _ = transformer.forward(cfg, params, jnp.asarray(toks), remat=False)
    dims = D.Dims.of(config)
    ref = D.make_params(W.root_key(common.key_words(smoke.SEED)), dims, jnp.float32)
    got = D.hidden(dims, ref, jnp.asarray(toks)) @ D.unembedding(ref)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_program_decode(name):
    """The program's decode steps through its (bfloat16) cache, teacher-forced
    over a sequence, against the reference's forward: within the cache's
    rounding; the served gaps of its greedy tokens are at that scale too."""
    config = config_of(name)
    cfg, params = program_params(config, smoke.SEED, jnp.float32)
    toks = common.lm_rows(smoke.SEED, 2, 2, 16, config["vocab_size"])
    cache = transformer.init_cache(cfg, 2, 16)
    step = jax.jit(lambda p, c, t: transformer.decode_step(cfg, p, c, t))
    logits = []
    for t in range(16):
        lg, cache = step(params, cache, jnp.asarray(toks[:, t:t + 1]))
        logits.append(lg[:, 0])
    got = np.stack(logits, axis=1)
    dims = D.Dims.of(config)
    ref = D.make_params(W.root_key(common.key_words(smoke.SEED)), dims, jnp.float32)
    want = np.asarray(D.hidden(dims, ref, jnp.asarray(toks)) @ D.unembedding(ref))
    np.testing.assert_allclose(got, want, atol=5e-2)
    # greedy: 8 prompt tokens, then 8 served tokens fed back
    cache = transformer.init_cache(cfg, 2, 16)
    tok, served = None, []
    for t in range(15):
        feed = jnp.asarray(toks[:, t:t + 1]) if t < 8 else tok
        lg, cache = step(params, cache, feed)
        tok = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        if t >= 7:
            served.append(np.asarray(tok[:, 0]))
    served = np.stack(served, axis=1)
    gaps = serve_ref.gaps(config, smoke.SEED, jnp.float32,
                          [(toks[r, :8], served[r]) for r in range(2)])
    assert compare.served_gap(gaps) < 5e-2


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_training_matches_program(name):
    config = config_of(name)
    job = json.loads((smoke.REPO / "chipbench" / "traffic" / "train.s2048.json").read_text())
    job = dict(job, seq=32)
    cfg = common.arch_config(config, schedule=job["lr_schedule"])
    _, params = program_params(config, smoke.SEED, jnp.float32)
    opt_state = opt_lib.init(params)
    step = T.make_step(cfg, steps=job["horizon_steps"], lr=job["lr"])
    names = [W.leaf_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    p0 = [np.asarray(x) for x in jax.tree.leaves(params)]
    losses = []
    for s in range(2):
        b = make_batch(cfg, job["seq"], job["batch"], step=s, seed=smoke.SEED)
        params, opt_state, metrics = step(params, opt_state, b)
        losses.append(float(metrics["loss"]))
        if s == 0:
            grads = {n: float(np.linalg.norm(np.asarray(m))) / (1 - job["b1"])
                     for n, m in zip(names, jax.tree.leaves(opt_state.m))}
    change = {n: float(np.linalg.norm(np.asarray(p) - q))
              for n, p, q in zip(names, jax.tree.leaves(params), p0)}
    ref = train_ref.run(config, job, smoke.SEED, steps=2)
    got = {"losses": losses, "grad_norms": grads, "change_norms": change}
    numbers = compare.train_numbers(got, ref)
    assert numbers["loss_gap"] < 1e-5
    assert numbers["grad_gap"] < 1e-4
    assert numbers["change_gap"] < 1e-3


def test_training_rows_are_the_programs():
    cfg = common.arch_config(config_of("minicpm2b"))
    for step in (0, 5):
        b = make_batch(cfg, 32, 2, step=step, seed=smoke.SEED)
        toks, labels = common.train_rows(smoke.SEED, step, 2, 32, cfg.vocab)
        np.testing.assert_array_equal(b["tokens"], toks)
        np.testing.assert_array_equal(b["labels"], labels)


def test_wave_order_serves_every_seed_the_same_mix():
    for seed in (0, 7, 2**33 + 1):
        order = common.wave_order(seed, 2, 10)
        assert sorted(order) == [0] * 5 + [1] * 5
        assert all(sorted(order[i:i + 2]) == [0, 1] for i in range(0, 10, 2))
    assert common.wave_order(3, 2, 6) == common.wave_order(3, 2, 6)
