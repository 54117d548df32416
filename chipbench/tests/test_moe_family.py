"""The program's DeepSeek-V3-style MoE decoder (moonshot-v1-16b-a3b's layers)
against the plain reference (``reference/mla_moe.py``), at the family's smoke
size on the CPU, on seeded weights from ``reference/weights.py``: forward
logits, three training steps, decoding through the latent cache, and the
expert layer's share of a layer split over chips.

Tolerances: on the CPU a float32 product is a float32 product in both, so
program and reference differ only by the order of their sums and XLA's
fusion: about 1e-6 relative per value, growing through a few layers and an
AdamW step.  The bounds below sit at 1e-4 (logits, loss) and 1e-3
(per-leaf norms, where a leaf whose first gradient is tiny against the
median's moves on rounding alone), ten to a hundred times what the program
reads, and far below what the same reference computed in bfloat16 reads,
which each test checks fails them.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_smoke as smoke
from chipbench import common, compare
from chipbench.reference import mla_moe as R
from chipbench.reference import weights as W
from repro.launch import train as T
from repro.models import moe as moe_lib
from repro.models import transformer
from repro.train import optimizer as opt_lib

CONFIG = smoke.small_config(json.loads(
    (smoke.REPO / "chipbench" / "configs" / "moonlight16b_l5e8.json").read_text()))
JOB = smoke.small_traffic(json.loads(
    (smoke.REPO / "chipbench" / "traffic" / "train.s8192.json").read_text()))
FAMILY = common.family(smoke.REPO, CONFIG)
LOGIT_TOL = 1e-4  # max |program - reference| / max |reference|
TRAIN_TOL = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}


def _program(config=CONFIG, dtype=jnp.float32, **extra):
    cfg = FAMILY.arch_config(config, **extra)
    template = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype))
    key = W.root_key(common.key_words(smoke.SEED))
    params = jax.jit(lambda k: W.make_params(template, k, tied=cfg.tie_embeddings))(key)
    return cfg, params


def _reference_params(config=CONFIG, dtype=jnp.float32):
    dims = R.Dims.of(config)
    return dims, R.make_params(W.root_key(common.key_words(smoke.SEED)), dims, dtype)


def _tokens(n=2, length=64):
    return jnp.asarray(common.lm_rows(smoke.SEED, 0, n, length, CONFIG["vocab_size"]))


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_structure_is_the_published_one_at_smoke_size():
    cfg, params = _program()
    assert (cfg.n_dense_layers, cfg.n_layers, cfg.held_experts, cfg.n_experts, cfg.top_k) == (
        1, 3, 8, 16, 6)
    assert (cfg.router, cfg.routed_scale, cfg.kv_lora_rank) == ("sigmoid", 2.446, 32)
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 128)
    assert params["layers"]["moe"]["router"].shape == (2, 64, 16)
    assert params["layers"]["moe"]["w_gate"].shape == (2, 8, 64, 32)
    assert params["layers"]["moe"]["shared_gate"].shape == (2, 64, 64)
    # the correction bias is a model buffer kept beside the moments, not a
    # parameter, and starts at zero
    state = opt_lib.init(params)
    assert set(state.buffers) == {"router_bias"}
    assert state.buffers["router_bias"].shape == (2, 16)
    assert not np.any(np.asarray(state.buffers["router_bias"]))
    assert jax.tree.structure(state.m) == jax.tree.structure(params)


def test_forward_logits_match_the_reference():
    cfg, params = _program()
    toks = _tokens()
    got, aux = transformer.forward(cfg, params, toks, remat=False)
    dims, ref = _reference_params()
    with jax.default_matmul_precision("highest"):
        want = R.logits(dims, ref, toks)
    assert _gap(got, want) < LOGIT_TOL
    assert aux["load"].shape == (2, 16) and int(aux["load"].sum()) == 2 * 2 * 64 * 6
    # the control: the reference's weights and products in bfloat16
    _, bf16 = _reference_params(dtype=jnp.bfloat16)
    assert _gap(R.logits(dims, bf16, toks, dtype=jnp.bfloat16), want) > LOGIT_TOL


def _program_steps(steps=3, config=CONFIG):
    """The program's first training steps through launch/train, as the
    training cell reads them -> (numbers, params, optimizer state, loads)."""
    cfg, params = _program(config, schedule=JOB["lr_schedule"])
    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    opt_state = opt_lib.init(params)
    step = T.make_step(cfg, steps=JOB["horizon_steps"], lr=JOB["lr"], sync="auto", mesh=mesh)
    names = [W.leaf_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    loop = T.train_loop(cfg, mesh, step, params, opt_state, start=0, stop=steps,
                        seq=JOB["seq"], batch=JOB["batch"], seed=smoke.SEED)
    losses, first, loads = [], None, []
    for i, (_, params, opt_state, metrics) in enumerate(loop):
        losses.append(float(metrics["loss"]))
        loads.append(np.asarray(metrics["load"]))
        if i == 0:
            first = {n: float(jnp.linalg.norm(m) / (1 - JOB["b1"]))
                     for n, m in zip(names, jax.tree.leaves(opt_state.m))}
    template = jax.eval_shape(lambda: params)

    @jax.jit
    def change_norms(p, k):  # the initial weights made again, as the cell does
        p0 = W.make_params(template, k, tied=False)
        return [jnp.linalg.norm(a - b) for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]

    key = W.root_key(common.key_words(smoke.SEED))
    change = dict(zip(names, map(float, change_norms(params, key))))
    numbers = {"losses": losses, "grad_norms": first, "change_norms": change}
    return numbers, params, opt_state, loads


def test_three_training_steps_match_the_reference_and_bfloat16_does_not():
    got, _, opt_state, loads = _program_steps()
    ref = R.run(CONFIG, JOB, smoke.SEED)
    numbers = compare.train_numbers(got, ref)
    assert all(numbers[k] < TRAIN_TOL[k] for k in TRAIN_TOL), numbers
    control = R.run(CONFIG, JOB, smoke.SEED, dtype=jnp.bfloat16, precision="default")
    worse = compare.train_numbers(control, ref)
    assert any(worse[k] > TRAIN_TOL[k] for k in TRAIN_TOL), worse
    # the correction bias moved by gamma x sign(mean load - load) each step,
    # with no gradient and no decay
    gamma = CONFIG["bias_update_speed"]
    want = sum(gamma * np.sign(l.mean(-1, keepdims=True) - l) for l in loads)
    np.testing.assert_allclose(np.asarray(opt_state.buffers["router_bias"]), want,
                               atol=1e-7)


def test_planted_fault_shared_experts_left_out_is_not_correct(tmp_path, monkeypatch):
    swiglu = moe_lib.L.swiglu
    monkeypatch.setattr(moe_lib, "L", type("L", (), {"swiglu": staticmethod(
        lambda x, *w: 0 * swiglu(x, *w))}))
    out = smoke.run_cell(smoke.build(tmp_path), "moonlight16b.train.s8192")
    assert not out["correct"], out["checks"]


def test_decoding_through_the_latent_cache_matches_the_reference_forward():
    cfg, params = _program()
    toks = _tokens(2, 12)
    dims, ref = _reference_params()
    with jax.default_matmul_precision("highest"):
        want = R.logits(dims, ref, toks)
    cache = transformer.init_cache(cfg, 2, 16, dtype=jnp.float32)
    assert set(cache) == {"c_kv", "k_pe", "len"} and cache["c_kv"].shape == (3, 2, 16, 32)
    step = jax.jit(lambda p, c, t: transformer.decode_step(cfg, p, c, t))
    got = []
    for i in range(toks.shape[1]):
        logits, cache = step(params, cache, toks[:, i:i + 1])
        got.append(logits[:, 0])
    assert _gap(jnp.stack(got, 1), want) < LOGIT_TOL


def test_a_trained_model_decodes_with_the_correction_bias_it_trained_with():
    """Three training steps at a bias rate large enough to move the routing,
    then decoding through the latent cache with the trained buffers, as a
    checkpoint carries them: it matches the forward pass with them."""
    config = dict(CONFIG, bias_update_speed=0.05)
    cfg = FAMILY.arch_config(config, schedule=JOB["lr_schedule"])
    _, params, opt_state, _ = _program_steps(config=config)
    buffers = opt_state.buffers
    assert float(jnp.max(jnp.abs(buffers["router_bias"]))) > 0.1
    toks = _tokens(2, 12)
    want, _ = transformer.forward(cfg, params, toks, remat=False, buffers=buffers)
    unbiased, _ = transformer.forward(cfg, params, toks, remat=False)
    assert _gap(unbiased, want) > 10 * LOGIT_TOL  # the bias changes the routing
    cache = transformer.init_cache(cfg, 2, 16, dtype=jnp.float32)
    step = jax.jit(lambda p, c, t, b: transformer.decode_step(cfg, p, c, t, buffers=b))
    got = []
    for i in range(toks.shape[1]):
        logits, cache = step(params, cache, toks[:, i:i + 1], buffers)
        got.append(logits[:, 0])
    assert _gap(jnp.stack(got, 1), want) < LOGIT_TOL


# ---------------------------------------------------------------------------
# the expert layer's share (guide: each chip holds some experts of a layer)
# ---------------------------------------------------------------------------


def _layer(held, first, seed=0):
    """One sparse layer's expert part with 16 routed experts, ``held`` of them
    from ``first`` on, as the program and as the reference hold them."""
    config = dict(CONFIG, n_routed_experts=held, program=dict(CONFIG["program"],
                                                              expert_first=first))
    cfg = dataclasses.replace(FAMILY.arch_config(config), n_layers=2, n_dense_layers=1)
    dims = R.Dims.of(config)
    key = W.root_key(common.key_words(seed))
    names = [n for n in R.layer_shapes(dims, True) if n.startswith("moe/")]
    full = R.Dims.of(dict(config, n_routed_experts=16, program={"expert_first": 0}))
    p = {}
    for n in names:
        shape = R.layer_shapes(full, True)[n]
        x = W.layer_leaf(key, "layers/" + n, 0, shape, 1 / np.sqrt(shape[-2]), jnp.float32)
        p[n] = x[first:first + held] if n in ("moe/w_gate", "moe/w_up", "moe/w_down") else x
    return cfg, dims, p


def _program_layer(cfg, p, x, bias):
    return moe_lib.moe_block(cfg, {k[4:]: v for k, v in p.items()}, x, bias)


def _inputs(t=64, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, t, 64))
    return x, jnp.zeros((16,), jnp.float32)


def test_shares_of_a_layer_add_up_to_the_uncut_layer():
    x, bias = _inputs()
    cfg, dims, whole = _layer(16, 0)
    with jax.default_matmul_precision("highest"):
        want, _, _ = R.experts(dims, whole, x, bias, jnp.float32)
    shared = R.swiglu(x, whole["moe/shared_gate"], whole["moe/shared_up"],
                      whole["moe/shared_down"], jnp.float32)
    parts = []
    for first in (0, 8):
        cfg, _, p = _layer(8, first)
        y, _, load = _program_layer(cfg, p, x, bias)
        parts.append(y - shared)  # what every chip computes alike, counted once
        assert int(load.sum()) == 2 * 64 * 6  # routed over all 16, wherever held
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    whole_y, _, _ = _program_layer(*_layer(16, 0)[::2], x, bias)
    np.testing.assert_allclose(np.asarray(whole_y), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_pairs_that_chose_an_absent_expert_add_nothing():
    x, _ = _inputs()
    cfg, dims, p = _layer(8, 8)
    # a bias that puts every token's six choices on experts 0-5, held elsewhere
    bias = jnp.where(jnp.arange(16) < 6, 10.0, 0.0)
    y, _, load = _program_layer(cfg, p, x, bias)
    shared = R.swiglu(x, p["moe/shared_gate"], p["moe/shared_up"], p["moe/shared_down"],
                      jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(shared), rtol=1e-6, atol=1e-6)
    assert np.all(np.asarray(load[:6]) == 2 * 64)


@pytest.mark.parametrize("t", [64, 1024])
def test_no_pair_is_dropped_even_when_every_token_picks_one_held_expert(t):
    x, _ = _inputs(t)
    cfg, dims, p = _layer(8, 0)
    bias = jnp.zeros((16,), jnp.float32).at[3].set(10.0)  # every token picks expert 3
    y, _, load = _program_layer(cfg, p, x, bias)
    with jax.default_matmul_precision("highest"):
        want, _, ref_load = R.experts(dims, p, x, bias, jnp.float32)
    assert int(load[3]) == 2 * t == int(ref_load[3])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
