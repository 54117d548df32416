"""The check that decides ``correct`` catches a broken program and the
lower-precision control, at smoke size on the CPU.

Each fault is planted underneath the timed path (the program's optimizer,
loss, gradient sync, decode step or ``generate``), the rest of a run is
driven as on the chip but for the look for a chip, and ``correct`` must come
out false.  The controls are the reference computed one precision below the
configuration's, read against the cell's own limits: each from the
reference of the cell's model family.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_smoke as smoke
from chipbench import common, compare

SPEC = json.loads((smoke.REPO / "BENCHMARK.json").read_text())


def _traffic(name):
    return json.loads((smoke.REPO / "chipbench" / "traffic" / f"{name}.json").read_text())


TRAIN_CELLS = [w["name"] for w in SPEC["workloads"] if _traffic(w["traffic"])["kind"] == "train"]
SERVE_CELLS = [w["name"] for w in SPEC["workloads"] if _traffic(w["traffic"])["kind"] == "serve"]


def _run_broken(tmp_path, monkeypatch, cell, target, make_broken):
    module, attr = target.rsplit(".", 1)
    mod = __import__(module, fromlist=[attr])
    monkeypatch.setattr(mod, attr, make_broken(getattr(mod, attr)))
    return smoke.run_cell(smoke.build(tmp_path), cell)


def _state_unchanged(apply):
    def broken(cfg, state, params, grads):
        _, _, metrics = apply(cfg, state, params, grads)
        return params, state, metrics
    return broken


def _half_batch(cross_entropy):
    def broken(logits, labels):
        half = logits.shape[0] // 2
        return cross_entropy(logits[:half], labels[:half])
    return broken


TRAIN_FAULTS = {
    "state_unchanged": ("repro.train.optimizer.apply", _state_unchanged),
    "half_batch": ("repro.train.steps.cross_entropy", _half_batch),
}


@pytest.mark.parametrize("fault", sorted(TRAIN_FAULTS))
def test_training_fault_is_not_correct(tmp_path, monkeypatch, fault):
    target, make = TRAIN_FAULTS[fault]
    out = _run_broken(tmp_path, monkeypatch, "minicpm2b.train.s2048", target, make)
    assert not out["correct"], out["checks"]


def _cache_unchanged(make_decode_step):
    def broken(cfg):
        step = make_decode_step(cfg)

        def serve_step(params, cache, tokens):
            tok, _ = step(params, cache, tokens)
            return tok, cache
        return serve_step
    return broken


def _token_altered(generate):
    def broken(cfg, params, step_fn, prompts, n_decode):
        tokens, prefill_s, decode_s = generate(cfg, params, step_fn, prompts, n_decode)
        tokens = tokens.copy()
        tokens[:, -1] = (tokens[:, -1] + 1) % cfg.vocab
        return tokens, prefill_s, decode_s
    return broken


SERVE_FAULTS = {
    "state_unchanged": ("repro.train.steps.make_decode_step", _cache_unchanged),
    "token_altered": ("repro.launch.serve.generate", _token_altered),
}


@pytest.mark.parametrize("cell", SERVE_CELLS)
@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serving_fault_is_not_correct(tmp_path, monkeypatch, cell, fault):
    target, make = SERVE_FAULTS[fault]
    out = _run_broken(tmp_path, monkeypatch, cell, target, make)
    assert not out["correct"], out["checks"]


FOUR_CHIPS = [w["name"] for w in SPEC["workloads"] if w["chips"] == 4]
NO_EXCHANGE = ("from repro.core import collectives\n"
               "collectives.allreduce_tree = lambda grads, *a, **k: grads\n")


@pytest.mark.parametrize("cell", FOUR_CHIPS)
def test_four_chip_cell_is_correct_and_catches_a_missing_exchange(tmp_path, cell):
    """On four CPU devices, the four-chip cell with the paper's bidirectional
    ring: the sound run is correct, and the same run with the ring left out
    (each chip keeps its own rows' gradient) is not."""
    root = smoke.build(tmp_path)
    sound = smoke.run_cell_on_devices(root, cell, 4)
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    broken = smoke.run_cell_on_devices(root, cell, 4, prelude=NO_EXCHANGE)
    assert not broken["correct"], broken["checks"]


def _small_config(cell):
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    config = smoke.small_config(json.loads(
        (smoke.REPO / "chipbench" / "configs" / f"{w['config']}.json").read_text()))
    return w, config, common.family(smoke.REPO, config)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_training_control_in_bfloat16_is_not_correct(cell):
    w, config, family = _small_config(cell)
    job = smoke.small_traffic(_traffic(w["traffic"]))
    ref = family.train_reference(config, job, smoke.SEED)
    control = family.train_reference(config, job, smoke.SEED, dtype=jnp.bfloat16,
                                     precision="default")
    numbers = compare.train_numbers(control, ref)
    assert any(v > job["limits"][k] for k, v in numbers.items()), numbers


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serving_control_in_float8_is_not_correct(cell):
    """The control's first choices read against the reference's logits, at
    every position of a few drawn sequences."""
    w, config, family = _small_config(cell)
    limit = _traffic(w["traffic"])["limits"]["served_gap"]
    rng = np.random.default_rng(0)
    vocab = config["vocab_size"]
    requests = [(rng.integers(0, vocab, 16), rng.integers(0, vocab, 48)) for _ in range(4)]
    control = family.serve_gaps(config, smoke.SEED, jnp.bfloat16, requests, control=True)
    assert compare.served_gap(control) > limit
