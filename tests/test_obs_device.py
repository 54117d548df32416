"""The program's names on the JAX profiler (repro.obs.device): the named
scopes reach the compiled HLO's op_name, the spans change nothing the
program computes, and the collection hook spans only while the profiler is
on."""

from __future__ import annotations

import gc
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve as S
from repro.launch import train as T
from repro.models import get_model
from repro.obs import device as OD

ARCH = "minicpm-2b-smoke"
MODEL_SCOPES = {OD.EMBED, OD.ATTENTION, OD.MLP, OD.NORM, OD.UNEMBED, OD.LAYERS}
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def scopes_in(hlo_text: str) -> set:
    """Scope names that some instruction's op_name holds as a path component."""
    found = set()
    for op in re.findall(r'op_name="([^"]*)"', hlo_text):
        found.update(re.findall(r"(?:^|[/(])(%s)(?=[)/]|$)" % "|".join(OD.SCOPES), op))
    return found


def _train(sync="auto"):
    cfg = get_config(ARCH)
    mesh, params, opt_state, step = T.build(cfg, steps=10, sync=sync)
    return cfg, mesh, params, opt_state, step


def test_train_step_hlo_carries_every_scope():
    cfg, mesh, params, opt_state, step = _train()
    b = T.place_batch({"tokens": jnp.zeros((2, 16), jnp.int32),
                       "labels": jnp.zeros((2, 16), jnp.int32)}, mesh)
    found = scopes_in(step.lower(params, opt_state, b).compile().as_text())
    assert found == MODEL_SCOPES | {OD.LOSS, OD.OPTIMIZER}


def test_moe_train_step_hlo_carries_the_nested_scopes():
    """Latent attention and the expert layer name their parts under
    ``attention`` and ``mlp``; the whole expert layer, with its loop over
    each token's choices and that loop's gradient sums, is under ``moe``."""
    cfg = get_config("moonshot-v1-16b-a3b-smoke")
    mesh, params, opt_state, step = T.build(cfg, steps=10)
    b = T.place_batch({"tokens": jnp.zeros((2, 16), jnp.int32),
                       "labels": jnp.zeros((2, 16), jnp.int32)}, mesh)
    text = step.lower(params, opt_state, b).compile().as_text()
    nested = {OD.LATENT: OD.ATTENTION, OD.MOE: OD.MLP, OD.ROUTER: OD.MOE,
              OD.DISPATCH: OD.MOE, OD.EXPERTS: OD.MOE, OD.SHARED_EXPERTS: OD.MOE,
              OD.COMBINE: OD.MOE}
    ops = re.findall(r'op_name="([^"]*)"', text)
    for name, outer in nested.items():
        under = re.compile(rf"(^|/){outer}/(.*/)?{name}/")
        assert any(under.search(op) for op in ops), name
    loop = re.compile(rf"(^|/){OD.MLP}/(.*/)?while/")
    assert not [op for op in ops if loop.search(op) and f"/{OD.MOE}/" not in op]
    assert any(loop.search(op) and op.endswith("add_any") for op in ops)
    assert scopes_in(text) == MODEL_SCOPES | {OD.LOSS, OD.OPTIMIZER}


def test_decode_step_hlo_carries_the_model_scopes():
    cfg = get_config(ARCH)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    cache = model.init_cache(cfg, 2, 8)
    text = S.make_step(cfg).lower(params, cache, jnp.zeros((2, 1), jnp.int32)).compile().as_text()
    assert scopes_in(text) == MODEL_SCOPES


BIDIR = """
import json, re, sys
import jax, jax.numpy as jnp
sys.path.insert(0, {tests!r})
import test_obs_device as t
cfg, mesh, params, opt_state, step = t._train(sync="bidir")
b = t.T.place_batch({{"tokens": jnp.zeros((4, 16), jnp.int32),
                      "labels": jnp.zeros((4, 16), jnp.int32)}}, mesh)
print(json.dumps(sorted(t.scopes_in(step.lower(params, opt_state, b).compile().as_text()))))
"""


def test_bidir_train_step_on_four_devices_carries_grad_sync():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.abspath(SRC),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, "-c", BIDIR.format(tests=os.path.dirname(__file__))],
                          env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    found = set(json.loads(done.stdout.strip().splitlines()[-1]))
    assert found == MODEL_SCOPES | {OD.LOSS, OD.OPTIMIZER, OD.GRAD_SYNC}


def _profiled(fn, directory):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def _host_spans(directory) -> list:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(directory), "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((ev.name, dict(ev.stats)) for ev in line.events
                       if ev.name.startswith(OD.SPAN_PREFIXES))
    return out


def test_generate_is_bit_identical_with_the_profiler_on_and_off(tmp_path):
    cfg = get_config(ARCH)
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(1))
    step = S.make_step(cfg)
    prompts = (np.arange(12, dtype=np.int32).reshape(3, 4) * 7) % cfg.vocab
    off = S.generate(cfg, params, step, prompts, 5)[0]
    on = _profiled(lambda: S.generate(cfg, params, step, prompts, 5)[0], tmp_path)
    np.testing.assert_array_equal(on, off)
    spans = _host_spans(tmp_path)
    names = [n for n, _ in spans]
    assert names.count(OD.SERVE_STEP) == 4 + 5 and names.count(OD.SERVE_H2D) == 4
    for n in (OD.SERVE_GENERATE, OD.SERVE_PREFILL, OD.SERVE_DECODE, OD.SERVE_FETCH):
        assert names.count(n) == 1
    args = dict(spans)[OD.SERVE_GENERATE]
    assert (args["batch"], args["prompt"], args["output"]) == (3, 4, 5)
    assert dict(spans)[OD.SERVE_PREFILL] == {"batch": 3, "prompt": 4}


def test_train_loop_is_bit_identical_with_the_profiler_on_and_off(tmp_path):
    def three_steps():
        cfg, mesh, params, opt_state, step = _train()
        out = list(T.train_loop(cfg, mesh, step, params, opt_state, start=0, stop=3,
                                seq=16, batch=2, seed=5))
        _, params, _, metrics = out[-1]
        return ([float(m["loss"]) for *_, m in out],
                [np.asarray(x) for x in jax.tree.leaves(params)])

    off = three_steps()
    on = _profiled(three_steps, tmp_path)
    assert on[0] == off[0]
    for a, b in zip(on[1], off[1]):
        np.testing.assert_array_equal(a, b)
    names = [n for n, _ in _host_spans(tmp_path)]
    for n in (OD.TRAIN_BATCH, OD.TRAIN_PLACE, OD.TRAIN_STEP):
        assert names.count(n) == 3


def test_collections_are_spanned_only_while_the_profiler_is_on(tmp_path):
    OD.trace_gc()
    OD.trace_gc()
    assert gc.callbacks.count(OD._gc_span) == 1
    gc.collect()
    assert OD._gc_span.open is None
    _profiled(gc.collect, tmp_path)
    assert OD._gc_span.open is None
    gcs = [args for n, args in _host_spans(tmp_path) if n == OD.HOST_GC]
    assert any(a["generation"] == 2 and "collected" in a for a in gcs)


@pytest.mark.parametrize("module", ["repro.obs", "repro.obs.device"])
def test_the_obs_layer_does_not_load_jax(module):
    code = f"import sys, {module}; print('jax' in sys.modules)"
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=dict(os.environ, PYTHONPATH=os.path.abspath(SRC)), timeout=120)
    assert done.stdout.strip() == "False", done.stderr[-2000:]
