"""Compile-only checks of the main path for a TPU v5e described without a chip.

The ``v5e:2x2`` topology is described in a module fixture, so the TPU's
library is loaded only by the process that runs these tests, and only once
they start.  Nothing here runs: a pass means the TPU compiler accepts the
program and that it fits one chip's memory, not that its results are right.
``chip_smoke.py`` checks results on the chip.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.core import collectives as coll
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rms
from repro.launch import serve, train
from repro.models import get_model
from repro.train import optimizer as opt_lib

HBM_LIMIT = 15.75e9  # bytes one program may use on a 16 GB v5e, as its compiler says
MINICPM = get_config("minicpm-2b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep the cache off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("layout", [(36, 36, 64), (32, 8, 128)],
                         ids=["minicpm-2b", "granite-8b"])
def test_flash_kernel_compiles(one_chip, layout):
    h, kv, d = layout
    q = jax.ShapeDtypeStruct((2, 2048, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, 2048, kv, d), jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(lambda q, k, v: fa.flash_attention_fwd(q, k, v, True, 0,
                                                        interpret=False))
    assert "tpu_custom_call" in fn.lower(q, k, k).compile().as_text()


def test_grouped_matmul_kernel_compiles(one_chip):
    """The held experts' grouped matmul at Moonlight-16B-A3B's widths: 8192
    rows of float32 pairs, 8 held experts of 2048 x 1408 (and back), one
    last group for the pairs held elsewhere; its backward too."""
    from repro.kernels.ops import megablox
    from repro.models import moe as moe_lib

    x = jax.ShapeDtypeStruct((8192, 2048), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, 2048, 1408), jnp.float32, sharding=one_chip)
    w_down = jax.ShapeDtypeStruct((8, 1408, 2048), jnp.float32, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((9,), jnp.int32, sharding=one_chip)

    def gmm(a, b, sizes):  # compiled, not interpreted: the process's backend is the CPU
        return megablox.gmm(a, b, sizes, a.dtype, moe_lib._tiling, None, None, False, False)

    def expert(x, w, w_down, sizes):
        return jnp.sum(gmm(jax.nn.silu(gmm(x, w, sizes)), w_down, sizes))

    fn = jax.jit(jax.grad(expert, argnums=(0, 1, 2)))
    assert "tpu_custom_call" in fn.lower(x, w, w_down, sizes).compile().as_text()


def test_rmsnorm_kernel_compiles(one_chip):
    x = jax.ShapeDtypeStruct((2, 2048, MINICPM.d_model), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((MINICPM.d_model,), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda x, g: rms.rmsnorm(x, g, interpret=False))
    assert "tpu_custom_call" in fn.lower(x, g).compile().as_text()


def test_decode_step_fits_one_chip(one_chip):
    """minicpm-2b, all 40 layers in bf16, batch 8, 192 cached positions."""
    model = get_model(MINICPM)
    params = jax.eval_shape(lambda: model.init_params(MINICPM, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_cache(MINICPM, 8, 192))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    compiled = serve.make_step(MINICPM).lower(
        _placed(params, one_chip), _placed(cache, one_chip), tok).compile()
    assert _hbm_bytes(compiled) < HBM_LIMIT


def test_decode_step_updates_the_cache_in_place(one_chip):
    """minicpm-2b at batch 16 and 512 positions: the donated cache is written
    in place, with no copy or transpose of the stacked cache or of one layer
    of it, and the step's temporaries stay far below the cache's size."""
    model = get_model(MINICPM)
    params = jax.eval_shape(lambda: model.init_params(MINICPM, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_cache(MINICPM, 16, 512))
    tok = jax.ShapeDtypeStruct((16, 1), jnp.int32, sharding=one_chip)
    compiled = serve.make_step(MINICPM).lower(
        _placed(params, one_chip), _placed(cache, one_chip), tok).compile()
    stacked = cache["k"].size
    cache_sized = {stacked, stacked // MINICPM.n_layers}
    moved = [
        line.strip()[:160] for line in compiled.as_text().splitlines()
        if (m := re.search(r"= \w+\[([\d,]*)\]\S* (?:copy|transpose)\(", line))
        and math.prod(int(d) for d in m.group(1).split(",") if d) in cache_sized
    ]
    assert not moved, moved
    cache_bytes = 2 * stacked * cache["k"].dtype.itemsize  # k and v
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 4


def test_train_step_fits_one_chip(one_chip):
    """minicpm-2b cut to 2 layers, f32 params and AdamW state, batch 2 x 2048."""
    cfg = dataclasses.replace(MINICPM, n_layers=2)
    model = get_model(cfg)
    params = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    opt_state = jax.eval_shape(opt_lib.init, params)
    batch = {k: jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    compiled = train.make_step(cfg, steps=5).lower(
        _placed(params, one_chip), _placed(opt_state, one_chip), batch).compile()
    assert _hbm_bytes(compiled) < HBM_LIMIT


@pytest.mark.parametrize("algo", ["ring", "bidir", "torus", "hamiltonian"])
def test_allreduce_compiles_on_four_chips(topo, algo):
    """The paper's allreduce over the four described chips as a 2x2 mesh:
    neighbour permutes only, no XLA all-reduce."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("r", "c"))
    spec = P(("r", "c"))
    x = jax.ShapeDtypeStruct((4, 4 << 20), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    fn = jax.jit(jax.shard_map(
        lambda v: coll.allreduce(v, algo, ("r", "c"), (2, 2)),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
    text = fn.lower(x).compile().as_text()
    assert "collective-permute" in text
    assert "all-reduce(" not in text and "all-reduce-start" not in text
