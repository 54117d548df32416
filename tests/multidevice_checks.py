"""Multi-device correctness checks, run in a subprocess with fake devices.

Invoked by tests/test_collectives.py as::

    XLA_FLAGS=--xla_force_host_platform_device_count=16 python -m tests.multidevice_checks

Each check prints ``OK <name>`` on success; any failure raises.
Kept in one script so the (expensive) jax multi-device init happens once.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import collectives as coll  # noqa: E402


def make_mesh(shape=(4, 4), names=("data", "model")):
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names))


def check_allreduce_algorithms():
    mesh = make_mesh()
    x = jnp.arange(16 * 37, dtype=jnp.float32).reshape(16, 37) / 7.0

    ref_fn = jax.jit(
        jax.shard_map(
            lambda v: jax.lax.psum(v, ("data", "model")),
            mesh=mesh, check_vma=False, in_specs=P("data", None), out_specs=P("data", None),
        )
    )
    ref = ref_fn(x)

    for algo in ("ring", "bidir", "torus", "hamiltonian"):
        fn = jax.jit(
            jax.shard_map(
                lambda v, a=algo: coll.allreduce(v, a, ("data", "model"), (4, 4)),
                mesh=mesh, check_vma=False, in_specs=P("data", None), out_specs=P("data", None),
            )
        )
        out = fn(x)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        print(f"OK allreduce:{algo}")

    # a 2x2 mesh (one four-chip host): hamiltonian has no edge-disjoint
    # cycle pair there and runs over a single cycle
    mesh22 = make_mesh((2, 2), ("r", "c"))
    x22 = jax.random.normal(jax.random.PRNGKey(3), (4, 1000))
    spec = P(("r", "c"), None)
    ref22 = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, ("r", "c")),
        mesh=mesh22, check_vma=False, in_specs=spec, out_specs=spec))(x22)
    for algo in ("ring", "bidir", "torus", "hamiltonian"):
        out = jax.jit(jax.shard_map(
            lambda v, a=algo: coll.allreduce(v, a, ("r", "c"), (2, 2)),
            mesh=mesh22, check_vma=False, in_specs=spec, out_specs=spec))(x22)
        np.testing.assert_allclose(out, ref22, rtol=1e-5, atol=1e-5)
        print(f"OK allreduce2x2:{algo}")

    # 1D variants over a single axis
    x1 = jnp.arange(16 * 64, dtype=jnp.float32).reshape(16, 64) / 7.0
    ref1 = jax.jit(
        jax.shard_map(
            lambda v: jax.lax.psum(v, "model"),
            mesh=mesh, check_vma=False, in_specs=P("data", "model"), out_specs=P("data", "model"),
        )
    )(x1)
    for algo in ("ring", "bidir"):
        out = jax.jit(
            jax.shard_map(
                lambda v, a=algo: coll.allreduce(v, a, ("model",)),
                mesh=mesh, check_vma=False, in_specs=P("data", "model"), out_specs=P("data", "model"),
            )
        )(x1)
        np.testing.assert_allclose(out, ref1, rtol=1e-5, atol=1e-5)
        print(f"OK allreduce1d:{algo}")


def check_reduce_scatter_allgather():
    mesh = make_mesh((16,), ("r",))
    x = jnp.arange(16 * 32, dtype=jnp.float32).reshape(16, 32)

    def rs_ag(v):
        chunk = coll.ring_reduce_scatter(v, "r")
        return coll.ring_all_gather(chunk, "r").reshape(v.shape)

    out = jax.jit(
        jax.shard_map(rs_ag, mesh=mesh, check_vma=False, in_specs=P("r", None), out_specs=P("r", None))
    )(x)
    ref = jax.jit(
        jax.shard_map(
            lambda v: jax.lax.psum(v, "r"),
            mesh=mesh, check_vma=False, in_specs=P("r", None), out_specs=P("r", None),
        )
    )(x)
    np.testing.assert_allclose(out, ref, rtol=1e-5)
    print("OK rs+ag == psum")


def check_allreduce_tree():
    mesh = make_mesh()
    tree = {
        "w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        "b": jnp.ones((5,), jnp.bfloat16),
    }

    def f(t):
        return coll.allreduce_tree(t, "torus", ("data", "model"), (4, 4), mean=True)

    out = jax.jit(
        jax.shard_map(f, mesh=mesh, check_vma=False, in_specs=(P(),), out_specs=P())
    )(tree)
    # replicated inputs -> mean over 16 identical copies == identity
    np.testing.assert_allclose(out["w"], tree["w"], rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out["b"], np.float32), np.asarray(tree["b"], np.float32), rtol=1e-2
    )
    print("OK allreduce_tree")


def check_compression():
    from repro.core import compression as comp

    mesh = make_mesh((16,), ("d",))
    g = jax.random.normal(jax.random.PRNGKey(0), (16, 64))

    def f(gs):
        st = comp.init_state(gs)
        out, st2 = comp.sparse_allreduce(gs, st, k=8, axis_name="d")
        return out, st2.residual

    out, resid = jax.jit(
        jax.shard_map(f, mesh=mesh, check_vma=False, in_specs=P("d", None), out_specs=P("d", None))
    )(g)
    # sparse allreduce + residual must preserve the total gradient mass:
    # sum over devices of (sent + residual) == sum of raw gradients
    sent_total = np.asarray(out).sum(0) * 16 / 16  # out replicated per shard row
    # each shard row holds the same reduced vector; take row 0
    reduced = np.asarray(out)[0]
    resid_sum = np.asarray(resid).sum(0)
    raw_mean = np.asarray(g).mean(0)
    np.testing.assert_allclose(reduced + resid_sum / 16, raw_mean, rtol=1e-4, atol=1e-5)
    print("OK sparse_allreduce mass conservation")


def check_hlo_collective_bytes():
    """ring vs psum: the ring lowers to collective-permute only."""
    mesh = make_mesh()
    x = jax.ShapeDtypeStruct((16, 1024), jnp.float32)
    lo = jax.jit(
        jax.shard_map(
            lambda v: coll.ring_allreduce(v, "model"),
            mesh=mesh, check_vma=False, in_specs=P("data", "model"), out_specs=P("data", "model"),
        )
    ).lower(x)
    txt = lo.compile().as_text()
    assert "collective-permute" in txt, "ring must lower to collective-permute"
    assert "all-reduce" not in txt.replace("all-reduce-scatter", ""), \
        "ring allreduce must not fall back to XLA all-reduce"
    print("OK hlo: ring lowers to collective-permute")


def check_collective_train_step():
    """Paper-collective gradient sync == auto psum sync (same updates)."""
    from repro.configs.base import ArchConfig
    from repro.parallel.sharding import Policy
    from repro.train import optimizer as opt, steps as steps_lib
    from repro.data.pipeline import make_batch

    cfg = ArchConfig("tiny", "dense", 2, 32, 4, 2, 64, 128)
    from repro.models import get_model

    m = get_model(cfg)
    params = m.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ocfg = opt.AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    mesh = make_mesh((4, 4))
    policy = Policy(data_axes=("data",))
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, 8, 16).items()}

    ref_step = jax.jit(
        steps_lib.make_train_step(
            cfg, ocfg, steps_lib.TrainOptions(remat=False), policy
        )
    )
    with jax.set_mesh(mesh):
        p_ref, _, m_ref = ref_step(params, opt.init(params), batch)

    # 1-axis algorithms over "data"; 2-axis over the full (data, model) grid
    # (pure-DP mapping, the paper's small-model case).
    policy2d = Policy(data_axes=("data", "model"))
    for algo, pol in [("ring", policy), ("bidir", policy),
                      ("torus", policy2d), ("hamiltonian", policy2d)]:
        step = steps_lib.make_train_step(
            cfg, ocfg, steps_lib.TrainOptions(remat=False, sync=algo), pol, mesh
        )
        with jax.set_mesh(mesh):
            p_new, _, m_new = jax.jit(step)(params, opt.init(params), batch)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_new)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-4, atol=2e-5,
            )
        print(f"OK collective train step: {algo} (loss {float(m_new['loss']):.4f})")


def check_pipeline_parallel():
    """GPipe pipeline over 4 stages == sequential stage application."""
    from repro.parallel import pipeline as pp

    mesh = make_mesh((4,), ("pipe",))
    m_micro, mb, d = 8, 4, 16
    ws = jax.random.normal(jax.random.PRNGKey(0), (4, d, d)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (m_micro, mb, d))

    def stage(w, h):
        return jnp.tanh(h @ w)

    run = jax.jit(
        jax.shard_map(
            lambda w, xx: pp.pipeline_forward(stage, w[0], xx, "pipe"),
            mesh=mesh, check_vma=False,
            in_specs=(P("pipe", None, None), P(None, None, None)),
            out_specs=P(None, None, None),
        )
    )
    # outputs valid on last stage; shard_map out_specs P(None) takes device 0's
    # copy, so gather explicitly via psum of masked output inside instead:
    def run_fn(w, xx):
        out = pp.pipeline_forward(stage, w[0], xx, "pipe")
        idx = jax.lax.axis_index("pipe")
        out = jnp.where(idx == jax.lax.axis_size("pipe") - 1, out, 0.0)
        return jax.lax.psum(out, "pipe")

    run = jax.jit(
        jax.shard_map(
            run_fn, mesh=mesh, check_vma=False,
            in_specs=(P("pipe", None, None), P(None, None, None)),
            out_specs=P(None, None, None),
        )
    )
    out = run(ws, x)
    ref = x
    for i in range(4):
        ref = jax.vmap(lambda h: stage(ws[i], h))(ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)
    print("OK pipeline forward == sequential")

    # gradient flows through the pipeline.  NOTE: differentiate the *masked
    # per-device* loss (no psum in the AD path) — the global loss is the
    # implicit sum of per-device scalars, and the ppermute transposes carry
    # cotangents back to earlier stages.
    def loss(w, xx):
        out = pp.pipeline_forward(stage, w[0], xx, "pipe")
        idx = jax.lax.axis_index("pipe")
        out = jnp.where(idx == jax.lax.axis_size("pipe") - 1, out, 0.0)
        return jnp.mean(out**2)

    g = jax.jit(
        jax.shard_map(
            jax.grad(loss), mesh=mesh, check_vma=False,
            in_specs=(P("pipe", None, None), P(None, None, None)),
            out_specs=P("pipe", None, None),
        )
    )(ws, x)

    gref = jax.grad(lambda w: jnp.mean(
        jax.vmap(lambda h: stage(w[3], stage(w[2], stage(w[1], stage(w[0], h)))))(x) ** 2
    ))(ws)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref), rtol=1e-4, atol=1e-6)
    print("OK pipeline backward == sequential grad")


def check_moe_ep():
    """Expert-parallel MoE (all_to_all) == single-device dispatch."""
    from repro.configs.base import ArchConfig
    from repro.models import moe as moe_lib

    mesh = make_mesh((4,), ("model",))
    b, s, d, f, e, k = 2, 8, 16, 32, 8, 2
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, d))
    params = {
        "router": jax.random.normal(ks[1], (d, e)) * 0.1,
        "w_gate": jax.random.normal(ks[2], (e, d, f)) * 0.1,
        "w_up": jax.random.normal(ks[3], (e, d, f)) * 0.1,
        "w_down": jax.random.normal(ks[4], (f:=f, e, f, d))[0] * 0.1,
    }
    params["w_down"] = jax.random.normal(jax.random.PRNGKey(9), (e, f, d)) * 0.1

    # reference: the dropless layer over all experts (softmax router)
    cfg = ArchConfig("moe", "moe", 1, d, 1, 1, f, 8, n_experts=e, top_k=k)
    y_ref, _, _ = moe_lib.moe_block(cfg, params, x)

    def ep(xx, pp):
        local = jax.tree.map(lambda v: v, pp)
        y, aux = moe_lib.moe_apply_ep(xx, local, k, float(e), axis="model")
        return y

    y_ep = jax.jit(
        jax.shard_map(
            ep, mesh=mesh, check_vma=False,
            in_specs=(P(None, None, None),
                      {"router": P(None, None), "w_gate": P("model", None, None),
                       "w_up": P("model", None, None), "w_down": P("model", None, None)}),
            out_specs=P(None, None, None),
        )
    )(x, params)
    np.testing.assert_allclose(
        np.asarray(y_ep, np.float32), np.asarray(y_ref, np.float32), rtol=1e-4, atol=1e-5
    )
    print("OK moe EP all_to_all == dense dispatch")


def check_elastic_resharding():
    """Checkpoint written on one mesh restores onto a different mesh shape
    (the paper's defragmentation / elastic-restart story, §IV-A-b)."""
    import tempfile

    from repro.checkpoint import checkpoint as ckpt

    state = {
        "w": jnp.arange(16 * 32, dtype=jnp.float32).reshape(16, 32),
        "b": jnp.ones((32,), jnp.bfloat16),
    }
    mesh_a = make_mesh((4, 4))
    sh_a = {"w": jax.NamedSharding(mesh_a, P("data", "model")),
            "b": jax.NamedSharding(mesh_a, P("model"))}
    state_a = jax.tree.map(jax.device_put, state, sh_a)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d + "/c", state_a, step=3)
        mesh_b = make_mesh((2, 8), ("data", "model"))
        sh_b = {"w": jax.NamedSharding(mesh_b, P("model", "data")),
                "b": jax.NamedSharding(mesh_b, P(None))}
        restored, step = ckpt.restore(d + "/c", state, shardings=sh_b)
        assert step == 3
        for k in state:
            np.testing.assert_array_equal(
                np.asarray(restored[k], np.float32), np.asarray(state[k], np.float32))
        assert restored["w"].sharding.mesh.shape == {"data": 2, "model": 8}
    print("OK elastic resharding across mesh shapes")


if __name__ == "__main__":
    assert len(jax.devices()) >= 16, f"need >=16 fake devices, got {len(jax.devices())}"
    check_elastic_resharding()
    check_allreduce_algorithms()
    check_reduce_scatter_allgather()
    check_allreduce_tree()
    check_compression()
    check_hlo_collective_bytes()
    check_collective_train_step()
    check_pipeline_parallel()
    check_moe_ep()
    print("ALL-OK")
