"""Mixture-of-Experts layer (GShard-style top-k routing, capacity-bounded).

Two execution strategies:

* ``moe_apply`` (default) — *scatter/gather dispatch*: tokens are grouped in
  fixed-size sequence chunks; each group scatters its routed tokens into an
  ``(E, C, D)`` capacity buffer, runs the expert GEMMs batched over E, and
  gathers back.  Expert weights are tensor-sharded on d_ff (Megatron-style
  column/row split), so it is dry-run-safe at every scale and needs no
  cross-device token exchange — the paper's "operator parallelism" pattern.

* ``moe_apply_ep`` — *true expert parallelism*: experts are sharded over the
  ``model`` mesh axis inside a ``shard_map``; token slabs are exchanged with
  ``lax.all_to_all``, which is exactly the MoE alltoall traffic the paper
  analyses for GPT-3-MoE (§V-B5).  Used by the EP dry-run variant and the
  collective benchmarks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


GROUP_TOKENS = 4096  # tokens per dispatch group (bounds the capacity buffer)


def capacity(group: int, top_k: int, n_experts: int, factor: float) -> int:
    return max(1, int(group * top_k * factor / n_experts))


def _route(x, w_router, top_k):
    """x: (T, D) -> gates (T, k) f32, experts (T, k) int32 (+aux loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = lax.top_k(probs, top_k)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch/GShard)
    e = w_router.shape[1]
    density = jnp.mean(jax.nn.one_hot(experts[:, 0], e), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * e
    return gates, experts, aux


def _group_dispatch(xg, gates, experts, w_gate, w_up, w_down, cap):
    """One group: xg (G, D); experts (G, k); returns (G, D)."""
    g, d = xg.shape
    k = experts.shape[1]
    e = w_gate.shape[0]
    flat_e = experts.reshape(-1)  # (G*k,) token-major
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (G*k, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # position within expert
    pos_of = jnp.sum(pos * onehot, axis=-1)  # (G*k,)
    keep = (pos_of < cap).astype(xg.dtype)
    xrep = jnp.repeat(xg, k, axis=0)  # (G*k, D)
    buf = jnp.zeros((e, cap, d), xg.dtype)
    buf = buf.at[flat_e, jnp.minimum(pos_of, cap - 1)].add(xrep * keep[:, None])
    # expert FFN (SwiGLU), batched over experts
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up
    )
    out = jnp.einsum("ecf,efd->ecd", h, w_down)
    y_choice = out[flat_e, jnp.minimum(pos_of, cap - 1)]  # (G*k, D)
    y_choice = y_choice * (keep * gates.reshape(-1).astype(xg.dtype))[:, None]
    return y_choice.reshape(g, k, d).sum(axis=1)


def moe_apply(x, params, top_k: int, capacity_factor: float = 1.25):
    """x: (B, S, D) -> (B, S, D). params: router (D,E), w_gate/up (E,D,F),
    w_down (E,F,D)."""
    b, s, d = x.shape
    group = min(GROUP_TOKENS, s)
    n_groups = (s + group - 1) // group
    pad = n_groups * group - s
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    xg = xp.reshape(b * n_groups, group, d)
    e = params["router"].shape[1]
    cap = capacity(group, top_k, e, capacity_factor)

    def per_group(xx):
        gates, experts, aux = _route(xx, params["router"], top_k)
        y = _group_dispatch(
            xx, gates, experts, params["w_gate"], params["w_up"], params["w_down"], cap
        )
        return y, aux

    y, aux = jax.vmap(per_group)(xg)
    y = y.reshape(b, n_groups * group, d)
    if pad:
        y = y[:, :s]
    return y, jnp.mean(aux)


def moe_apply_gshard(x, params, top_k: int, capacity_factor: float,
                     expert_spec=None):
    """GShard-style einsum dispatch with the expert dim sharded over ``model``.

    Unlike ``moe_apply`` (whose row-parallel w_down psums the full (E, C, D)
    capacity buffer — 5x the token bytes), every expert GEMM here is *local*
    to the expert's owner and the only cross-model-axis collective is the
    (T, D) combine psum, the same floor as a dense Megatron MLP.  This is the
    GSPMD-native equivalent of all_to_all expert parallelism (the shard_map
    a2a variant below trips an XLA-CPU remat bug under scan+checkpoint; see
    EXPERIMENTS.md §Perf).

    expert_spec: optional NamedSharding pinning the (G, E, C, D) buffers'
    E dim to the model axis.
    """
    b, s, d = x.shape
    group = min(GROUP_TOKENS, s)
    n_groups = (s + group - 1) // group
    pad = n_groups * group - s
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    xg = xp.reshape(b * n_groups, group, d)
    e = params["router"].shape[1]
    cap = capacity(group, top_k, e, capacity_factor)

    gates, experts, aux = jax.vmap(
        lambda xx: _route(xx, params["router"], top_k))(xg)
    # dispatch/combine one-hots: (G, T, E, C)
    flat_e = experts.reshape(xg.shape[0], -1)  # (G, T*k)
    onehot_e = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot_e, axis=1) - onehot_e
    pos_of = jnp.sum(pos * onehot_e, axis=-1)
    keep = pos_of < cap
    disp = (
        jax.nn.one_hot(flat_e, e, dtype=xg.dtype)[..., None]
        * jax.nn.one_hot(jnp.minimum(pos_of, cap - 1), cap, dtype=xg.dtype)[..., None, :]
        * keep[..., None, None].astype(xg.dtype)
    )  # (G, T*k, E, C)
    comb = disp * gates.reshape(gates.shape[0], -1)[..., None, None].astype(xg.dtype)
    xrep = jnp.repeat(xg, top_k, axis=1)  # (G, T*k, D)
    buf = jnp.einsum("gtec,gtd->gecd", disp, xrep)
    if expert_spec is not None:
        buf = jax.lax.with_sharding_constraint(buf, expert_spec)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, params["w_gate"])) * jnp.einsum(
        "gecd,edf->gecf", buf, params["w_up"]
    )
    out = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    if expert_spec is not None:
        out = jax.lax.with_sharding_constraint(out, expert_spec)
    y = jnp.einsum("gtec,gecd->gtd", comb, out)  # E contraction -> psum(T,D)
    return _gshard_regroup(y, b, n_groups, group, top_k, d, pad, s), jnp.mean(aux)


def _gshard_regroup(y, b, n_groups, group, top_k, d, pad, s):
    # y: (G, T*k, D) contributions per (token, choice); fold the k copies.
    y = y.reshape(b * n_groups, group, top_k, d).sum(axis=2)
    y = y.reshape(b, n_groups * group, d)
    if pad:
        y = y[:, :s]
    return y


def moe_apply_ep(x, params, top_k: int, capacity_factor: float, axis: str = "model"):
    """Expert-parallel MoE *inside shard_map over ``axis``*.

    Local tokens are dispatched into per-expert capacity slabs, exchanged with
    ``lax.all_to_all`` so each device receives the slabs of its own experts,
    computed, and exchanged back.  Caller must run this under shard_map with
    experts sharded over ``axis`` (w_gate/w_up/w_down leading dim = local
    experts) and tokens sharded over the data axes.
    """
    b, s, d = x.shape
    n_dev = lax.axis_size(axis)
    e_local = params["w_gate"].shape[0]
    e = e_local * n_dev
    t = b * s
    xt = x.reshape(t, d)
    gates, experts, aux = _route(xt, params["router"], top_k)
    cap = capacity(t, top_k, e, capacity_factor)
    flat_e = experts.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_of = jnp.sum(pos * onehot, axis=-1)
    keep = (pos_of < cap).astype(x.dtype)
    xrep = jnp.repeat(xt, top_k, axis=0)
    slabs = jnp.zeros((e, cap, d), x.dtype)
    slabs = slabs.at[flat_e, jnp.minimum(pos_of, cap - 1)].add(xrep * keep[:, None])
    # exchange: (E, C, D) -> (n_dev, e_local, C, D) -> a2a over dim 0
    slabs = slabs.reshape(n_dev, e_local, cap, d)
    recv = lax.all_to_all(slabs, axis, split_axis=0, concat_axis=0, tiled=False)
    # recv: (n_dev, e_local, C, D): token slabs from every peer for MY experts
    h = jax.nn.silu(jnp.einsum("pecd,edf->pecf", recv, params["w_gate"])) * jnp.einsum(
        "pecd,edf->pecf", recv, params["w_up"]
    )
    out = jnp.einsum("pecf,efd->pecd", h, params["w_down"])
    back = lax.all_to_all(out, axis, split_axis=0, concat_axis=0, tiled=False)
    back = back.reshape(e, cap, d)
    y_choice = back[flat_e, jnp.minimum(pos_of, cap - 1)]
    y_choice = y_choice * (keep * gates.reshape(-1).astype(x.dtype))[:, None]
    y = y_choice.reshape(t, top_k, d).sum(axis=1)
    return y.reshape(b, s, d), aux
