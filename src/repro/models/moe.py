"""Mixture-of-Experts layer.

* ``moe_block`` (every MoE config's layer on the default ``tp`` mode) is
  *dropless* and told which experts it holds: it routes every token over all
  ``n_experts`` (softmax top-k, or DeepSeek-V3's sigmoid router with a
  correction bias), sorts the (token, choice) pairs by held expert and runs
  the held experts as grouped matmuls (megablox ``gmm``) over exactly the
  pairs that chose them.  A pair that chose an expert held on another chip
  takes no expert work and adds nothing; no pair is dropped at any load.
  The k choices of every token run one after another, T rows at a time.
  Shared experts (an MLP every token passes through) are added once.  On one
  chip the layer runs without the exchange of expert parallelism.

* ``moe_apply_gshard`` and ``moe_apply_ep`` keep GShard-style capacity
  buffers with fixed shapes, for the ``gshard`` and ``ep`` dry-run variants
  and the multi-device checks: ``moe_apply_ep`` exchanges token slabs with
  ``lax.all_to_all``, exactly the MoE alltoall traffic the paper analyses
  for GPT-3-MoE (§V-B5).  Both take the softmax router and no shared experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops as kops
from repro.models import layers as L
from repro.obs import device as OD

GROUP_TOKENS = 4096  # tokens per dispatch group (bounds the capacity buffer)
ROW_TILE = 128  # rows of (token, choice) pairs per grouped-matmul tile


def capacity(group: int, top_k: int, n_experts: int, factor: float) -> int:
    return max(1, int(group * top_k * factor / n_experts))


def _route(x, w_router, top_k):
    """Softmax router. x: (T, D) -> gates (T, k) f32, experts (T, k) int32
    (+ the Switch/GShard load-balancing loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = lax.top_k(probs, top_k)
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
    e = w_router.shape[1]
    density = jnp.mean(jax.nn.one_hot(experts[:, 0], e), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * e
    return gates, experts, aux


def _route_sigmoid(cfg, x, w_router, bias, batch):
    """DeepSeek-V3's router (noaux_tc, one group).  Scores s = sigmoid(x W_r)
    in float32; the top k of s + b choose the experts (b, the correction
    bias, takes no gradient); the gates are the chosen s, normalized to sum
    to 1 and scaled by ``routed_scale``.  The sequence-wise balance loss is
    sum_i f_i P_i per sequence, with f_i = E / (k S) x the pairs that chose
    expert i and P_i the mean over the sequence of s_i / sum_j s_j."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores + lax.stop_gradient(bias), cfg.top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
    t, e = scores.shape
    s = t // batch
    share = (scores / scores.sum(-1, keepdims=True)).reshape(batch, s, e).mean(1)
    chose = jnp.sum(jax.nn.one_hot(experts.reshape(batch, s * cfg.top_k), e), axis=1)
    aux = jnp.mean(jnp.sum(chose * (e / (cfg.top_k * s)) * share, axis=-1))
    return gates, experts, aux


def balance_bias(bias: jax.Array, load: jax.Array, rate: float) -> jax.Array:
    """DeepSeek-V3's balancing without an auxiliary loss, after each step:
    each expert's bias moves by ``rate`` up where its load (pairs routed to
    it, per layer) is under the layer's mean and down where it is over."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)


def _tiling(m: int, k: int, n: int):
    """Tiles of a grouped matmul (m, k) x (k, n): ``ROW_TILE`` rows (all of
    them where fewer), and each width in 512s where that divides it, else
    whole (1408 = 11 x 128 is)."""
    return (min(ROW_TILE, m), 512 if k % 512 == 0 else k, 512 if n % 512 == 0 else n)


def _routed(cfg, p, xt, expert, gate):
    """The held experts' part for one choice of every token: xt (T, D),
    ``expert``/``gate`` (T,) -> (T, D), 0 where the chosen expert is not held.
    A token takes one pair here, so each row moves once each way."""
    t, d = xt.shape
    held = cfg.held_experts
    with jax.named_scope(OD.DISPATCH):
        # held experts' tokens first, by expert; all others form one last group
        local = expert - cfg.expert_first
        group = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1).astype(jnp.int32)
        tile = ROW_TILE if t >= ROW_TILE else 8
        rows = -(-t // tile) * tile  # whole tiles; the padding belongs to no group
        xs = jnp.take(xt, jnp.pad(order, (0, rows - t)), axis=0, unique_indices=rows == t)
    with jax.named_scope(OD.EXPERTS):
        def gmm(lhs, w):
            return kops.grouped_matmul(lhs, w, sizes, _tiling)
        h = jax.nn.silu(gmm(xs, p["w_gate"])) * gmm(xs, p["w_up"])
        out = gmm(h, p["w_down"])  # rows of absent experts and padding: 0
    with jax.named_scope(OD.COMBINE):
        back = jnp.take(out, jnp.argsort(order), axis=0, unique_indices=True)
        return back * gate[:, None].astype(back.dtype)


@jax.named_scope(OD.MOE)
def moe_block(cfg, p, x, bias=None):
    """x (B, S, D) -> (y (B, S, D), balance loss, load (n_experts,) int32).

    ``p``: ``router`` (D, E) over all ``cfg.n_experts``; ``w_gate``/``w_up``
    (H, D, F) and ``w_down`` (H, F, D) for the H = ``cfg.held_experts``
    experts from ``cfg.expert_first`` on; ``shared_gate``/``shared_up``
    (D, Fs) and ``shared_down`` (Fs, D) where ``cfg.shared_d_ff``.  ``bias``
    (E,): the sigmoid router's correction bias.  ``load`` counts the
    (token, choice) pairs each of the E experts was given.

    The routed part runs one choice of every token at a time (a scan over
    the k choices, each recomputed in the backward), so its buffers hold T
    rows and not T x k."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    with jax.named_scope(OD.ROUTER):
        if cfg.router == "sigmoid":
            if bias is None:
                bias = jnp.zeros((cfg.n_experts,), jnp.float32)
            gates, experts, aux = _route_sigmoid(cfg, xt, p["router"], bias, b)
        else:
            gates, experts, aux = _route(xt, p["router"], cfg.top_k)
        load = jnp.bincount(experts.reshape(-1), length=cfg.n_experts).astype(jnp.int32)
    routed = jax.checkpoint(lambda e, g: _routed(cfg, p, xt, e, g))

    def one_choice(y, choice):
        part = routed(*choice)
        with jax.named_scope(OD.COMBINE):
            return y + part, None

    y, _ = lax.scan(one_choice, jnp.zeros((t, d), x.dtype), (experts.T, gates.T))
    y = y.reshape(b, s, d)
    if cfg.shared_d_ff:
        with jax.named_scope(OD.SHARED_EXPERTS):
            y = y + L.swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y, aux, load


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
