"""train_step / serve_step builders.

Gradient synchronization modes (the paper's technique as a first-class
feature):

* ``sync="auto"``  — plain pjit: XLA inserts its own all-reduce /
  reduce-scatter for the data-parallel gradient sum (baseline).
* ``sync in {"ring","bidir","torus","hamiltonian"}`` — the paper's HxMesh
  collective algorithms (core/collectives.py): the loss/grad is evaluated
  inside a *partial-manual* shard_map (manual over the data axes, auto over
  ``model``), and gradients are reduced with neighbor-only ppermute rings —
  the traffic pattern HammingMesh serves at full bandwidth.
* ``compress_k > 0`` — top-k sparsified gradient sync with error feedback
  (paper Appendix A) over the data axis.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import collectives as coll
from repro.models import get_model, transformer
from repro.obs import device as OD
from repro.parallel.sharding import Policy
from repro.train import optimizer as opt


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    sync: str = "auto"  # auto | ring | bidir | torus | hamiltonian
    remat: bool = True
    use_kernel: bool = False
    compress_k: int = 0
    # sequence-chunked CE: compute unembed+loss in S-chunks so the full
    # (tokens, vocab) logits are never materialized (0 = off).
    ce_chunk: int = 0


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Vocab-sharding-friendly CE: logsumexp minus one-hot-contracted logit.

    Both reductions contract the vocab axis, so a model-axis-sharded vocab
    stays sharded end-to-end (a take_along_axis gather would force a full
    replication of the logits)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    label_logit = jnp.sum(logits * onehot, axis=-1)
    return jnp.mean(lse - label_logit)


def make_loss_fn(cfg: ArchConfig, options: TrainOptions, act_specs=None):
    """loss_fn(params, batch, buffers) -> (loss + the weighted balance loss,
    (loss, aux)); ``buffers`` is the model's state without gradient (the
    optimizer state's ``buffers``)."""
    model = get_model(cfg)

    def loss_fn(params, batch, buffers):
        extras = {"buffers": buffers}
        if "positions" in batch:
            extras["positions"] = batch["positions"]
        if "encoder_frames" in batch:
            extras["encoder_frames"] = batch["encoder_frames"]
        if options.ce_chunk and cfg.family in ("dense", "moe", "vlm"):
            hidden, aux = model.forward(
                cfg, params, batch["tokens"], remat=options.remat,
                use_kernel=options.use_kernel, act_specs=act_specs,
                return_hidden=True, **extras,
            )
            unembed = params.get("unembed", params["embed"].T)
            with jax.named_scope(OD.LOSS):
                loss = chunked_cross_entropy(
                    hidden, unembed, batch["labels"], cfg.vocab, options.ce_chunk)
        else:
            logits, aux = model.forward(
                cfg, params, batch["tokens"], remat=options.remat,
                use_kernel=options.use_kernel, act_specs=act_specs, **extras,
            )
            with jax.named_scope(OD.LOSS):
                loss = cross_entropy(logits, batch["labels"])
        return loss + cfg.moe_aux_weight * aux["balance"], (loss, aux)

    return loss_fn


def _step_out(cfg: ArchConfig, state: opt.AdamWState, loss, aux, m):
    """The step's new optimizer state and metrics: the model's buffers move
    by the step (the correction biases by each expert's load), and where the
    model routes, the metrics carry that load per sparse layer."""
    with jax.named_scope(OD.OPTIMIZER):
        state = state._replace(buffers=transformer.update_buffers(cfg, state.buffers, aux))
    metrics = {"loss": loss, "aux": aux["balance"], **m}
    if aux["load"] is not None:
        metrics["load"] = aux["load"]
    return state, metrics


def chunked_cross_entropy(hidden, unembed, labels, vocab: int, chunk: int):
    """CE without materializing the full (tokens, V) logits: scan over
    sequence chunks, each chunk computes its own unembed matmul + loss sum.
    The chunk loop is rematerialized in the backward pass."""
    b, s, d = hidden.shape
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    nc = hidden.shape[1] // chunk
    hc = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)  # (nc, b, chunk, d)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)
    valid_per_chunk = jnp.full((nc,), b * chunk, jnp.float32)
    if pad:
        valid_per_chunk = valid_per_chunk.at[-1].set(b * (chunk - pad))

    def body(acc, inp):
        h, lab, ci = inp
        logits = jnp.einsum("bsd,dv->bsv", h, unembed).astype(jnp.float32)
        if logits.shape[-1] != vocab:
            keep = jnp.arange(logits.shape[-1]) < vocab
            logits = jnp.where(keep, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(lab, logits.shape[-1], dtype=logits.dtype)
        ll = lse - jnp.sum(logits * onehot, axis=-1)
        # mask padded positions in the last chunk
        spos = jnp.arange(h.shape[1])
        mask = (ci * chunk + spos) < s if pad else jnp.ones_like(spos, bool)
        return acc + jnp.sum(ll * mask[None, :]), None

    from jax import lax

    total, _ = lax.scan(jax.checkpoint(body), jnp.float32(0.0),
                        (hc, lc, jnp.arange(nc)))
    return total / (b * s)


def make_train_step(cfg: ArchConfig, ocfg: opt.AdamWConfig, options: TrainOptions,
                    policy: Policy, mesh=None, act_specs=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""
    loss_fn = make_loss_fn(cfg, options, act_specs=act_specs)

    if options.sync == "auto":

        def train_step(params, opt_state, batch):
            (tot, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, opt_state.buffers
            )
            with jax.named_scope(OD.OPTIMIZER):
                new_params, new_state, m = opt.apply(ocfg, opt_state, params, grads)
            new_state, metrics = _step_out(cfg, new_state, loss, aux, m)
            return new_params, new_state, metrics

        return train_step

    # --- paper-collective mode: manual data axes, auto model axis ----------
    assert mesh is not None
    data_axes = policy.data_axes
    dp_shape = tuple(mesh.shape[a] for a in data_axes)
    algo = options.sync
    # inside the manual region, activation anchors may only reference the
    # remaining *auto* axes — strip the (manual) data axes from the specs.
    if act_specs:
        from jax.sharding import NamedSharding, PartitionSpec as P_

        def strip(ns):
            if not hasattr(ns, "spec"):
                return ns
            parts = []
            for entry in ns.spec:
                if entry is None:
                    parts.append(None)
                elif isinstance(entry, tuple):
                    kept = tuple(a for a in entry if a not in data_axes)
                    parts.append(kept if kept else None)
                else:
                    parts.append(None if entry in data_axes else entry)
            return NamedSharding(ns.mesh, P_(*parts))

        inner_act_specs = {k: strip(v) for k, v in act_specs.items()}
        loss_fn = make_loss_fn(cfg, options, act_specs=inner_act_specs)

    def synced_grads(params, batch, buffers):
        """Runs on one data shard (manual); model axis stays auto."""
        (tot, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, buffers
        )
        axes = data_axes if len(data_axes) > 1 else (data_axes[0],)
        with jax.named_scope(OD.GRAD_SYNC):
            if options.compress_k:
                from repro.core import compression as comp

                def sync_leaf(g):
                    st = comp.init_state(g)  # stateless variant: residual dropped
                    out, _ = comp.sparse_allreduce(
                        g.astype(jnp.float32), st, options.compress_k, axes[0]
                    )
                    return (out / dp_total(axes)).astype(g.dtype)

                grads = jax.tree.map(sync_leaf, grads)
            elif len(axes) == 1:
                grads = coll.allreduce_tree(grads, algo, axes, None, mean=True)
            else:
                grads = coll.allreduce_tree(grads, algo, axes, dp_shape, mean=True)
            loss = jax.lax.pmean(loss, axes)
            load = aux["load"]
            aux = {"balance": jax.lax.pmean(aux["balance"], axes),
                   "load": None if load is None else jax.lax.psum(load, axes)}
        return grads, loss, aux

    def dp_total(axes):
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n

    def train_step(params, opt_state, batch):
        grads_fn = jax.shard_map(
            synced_grads,
            mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: P(policy.dp), batch), P()),
            out_specs=(P(), P(), P()),
            axis_names=set(data_axes),
            check_vma=False,
        )
        grads, loss, aux = grads_fn(params, batch, opt_state.buffers)
        with jax.named_scope(OD.OPTIMIZER):
            new_params, new_state, m = opt.apply(ocfg, opt_state, params, grads)
        new_state, metrics = _step_out(cfg, new_state, loss, aux, m)
        return new_params, new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ArchConfig, options: TrainOptions, act_specs=None):
    model = get_model(cfg)

    def prefill_step(params, batch, buffers=None):
        extras = {"buffers": buffers}
        if "positions" in batch:
            extras["positions"] = batch["positions"]
        if "encoder_frames" in batch:
            extras["encoder_frames"] = batch["encoder_frames"]
        logits, _ = model.forward(
            cfg, params, batch["tokens"], remat=options.remat,
            use_kernel=options.use_kernel, act_specs=act_specs, **extras,
        )
        return logits[:, -1:]

    return prefill_step


def make_decode_step(cfg: ArchConfig):
    model = get_model(cfg)

    def serve_step(params, cache, tokens, buffers=None):
        logits, new_cache = model.decode_step(cfg, params, cache, tokens, buffers=buffers)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], new_cache

    return serve_step
