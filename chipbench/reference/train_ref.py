"""The reference's first training steps: losses, the first gradient's norm
per leaf and each leaf's change after the steps.

It makes its own weights and rows from the seed and runs on one device, on
the whole global batch.  ``dtype`` and ``precision`` give the control
(bfloat16 for the float32 the training configuration states); ``fault``
plants one of the faults the comparison must catch: ``half_batch`` (the
gradient and loss of the first half of the rows only) and ``no_exchange``
(the gradient of the first chip's share of the rows only).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common
from chipbench.reference import decoder as D
from chipbench.reference import weights as W


def leaf_norms(tree: dict) -> dict:
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for n, x in tree.items()}


def change_norms(dims: D.Dims, key, params: dict, dtype) -> dict:
    """||params - initial|| per leaf, the initial weights made again."""
    return {n: jnp.sqrt(jnp.sum(jnp.square(params[n].astype(jnp.float32)
                                           - D.make_leaf(key, dims, n, dtype))))
            for n in params}


def run(config: dict, job: dict, seed: int, *, steps: int = 3, dtype=jnp.float32,
        precision: str = "highest", fault: str | None = None, chips: int = 1,
        device=None) -> dict:
    dims = D.Dims.of(config)
    key = W.root_key(common.key_words(seed))
    batch, seq = job["batch"], job["seq"]
    grad_rows = {None: batch, "half_batch": batch // 2,
                 "no_exchange": batch // chips}[fault]
    loss_rows = batch // 2 if fault == "half_batch" else batch
    device = device or jax.devices()[0]

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, tokens, labels, lr, t):
        def loss_of(p, rows):
            return D.loss(dims, p, tokens[:rows], labels[:rows], dtype)

        loss, grads = jax.value_and_grad(loss_of)(params, grad_rows)
        if loss_rows != grad_rows:
            loss = loss_of(params, loss_rows)
        grads = {n: g.astype(jnp.float32) for n, g in grads.items()}
        params, m, v, clipped = D.adamw(job, lr, t, params, grads, m, v, dtype)
        return params, m, v, loss, leaf_norms(clipped)

    with jax.default_device(device), jax.default_matmul_precision(precision):
        params = jax.jit(lambda k: D.make_params(k, dims, dtype))(key)
        params = {n: p.astype(dtype) for n, p in params.items()}
        m = {n: jnp.zeros(p.shape, jnp.float32) for n, p in params.items()}
        v = {n: jnp.zeros(p.shape, jnp.float32) for n, p in params.items()}
        losses, first_grad = [], None
        for t in range(1, steps + 1):
            tokens, labels = common.train_rows(seed, t - 1, batch, seq, dims.vocab)
            params, m, v, loss, gnorms = step(params, m, v, tokens, labels,
                                              np.float32(D.lr_at(job, t)), np.float32(t))
            losses.append(float(loss))
            if first_grad is None:
                first_grad = {n: float(x) for n, x in gnorms.items()}
        del m, v
        change = jax.jit(lambda p: change_norms(dims, key, p, dtype))(params)
        return {"losses": losses, "grad_norms": first_grad,
                "change_norms": {n: float(x) for n, x in change.items()}}
