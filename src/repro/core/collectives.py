"""HxMesh-aware collective algorithms in JAX (paper §V-A2).

The paper's allreduce algorithms, implemented with ``jax.lax.ppermute`` so
that every transfer is a *neighbor* transfer on a ring — exactly the traffic
HammingMesh (and TPU ICI) serves at full bandwidth:

* ``ring_allreduce``       — pipelined unidirectional ring, T ≈ 2pα + 2Sβ
* ``bidir_ring_allreduce`` — two half-size rings in opposite directions,
                             T ≈ 2pα + Sβ (§V-A2b)
* ``hamiltonian_allreduce``— two bidirectional rings on *edge-disjoint
                             Hamiltonian cycles* of the 2D device mesh, using
                             all four mesh-neighbor links, T ≈ 2pα + S/2·β
* ``torus_allreduce``      — row reduce-scatter → column allreduce → row
                             allgather, T ≈ 4√p·α + Sβ(1+2√p)/(4√p) (§V-A2c)

All functions run *inside* ``jax.shard_map``.  ``allreduce_tree`` wraps a
gradient pytree: flatten → bucket → allreduce → unflatten, the paper's
overlapped-groups scheme (§V-B2).

Algorithm selection (paper Fig 13: "multi-algorithms should be used") is in
``select_algorithm`` via the α-β models of :mod:`repro.core.commodel`.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import commodel
from repro.core import hamiltonian as ham


AxisName = str | tuple[str, ...]


def _ring_perm(p: int, reverse: bool = False) -> list[tuple[int, int]]:
    if reverse:
        return [(i, (i - 1) % p) for i in range(p)]
    return [(i, (i + 1) % p) for i in range(p)]


def _chunked(x: jax.Array, p: int) -> tuple[jax.Array, int]:
    """Flatten and pad x to (p, m) chunks."""
    flat = x.reshape(-1)
    pad = (-flat.size) % p
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(p, -1), pad


def _ring_reduce_scatter(
    chunks: jax.Array,
    rank: jax.Array,
    p: int,
    perm: Sequence[tuple[int, int]],
    axis: AxisName,
) -> jax.Array:
    """Pipelined reduce-scatter along an arbitrary ring.

    ``rank`` is this device's position in the ring (traced scalar).  Returns
    the fully reduced chunk with index ``(rank + 1) % p``.
    """

    def body(r, buf):
        buf = lax.ppermute(buf, axis, perm)
        ci = jnp.mod(rank - r - 1, p)
        return buf + lax.dynamic_index_in_dim(chunks, ci, axis=0, keepdims=False)

    init = lax.dynamic_index_in_dim(chunks, jnp.mod(rank, p), axis=0, keepdims=False)
    return lax.fori_loop(0, p - 1, body, init)


def _ring_all_gather(
    buf: jax.Array,
    rank: jax.Array,
    p: int,
    perm: Sequence[tuple[int, int]],
    axis: AxisName,
) -> jax.Array:
    """All-gather along a ring; ``buf`` is chunk ``(rank+1) % p``."""
    out = jnp.zeros((p,) + buf.shape, buf.dtype)
    out = _dyn_set(out, jnp.mod(rank + 1, p), buf)

    def body(r, carry):
        out, cur = carry
        cur = lax.ppermute(cur, axis, perm)
        ci = jnp.mod(rank - r, p)  # chunk owned by the (r+1)-hop predecessor
        out = _dyn_set(out, ci, cur)
        return out, cur

    out, _ = lax.fori_loop(0, p - 1, body, (out, buf))
    return out.reshape(-1)


def _dyn_set(out: jax.Array, i: jax.Array, val: jax.Array) -> jax.Array:
    return lax.dynamic_update_slice_in_dim(out, val[None], i, axis=0)


def _ring_allreduce_1d(
    x: jax.Array, axis: str, reverse: bool = False
) -> jax.Array:
    p = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    if reverse:
        rank = p - 1 - rank
    perm = _ring_perm(p, reverse)
    chunks, pad = _chunked(x, p)
    buf = _ring_reduce_scatter(chunks, rank, p, perm, axis)
    flat = _ring_all_gather(buf, rank, p, perm, axis)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(x.shape)


# ---------------------------------------------------------------------------
# Public algorithms (inside shard_map)
# ---------------------------------------------------------------------------


def ring_allreduce(x: jax.Array, axis: str) -> jax.Array:
    """Pipelined unidirectional ring allreduce (paper §V-A2b)."""
    return _ring_allreduce_1d(x, axis)


def ring_reduce_scatter(x: jax.Array, axis: str) -> jax.Array:
    """Reduce-scatter returning this device's chunk (index = axis_index)."""
    p = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    perm = _ring_perm(p)
    chunks, _ = _chunked(x, p)
    # shift rank so the owned chunk is exactly ``axis_index``
    buf = _ring_reduce_scatter(chunks, jnp.mod(rank - 1, p), p, perm, axis)
    return buf


def ring_all_gather(x: jax.Array, axis: str) -> jax.Array:
    """All-gather of per-device chunks (chunk index = axis_index)."""
    p = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    perm = _ring_perm(p)
    return _ring_all_gather(x, jnp.mod(rank - 1, p), p, perm, axis)


def bidir_ring_allreduce(x: jax.Array, axis: str) -> jax.Array:
    """Bidirectional ring: halves travel in opposite directions (§V-A2b)."""
    flat = x.reshape(-1)
    pad = (-flat.size) % 2
    if pad:
        flat = jnp.pad(flat, (0, pad))
    h0, h1 = jnp.split(flat, 2)
    r0 = _ring_allreduce_1d(h0, axis, reverse=False)
    r1 = _ring_allreduce_1d(h1, axis, reverse=True)
    out = jnp.concatenate([r0, r1])
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


def hamiltonian_allreduce(
    x: jax.Array, axes: tuple[str, str], mesh_shape: tuple[int, int]
) -> jax.Array:
    """Dual edge-disjoint Hamiltonian-cycle allreduce (§V-A2b, App. D).

    The 2D device mesh (axes[0] × axes[1]) is covered by two edge-disjoint
    Hamiltonian cycles (red/green); each carries half the data as a
    bidirectional ring → S/2 bytes per link direction, all four mesh
    directions busy. ``mesh_shape`` must be static.
    """
    r, c = mesh_shape
    p = r * c
    try:
        red, green = ham.dual_cycles(r, c)
    except ValueError:
        # no edge-disjoint pair (e.g. 2x2): as netsim does, run every quarter
        # over one Hamiltonian cycle, two in each direction
        red = green = ham.single_cycle(r, c)

    def mk(cycle):
        # device (i,j) -> rank in cycle; perm pairs over linearized (i*c+j)
        rank_tbl = np.zeros((r, c), dtype=np.int32)
        for k, (i, j) in enumerate(cycle):
            rank_tbl[i, j] = k
        perm = []
        for k, (i, j) in enumerate(cycle):
            ni, nj = cycle[(k + 1) % p]
            perm.append((i * c + j, ni * c + nj))
        rperm = [(b, a) for a, b in perm]
        return jnp.asarray(rank_tbl), perm, rperm

    rank_red, perm_red, rperm_red = mk(red)
    rank_green, perm_green, rperm_green = mk(green)

    i = lax.axis_index(axes[0])
    j = lax.axis_index(axes[1])
    kr = rank_red[i, j]
    kg = rank_green[i, j]

    flat = x.reshape(-1)
    pad = (-flat.size) % 4
    if pad:
        flat = jnp.pad(flat, (0, pad))
    quarters = jnp.split(flat, 4)

    outs = []
    for q, rank, perm, reverse in [
        (quarters[0], kr, perm_red, False),
        (quarters[1], kr, rperm_red, True),
        (quarters[2], kg, perm_green, False),
        (quarters[3], kg, rperm_green, True),
    ]:
        rk = jnp.mod(p - 1 - rank, p) if reverse else rank
        chunks, qpad = _chunked(q, p)
        buf = _ring_reduce_scatter(chunks, rk, p, perm, axes)
        full = _ring_all_gather(buf, rk, p, perm, axes)
        if qpad:
            full = full[:-qpad]
        outs.append(full)
    out = jnp.concatenate(outs)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


def torus_allreduce(
    x: jax.Array, row_axis: str, col_axis: str, dual: bool = True
) -> jax.Array:
    """2D-torus allreduce (paper §V-A2c).

    reduce-scatter along rows → allreduce along columns → allgather along
    rows.  With ``dual=True``, two transposed instances run on half the data
    each to use all four interfaces (the paper's 4-NIC variant).
    """

    def one(inp: jax.Array, ax0: str, ax1: str) -> jax.Array:
        p0 = lax.axis_size(ax0)
        rank0 = lax.axis_index(ax0)
        perm0 = _ring_perm(p0)
        chunks, pad0 = _chunked(inp, p0)
        buf = _ring_reduce_scatter(chunks, rank0, p0, perm0, ax0)
        buf = bidir_ring_allreduce(buf, ax1)
        flat = _ring_all_gather(buf, rank0, p0, perm0, ax0)
        if pad0:
            flat = flat[:-pad0]
        return flat

    if not dual:
        return one(x.reshape(-1), row_axis, col_axis).reshape(x.shape)
    flat = x.reshape(-1)
    pad = (-flat.size) % 2
    if pad:
        flat = jnp.pad(flat, (0, pad))
    h0, h1 = jnp.split(flat, 2)
    o0 = one(h0, row_axis, col_axis)
    o1 = one(h1, col_axis, row_axis)
    out = jnp.concatenate([o0, o1])
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


ALGORITHMS = ("psum", "ring", "bidir", "torus", "hamiltonian")


def allreduce(
    x: jax.Array,
    algorithm: str,
    axes: tuple[str, ...],
    mesh_shape: tuple[int, ...] | None = None,
) -> jax.Array:
    """Dispatch one of the paper's algorithms over 1 or 2 mesh axes."""
    if algorithm == "psum":
        return lax.psum(x, axes)
    if len(axes) == 1:
        if algorithm == "ring":
            return ring_allreduce(x, axes[0])
        if algorithm == "bidir":
            return bidir_ring_allreduce(x, axes[0])
        raise ValueError(f"{algorithm} needs a 2D mesh")
    ax0, ax1 = axes
    if algorithm == "ring":
        # ring over the row axis, then over the column axis (hierarchical)
        return ring_allreduce(ring_allreduce(x, ax0), ax1)
    if algorithm == "bidir":
        return bidir_ring_allreduce(bidir_ring_allreduce(x, ax0), ax1)
    if algorithm == "torus":
        return torus_allreduce(x, ax0, ax1)
    if algorithm == "hamiltonian":
        assert mesh_shape is not None, "hamiltonian needs static mesh_shape"
        return hamiltonian_allreduce(x, (ax0, ax1), mesh_shape)
    raise ValueError(f"unknown algorithm {algorithm!r}")


def select_algorithm(p: int, size_bytes: float) -> str:
    """Multi-algorithm selection from the α-β models (paper Fig 13)."""
    name, _ = commodel.best_algorithm(p, size_bytes)
    return {"ring": "ring", "bidir": "bidir", "hamiltonian": "hamiltonian",
            "torus": "torus"}[name]


# ---------------------------------------------------------------------------
# Gradient-pytree wrapper (outside shard_map)
# ---------------------------------------------------------------------------


def allreduce_tree(
    grads,
    algorithm: str,
    axes: tuple[str, ...],
    mesh_shape: tuple[int, ...] | None = None,
    mean: bool = True,
):
    """Allreduce a gradient pytree inside shard_map: flatten → concat →
    one bucketed collective → unflatten (the paper's grouped reduction)."""
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [l.size for l in leaves]
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    total = allreduce(flat, algorithm, axes, mesh_shape)
    if mean:
        n = 1
        for ax in axes:
            n *= lax.axis_size(ax)
        total = total / n
    out = []
    off = 0
    for size, shape, dtype in zip(sizes, shapes, dtypes):
        out.append(total[off : off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(treedef, out)
