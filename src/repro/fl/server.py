"""Server-side aggregation G(·) and global-model update (paper Eq. 3/4/6)."""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def _ordered_sum(tree: PyTree) -> PyTree:
    """Per leaf, the sum over the leading client axis as the chain
    ``((0 + x0) + x1) + ...``, accumulated in at least f32; one loop over
    the clients for the whole tree.

    A plain ``reduce`` lets the compiler order the adds by the operand's
    layout, and XLA:TPU lays out a shard_map-gathered array differently
    from the vmap one, so the two fan-outs would round differently. An
    unrolled chain of per-client slices is not safe either: XLA:CPU
    rounds the vmap round differently with it, even behind an
    ``optimization_barrier``. The loop's carry fixes the order on every
    backend."""
    acc = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape[1:],
                            jnp.promote_types(x.dtype, jnp.float32)), tree)
    return jax.lax.scan(
        lambda a, xi: (jax.tree_util.tree_map(jnp.add, a, xi), None),
        acc, tree)[0]


def client_sum(tree: PyTree) -> PyTree:
    """``jnp.sum(x, axis=0)`` per leaf, adding the clients in index order."""
    return jax.tree_util.tree_map(lambda s, x: s.astype(x.dtype),
                                  _ordered_sum(tree), tree)


def client_mean(tree: PyTree) -> PyTree:
    """``jnp.mean(x, axis=0)`` per leaf, adding the clients in index order."""
    return jax.tree_util.tree_map(
        lambda s, x: (s / x.shape[0]).astype(x.dtype),
        _ordered_sum(tree), tree)


def aggregate(recons: PyTree, weights: Optional[jax.Array] = None) -> PyTree:
    """G over the leading client axis: arithmetic mean or |D_i|-weighted."""
    if weights is None:
        return client_mean(recons)
    w = weights / jnp.sum(weights)
    return client_sum(jax.tree_util.tree_map(
        lambda x: w.reshape((-1,) + (1,) * (x.ndim - 1)) * x, recons))


def server_update(global_params: PyTree, agg_update: PyTree,
                  server_lr: float = 1.0) -> PyTree:
    """w^{t+1} = w^t - lr * G(...). agg_update carries the paper's g sign."""
    return jax.tree_util.tree_map(
        lambda p, u: (p.astype(jnp.float32) - server_lr * u.astype(jnp.float32)).astype(p.dtype),
        global_params, agg_update)
