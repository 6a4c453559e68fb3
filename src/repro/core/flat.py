"""Pytree <-> flat-vector utilities and tree algebra.

Compressors in this package operate on *flat* float32 vectors — the
concatenation of every leaf of the gradient pytree. ``Flattener`` records
shapes/dtypes once so compress/decompress round-trips are exact.

The tree algebra works leaf by leaf in each leaf's own shape and never
ravels, concatenates or pads a leaf: on a TPU, raveling a tiled leaf is
itself a relayout copy. The 3SFC encoder is memory-bound end to end
(arithmetic intensity ~0.25 FLOP/byte), so the unit of cost is *passes
over the gradient tree* (d floats, f32):

* ``tree_stats(a, b)`` — ONE pass: reads each leaf of a and of b once
  (2d·4 bytes) and returns all three partials ``(a·b, ||a||², ||b||²)``,
  one multi-output reduction per leaf pair. A separate dot + two norms
  reads each tree twice, and the seed encoder's dot/sqnorm/cosine
  sequence totalled ~8 passes plus a materialized s·∇F tree.
* ``tree_ef_update(u, d, s)`` — ONE pass for ``e' = u − s·d`` (read u,
  read d, write e'), one elementwise fusion per leaf; it never
  materializes ``s·d`` or the recon tree.
"""
from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


class Flattener:
    """Round-trippable pytree <-> 1-D float32 vector mapping.

    The mapping is static (shapes/dtypes/treedef captured at construction),
    so ``flatten``/``unflatten`` are jit-safe closures.
    """

    def __init__(self, tree: PyTree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        self.treedef = treedef
        self.shapes: List[Tuple[int, ...]] = [jnp.shape(l) for l in leaves]
        self.dtypes = [jnp.result_type(l) for l in leaves]
        self.sizes = [int(np.prod(s)) if len(s) else 1 for s in self.shapes]
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)]).tolist()
        self.total = int(self.offsets[-1])

    def flatten(self, tree: PyTree) -> jax.Array:
        leaves = jax.tree_util.tree_leaves(tree)
        return jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves]
        ) if leaves else jnp.zeros((0,), jnp.float32)

    def unflatten(self, vec: jax.Array) -> PyTree:
        leaves = []
        for shape, dtype, off, size in zip(
            self.shapes, self.dtypes, self.offsets[:-1], self.sizes
        ):
            chunk = jax.lax.dynamic_slice_in_dim(vec, off, size)
            leaves.append(chunk.reshape(shape).astype(dtype))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


# --- tree algebra (used where flattening would force a big concat) ---------


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x * s, a)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y, leafwise."""
    return jax.tree_util.tree_map(lambda xi, yi: alpha * xi + yi, x, y)


def _check_lockstep(a_tree: PyTree, b_tree: PyTree) -> Tuple[list, list]:
    """Trace-time guard: pairing leaves of trees whose structures or leaf
    shapes differ would silently mis-pair or broadcast them, so reject both
    loudly. Returns the two leaf lists."""
    a_leaves, a_def = jax.tree_util.tree_flatten(a_tree)
    b_leaves, b_def = jax.tree_util.tree_flatten(b_tree)
    if a_def != b_def:
        raise ValueError(
            f"lockstep tree mismatch: treedefs {a_def} vs {b_def}")
    a_shapes = [jnp.shape(l) for l in a_leaves]
    b_shapes = [jnp.shape(l) for l in b_leaves]
    if a_shapes != b_shapes:
        raise ValueError(
            f"lockstep tree mismatch: leaf shapes {a_shapes} vs {b_shapes}")
    return a_leaves, b_leaves


def _leaf_stats(x: jax.Array, y: jax.Array) -> jax.Array:
    """(3,) f32 = [x·y, ||x||², ||y||²] over all axes of one leaf pair.

    Elementwise products summed in f32, not ``jnp.dot``: a dot at default
    precision runs in bf16 on a TPU. The three sums share their operands,
    so XLA makes them one multi-output reduction that reads each leaf once.
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    return jnp.stack([jnp.sum(x * y), jnp.sum(x * x), jnp.sum(y * y)])


def tree_stats(a: PyTree, b: PyTree) -> jax.Array:
    """(3,) f32 = [a·b, ||a||², ||b||²] over whole pytrees, ONE HBM pass.

    The primitive every pair reduction below dispatches through: each leaf
    pair is reduced in its own shape (``_leaf_stats``) and the leaves'
    triples are added. Mixed-dtype trees are cast to f32 leaf by leaf; a
    and b must share treedef and leaf shapes. Plain XLA, so differentiable
    to any order (the 3SFC encoder takes grad-of-grad through it) and safe
    under jit/vmap.
    """
    a_leaves, b_leaves = _check_lockstep(a, b)
    total = jnp.zeros((3,), jnp.float32)
    for x, y in zip(a_leaves, b_leaves):
        total = total + _leaf_stats(x, y)
    return total


def tree_ef_update(u: PyTree, d: PyTree, s: jax.Array) -> PyTree:
    """EF residual e' = u − s·d over whole pytrees, one pass.

    One fused elementwise op per leaf, in the leaf's own shape: reads u and
    d once, writes e', and never materializes the scaled ``s·d`` (= recon)
    tree. Output leaves are f32 in u's shapes.
    """
    u_leaves, d_leaves = _check_lockstep(u, d)
    s = jnp.asarray(s, jnp.float32)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(u),
        [ui.astype(jnp.float32) - s * di.astype(jnp.float32)
         for ui, di in zip(u_leaves, d_leaves)])


def tree_dot(a: PyTree, b: PyTree) -> jax.Array:
    """Sum of elementwise products over all leaves, accumulated in f32."""
    return tree_stats(a, b)[0]


def tree_sqnorm(a: PyTree) -> jax.Array:
    # Deliberately NOT routed through the pair reduction: a single-tree sum
    # of squares is already one pass, with one sum per leaf, not three.
    parts = jax.tree_util.tree_map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), a
    )
    leaves = jax.tree_util.tree_leaves(parts)
    return sum(leaves) if leaves else jnp.zeros((), jnp.float32)


def tree_norm(a: PyTree) -> jax.Array:
    return jnp.sqrt(tree_sqnorm(a))


def tree_cosine(a: PyTree, b: PyTree, eps: float = 1e-12) -> jax.Array:
    """cos(a, b) from the fused stats triple — one pass over each tree
    (the naive dot + norm + norm route reads each tree twice)."""
    d, aa, bb = tree_stats(a, b)
    return d / (jnp.sqrt(aa) * jnp.sqrt(bb) + eps)


def tree_zeros_like(a: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.zeros_like, a)


def tree_size(a: PyTree) -> int:
    """Total number of scalars in the tree (static)."""
    return sum(int(np.prod(jnp.shape(l)) or 1) for l in jax.tree_util.tree_leaves(a))
