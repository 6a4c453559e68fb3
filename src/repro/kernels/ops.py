"""Public jit'd wrappers around the Pallas kernels.

Each wrapper handles flattening/padding to the (rows, 1024)-lane layout the
kernels tile over and reduces kernel partials to the user-facing result.
``_interpret()`` is the one switch that picks the kernels' mode: compiled
on a TPU backend, the Pallas interpreter on any other. The kernel
signatures take ``interpret`` without a default, so every caller goes
through this switch or states its mode.

HBM-pass accounting
-------------------
The 3SFC encoder is memory-bound end to end (arithmetic intensity ~0.25
FLOP/byte), so the unit of cost here is *passes over the gradient tree*
(d floats, f32):

* ``tree_fused_stats(a, b)`` — ONE pass: reads a once and b once (2d·4
  bytes) and returns all three partials ``(a·b, ||a||², ||b||²)``. The
  naive route (``tree_dot`` + two ``tree_sqnorm``/norms, as in a separate
  dot + norm + norm cosine) reads each tree twice — 4d·4 bytes, i.e. 2×
  the traffic — and a dot/sqnorm/cosine *sequence* as in the seed encoder
  totalled ~8 passes plus a materialized s·∇F tree.
* ``tree_ef_update(u, d, s)`` — ONE streaming pass for ``e' = u − s·d``
  (read u, read d, write e'): never materializes ``s·d`` or the recon tree.

Both stream pytree *leaves* through the kernels in lockstep chunks — there
is no monolithic ``jnp.concatenate`` of the whole tree, only bounded
per-chunk concats of adjacent small leaves (large leaves are sliced, never
copied whole), with the tail tile zero-padded (zeros are exact identities
for every partial).
"""
from __future__ import annotations

import functools
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ef_update import ef_update_2d
from repro.kernels.fused_cosine import ACC_BYTES, fused_cosine_2d
from repro.kernels.sign_quant import sign_quant_2d
from repro.kernels.ssd_chunk import ssd_chunk_call
from repro.kernels.topk_mask import topk_mask_2d

PyTree = Any

LANES = 1024

# Per-chunk element budget for the tree-streaming reductions: 4 Mi elems =
# 16 MiB f32 per operand — big enough to amortize kernel launches, small
# enough that the lockstep chunk concat never approaches a whole-tree copy.
TREE_CHUNK_ELEMS = 1 << 22


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _plan_rows(n: int, block_rows: int) -> Tuple[int, int]:
    """(block_rows', rows) covering n elems with minimal zero padding.

    Scans the 8-row-aligned block sizes (f32 sublane alignment for TPU) up
    to the requested ``block_rows`` and picks the one whose row count pads
    least, tie-breaking toward the largest block (fewer grid steps, bigger
    DMAs). The br=8 candidate caps padding at <8 rows (<32 KiB/operand) per
    call, so the accounting stays within ~1 tile of the 2d·4-byte ideal.
    """
    rows_needed = max(1, -(-n // LANES))
    if rows_needed <= 8:
        return 8, 8   # f32 min tile is (8, 128) sublanes×lanes — never go below
    best_br, best_rows = 8, -(-rows_needed // 8) * 8
    for br in range(16, block_rows + 1, 8):
        rows = -(-rows_needed // br) * br
        if rows <= best_rows:
            best_br, best_rows = br, rows
    return best_br, best_rows


def _to_2d(v: jax.Array, block_rows: int) -> Tuple[jax.Array, int]:
    """Flatten + zero-pad to (rows, LANES), rows % block_rows == 0."""
    n = v.size
    tile = block_rows * LANES
    rows = max(1, -(-n // tile)) * block_rows
    pad = rows * LANES - n
    v2 = jnp.pad(v.reshape(-1), (0, pad)).reshape(rows, LANES)
    return v2, n


# ---------------------------------------------------------------------------
# fused_cosine
# ---------------------------------------------------------------------------


def fused_cosine(x: jax.Array, y: jax.Array, block_rows: int = 128) -> jax.Array:
    """(3,) f32 = [x·y, ||x||², ||y||²] over flat views of x, y."""
    br, _ = _plan_rows(x.size, block_rows)
    x2, _ = _to_2d(x, br)
    y2, _ = _to_2d(y, br)
    return fused_cosine_2d(x2, y2, block_rows=br, interpret=_interpret())


# ---------------------------------------------------------------------------
# tree_fused_stats — the fused tree-reduction engine
# ---------------------------------------------------------------------------


def _ravel_f32(leaf: jax.Array) -> jax.Array:
    return jnp.ravel(leaf).astype(jnp.float32)


def _cat(parts: List[jax.Array]) -> jax.Array:
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _check_lockstep(a_tree: PyTree, b_tree: PyTree) -> Tuple[list, list]:
    """Trace-time guard: lockstep streaming silently mis-pairs trees whose
    structures or leaf shapes differ (zero padding hides length mismatches),
    so reject both loudly — matching the old tree_map-based reductions'
    behavior. Returns the two leaf lists."""
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


def _chunk_plan(sizes: List[int], chunk_elems: int) -> List[List[Tuple[int, int, int]]]:
    """Chunking plan: a list of chunks, each a list of (leaf_idx, off, take).

    Leaf sizes are static, so the plan is resolved at trace time: small
    adjacent leaves are packed into one chunk (bounded concat), leaves
    larger than ``chunk_elems`` are walked by static slices (no whole-leaf
    copy). The SINGLE source of truth for how the tree streamers below pack
    leaves — ``tree_stats_hbm_bytes`` accounts from this same plan, so the
    benchmark's byte numbers cannot drift from the kernels' actual tiling.
    """
    plan: List[List[Tuple[int, int, int]]] = []
    cur: List[Tuple[int, int, int]] = []
    n = 0
    for i, size in enumerate(sizes):
        off = 0
        while size - off > 0:
            take = min(chunk_elems - n, size - off)
            cur.append((i, off, take))
            n += take
            off += take
            if n == chunk_elems:
                plan.append(cur)
                cur, n = [], 0
    if cur:
        plan.append(cur)
    return plan


def _gather_chunk(leaves_1d: List[jax.Array],
                  chunk: List[Tuple[int, int, int]]) -> jax.Array:
    parts = []
    for i, off, take in chunk:
        v = leaves_1d[i]
        parts.append(v if (off == 0 and take == v.size)
                     else jax.lax.slice_in_dim(v, off, off + take))
    return _cat(parts)


def _tree_dot_naive(a: PyTree, b: PyTree) -> jax.Array:
    """Leafwise f32 dot (differentiable; used only in the stats JVP rule)."""
    parts = jax.tree_util.tree_map(
        lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)), a, b
    )
    leaves = jax.tree_util.tree_leaves(parts)
    return sum(leaves) if leaves else jnp.zeros((), jnp.float32)


@jax.custom_jvp
def tree_fused_stats(a_tree: PyTree, b_tree: PyTree) -> jax.Array:
    """(3,) f32 = [a·b, ||a||², ||b||²] over whole pytrees in ONE HBM pass.

    Streams lockstep leaf chunks through the ``fused_cosine_2d`` Pallas
    kernel (interpret mode off-TPU) and accumulates the (3,) partials in
    f32. Zero-padding of each chunk's tail tile is exact (zeros contribute
    nothing to any of the three sums). Mixed-dtype trees are cast to f32
    leaf-by-leaf; a/b must share treedef and leaf shapes.

    Differentiable to arbitrary order: the custom JVP routes tangents
    through plain leafwise reductions (the Pallas primal has no AD rule),
    so ``jax.grad``-of-``jax.grad`` encoder objectives work unchanged.
    """
    a_leaves, b_leaves = _check_lockstep(a_tree, b_tree)
    ra = [_ravel_f32(l) for l in a_leaves]
    rb = [_ravel_f32(l) for l in b_leaves]
    total = jnp.zeros((3,), jnp.float32)
    for chunk in _chunk_plan([v.size for v in ra], TREE_CHUNK_ELEMS):
        total = total + fused_cosine(_gather_chunk(ra, chunk),
                                     _gather_chunk(rb, chunk))
    return total


@tree_fused_stats.defjvp
def _tree_fused_stats_jvp(primals, tangents):
    a, b = primals
    da, db = tangents
    out = tree_fused_stats(a, b)
    tan = jnp.stack([
        _tree_dot_naive(da, b) + _tree_dot_naive(a, db),
        2.0 * _tree_dot_naive(a, da),
        2.0 * _tree_dot_naive(b, db),
    ])
    return out, tan


def tree_stats_hbm_bytes(tree: PyTree, block_rows: int = 128) -> int:
    """Static HBM bytes ``tree_fused_stats`` touches for this tree pair.

    Not a measurement: the Pallas grid DMAs exactly two (block_rows, LANES)
    f32 tiles per step plus one (3, 8, 128) f32 accumulator write per call
    (``fused_cosine.ACC_BYTES``) — the traffic is fixed by
    the BlockSpecs, so it can be accounted from the chunk plan alone. Used
    by ``benchmarks/bench_kernels.py``; XLA ``cost_analysis`` cannot see
    through the interpret-mode callback, and on CPU it charges every
    unfused elementwise intermediate, so this is the apples-to-apples
    "bytes the kernel reads on TPU" number.
    """
    sizes = [int(np.prod(jnp.shape(l))) for l in jax.tree_util.tree_leaves(tree)]
    total = 0
    for chunk in _chunk_plan(sizes, TREE_CHUNK_ELEMS):
        n = sum(take for _, _, take in chunk)
        _, rows = _plan_rows(n, block_rows)
        total += 2 * rows * LANES * 4 + ACC_BYTES   # two operand tiles + acc
    return total


def tree_ef_update(u_tree: PyTree, d_tree: PyTree, s: jax.Array) -> PyTree:
    """EF residual e' = u − s·d over whole pytrees, one streaming pass.

    Streams the same lockstep leaf chunks as ``tree_fused_stats`` through
    the ``ef_update_2d`` Pallas kernel (one launch per ~16 MiB chunk, not
    per leaf — bias/scale leaves don't each pay a padded tile) and slices
    the outputs back into leaves. Never materializes the scaled ``s·d``
    (= recon) tree. Output leaves are f32 in u's shapes. Not differentiable
    (EF state updates sit outside autodiff).
    """
    u_leaves, d_leaves = _check_lockstep(u_tree, d_tree)
    treedef = jax.tree_util.tree_structure(u_tree)
    ru = [_ravel_f32(l) for l in u_leaves]
    rd = [_ravel_f32(l) for l in d_leaves]
    pieces: List[List[jax.Array]] = [[] for _ in u_leaves]
    for chunk in _chunk_plan([v.size for v in ru], TREE_CHUNK_ELEMS):
        out = ef_update(_gather_chunk(ru, chunk), _gather_chunk(rd, chunk), s)
        pos = 0
        for i, off, take in chunk:
            pieces[i].append(jax.lax.slice_in_dim(out, pos, pos + take))
            pos += take
    new_leaves = [
        (_cat(ps) if ps else jnp.zeros((0,), jnp.float32)).reshape(jnp.shape(l))
        for ps, l in zip(pieces, u_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def cosine_similarity(x: jax.Array, y: jax.Array, eps: float = 1e-12) -> jax.Array:
    d, xx, yy = fused_cosine(x, y)
    return d / (jnp.sqrt(xx) * jnp.sqrt(yy) + eps)


def optimal_scale(target: jax.Array, direction: jax.Array, eps: float = 1e-12) -> jax.Array:
    """3SFC Eq. 8: s = <target, dir> / ||dir||² in one pass."""
    d, _, yy = fused_cosine(target, direction)
    return d / (yy + eps)


# ---------------------------------------------------------------------------
# ef_update
# ---------------------------------------------------------------------------


def ef_update(u: jax.Array, d: jax.Array, s: jax.Array,
              block_rows: int = 256) -> jax.Array:
    """e' = u - s·d, elementwise fused; returns u's shape, f32."""
    br, _ = _plan_rows(u.size, block_rows)
    u2, n = _to_2d(u, br)
    d2, _ = _to_2d(d, br)
    out = ef_update_2d(u2, d2, s, block_rows=br, interpret=_interpret())
    return out.reshape(-1)[:n].reshape(u.shape)


# ---------------------------------------------------------------------------
# sign_quant
# ---------------------------------------------------------------------------


def sign_quant(x: jax.Array, block_rows: int = 256) -> Tuple[jax.Array, jax.Array]:
    """(signs int8 of x's shape, scale = mean|x|)."""
    x2, n = _to_2d(x, block_rows)
    signs2, asum = sign_quant_2d(x2, block_rows=block_rows, interpret=_interpret())
    signs = signs2.reshape(-1)[:n].reshape(x.shape)
    return signs, asum[0, 0] / n


# ---------------------------------------------------------------------------
# topk_mask (threshold select)
# ---------------------------------------------------------------------------


def topk_threshold(x: jax.Array, k: int, sample: int = 65536) -> jax.Array:
    """Sampled threshold estimate: |x| of the ~k-th largest (DGC-style)."""
    v = jnp.abs(x.reshape(-1))
    n = v.size
    if n <= sample:
        kk = max(1, min(k, n))
        return jax.lax.top_k(v, kk)[0][-1]
    stride = n // sample
    sub = v[:: stride][:sample]
    kk = max(1, min(int(round(k * sub.size / n)), sub.size))
    return jax.lax.top_k(sub, kk)[0][-1]


def topk_mask(x: jax.Array, threshold: jax.Array,
              block_rows: int = 256) -> Tuple[jax.Array, jax.Array]:
    """(masked f32 of x's shape, kept count)."""
    x2, n = _to_2d(x, block_rows)
    # guard: padding zeros must never pass the threshold
    t = jnp.maximum(threshold, jnp.float32(1e-38))
    out2, cnt = topk_mask_2d(x2, t, block_rows=block_rows, interpret=_interpret())
    return out2.reshape(-1)[:n].reshape(x.shape), cnt[0, 0]


# ---------------------------------------------------------------------------
# ssd_chunk (used by models.ssm when use_pallas=True; oracle: models.ssm.ssd_scan)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ssd_chunked_ad(xdt: jax.Array, dA: jax.Array, Bc: jax.Array, Cc: jax.Array,
                   chunk: int, h0: jax.Array):
    """Differentiable wrapper: forward through the Pallas kernel, backward
    through the jnp oracle's VJP (forward parity is asserted in
    tests/test_kernels.py, so the cotangents are consistent). NOTE:
    ``custom_vjp`` has no JVP rule — the 3SFC grad-of-grad encoder must use
    the pure-jnp path (use_pallas_ssd stays False for training entries)."""
    return ssd_chunked(xdt, dA, Bc, Cc, chunk, h0)


def _ssd_ad_fwd(xdt, dA, Bc, Cc, chunk, h0):
    out = ssd_chunked(xdt, dA, Bc, Cc, chunk, h0)
    return out, (xdt, dA, Bc, Cc, h0)


def _ssd_ad_bwd(chunk, res, ct):
    from repro.models.ssm import ssd_scan
    xdt, dA, Bc, Cc, h0 = res
    _, vjp = jax.vjp(lambda a, b, c, d, h: ssd_scan(a, b, c, d, chunk, h),
                     xdt, dA, Bc, Cc, h0)
    return vjp(ct)


ssd_chunked_ad.defvjp(_ssd_ad_fwd, _ssd_ad_bwd)


def ssd_chunked(xdt: jax.Array, dA: jax.Array, Bc: jax.Array, Cc: jax.Array,
                chunk: int, h0: jax.Array = None):
    """Same contract as models.ssm.ssd_scan, but the intra-chunk math runs in
    the Pallas kernel. xdt (b,s,h,p); dA (b,s,h); B,C (b,s,n)."""
    b, s, h, pdim = xdt.shape
    n = Bc.shape[-1]
    Q = min(chunk, s)
    assert s % Q == 0
    nc = s // Q
    # kernel layout: (b, h, nc, Q, ...)
    xk = jnp.moveaxis(xdt.reshape(b, nc, Q, h, pdim), 3, 1)       # (b,h,nc,Q,P)
    dAk = jnp.moveaxis(dA.reshape(b, nc, Q, h), 3, 1)             # (b,h,nc,Q)
    Bk = Bc.reshape(b, nc, Q, n)
    Ck = Cc.reshape(b, nc, Q, n)
    y_diag, states, decay = ssd_chunk_call(
        xk.astype(jnp.float32), dAk.astype(jnp.float32),
        Bk.astype(jnp.float32), Ck.astype(jnp.float32), interpret=_interpret())
    # inter-chunk recurrence (tiny, sequential)
    chunk_decay = decay[..., -1]                                   # (b,h,nc)
    if h0 is None:
        h0 = jnp.zeros((b, h, pdim, n), jnp.float32)

    def step(carry, inp):
        st, dec = inp
        return st + dec[..., None, None] * carry, carry

    sts = jnp.moveaxis(states, 2, 0)                               # (nc,b,h,P,N)
    dcs = jnp.moveaxis(chunk_decay, 2, 0)                          # (nc,b,h)
    final, prev = jax.lax.scan(step, h0.astype(jnp.float32), (sts, dcs))
    prev = jnp.moveaxis(prev, 0, 2)                                # (b,h,nc,P,N)
    y_off = jnp.einsum("bcqn,bhcpn,bhcq->bhcqp",
                       Ck.astype(jnp.float32), prev, decay)
    y = y_diag + y_off                                             # (b,h,nc,Q,P)
    y = jnp.moveaxis(y, 1, 3).reshape(b, s, h, pdim)
    return y.astype(xdt.dtype), final.astype(xdt.dtype)
