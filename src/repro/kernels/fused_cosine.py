"""fused_cosine — one-HBM-pass (x·y, ||x||², ||y||²).

The 3SFC encoder's Eq. 8/9 needs three O(d) reductions over the same two
flat vectors. Done naively that is three HBM passes over 2·d floats; the
gradient trees here are up to ~10^10 elements, so the pass count IS the cost
(arithmetic intensity ≈ 0.25 FLOP/byte — deeply memory-bound). This kernel
computes all three partial sums per VMEM tile in a single pass.

Tiling: inputs are padded/reshaped to (rows, 1024) lanes (8·128-aligned);
each grid step streams a (BLOCK_ROWS, 1024) tile of x and y through VMEM
(2 × 512 KB) and folds each product tile into one (8, 128) vreg of partial
sums per statistic — elementwise adds only, no cross-lane reduction and no
scalar store. The (3, 8, 128) accumulator lives in the output block (same
block every step — the TPU grid is sequential, so this is the standard
Pallas reduction idiom); the wrapper sums its 1024 partials per statistic.

HBM-pass accounting
-------------------
Per call over d-element operands (f32):

    fused (this kernel) : read x once + read y once          = 2d·4 bytes
    unfused dot+norms   : x·y (2d), ||x||² (d), ||y||² (d)   = 4d·4 bytes
    seed encoder total  : dot + sqnorm + 2×cosine + recon    ≈ 8 passes

``benchmarks/bench_kernels.py`` measures this structurally via XLA
``cost_analysis`` bytes-accessed on the lowered reductions and records the
before/after numbers in ``BENCH_kernels.json``; ``ops.tree_fused_stats``
extends the same single-pass contract to whole gradient pytrees (chunked
leaf streaming, no monolithic concatenate).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 1024
BLOCK_ROWS = 128
ACC_SHAPE = (3, 8, 128)   # one f32 vreg of partial sums per statistic
ACC_BYTES = 3 * 8 * 128 * 4


def _fold(v: jax.Array) -> jax.Array:
    """(rows, LANES) -> (8, 128) partial sums, by vreg-aligned adds."""
    v = jnp.sum(v.reshape(-1, 8, LANES), axis=0)
    acc = v[:, :128]
    for j in range(1, LANES // 128):
        acc = acc + v[:, j * 128:(j + 1) * 128]
    return acc


def _kernel(x_ref, y_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    o_ref[0] += _fold(x * y)
    o_ref[1] += _fold(x * x)
    o_ref[2] += _fold(y * y)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_cosine_2d(x2: jax.Array, y2: jax.Array, *, block_rows: int = BLOCK_ROWS,
                    interpret: bool) -> jax.Array:
    """x2, y2: (rows, LANES) with rows % block_rows == 0. Returns (3,) f32."""
    rows = x2.shape[0]
    assert rows % block_rows == 0 and x2.shape == y2.shape
    grid = (rows // block_rows,)
    out = pl.pallas_call(
        _kernel,
        name="fused_cosine",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec(ACC_SHAPE, lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(ACC_SHAPE, jnp.float32),
        interpret=interpret,
    )(x2, y2)
    return jnp.sum(out, axis=(1, 2))
