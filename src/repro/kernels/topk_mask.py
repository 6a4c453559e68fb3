"""topk_mask — DGC sparsifier, TPU-native threshold-select form.

Exact global top-k is a sort (O(d log d), serial) — GPU-idiomatic, hostile
to the TPU. The DGC paper itself samples a threshold; we do the same
(ops.topk_threshold estimates tau from a strided sample with lax.top_k),
then this kernel does the single streaming pass: keep |x| >= tau, zero the
rest, count survivors (the count feeds budget accounting / tau refinement).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 1024
BLOCK_ROWS = 256


def _kernel(x_ref, t_ref, out_ref, cnt_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    x = x_ref[...].astype(jnp.float32)
    keep = jnp.abs(x) >= t_ref[0, 0]
    out_ref[...] = jnp.where(keep, x, 0.0)
    cnt_ref[0, 0] += jnp.sum(keep.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def topk_mask_2d(x2: jax.Array, threshold: jax.Array, *,
                 block_rows: int = BLOCK_ROWS, interpret: bool):
    rows = x2.shape[0]
    assert rows % block_rows == 0
    t2 = jnp.reshape(threshold.astype(jnp.float32), (1, 1))
    return pl.pallas_call(
        _kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, t2)
