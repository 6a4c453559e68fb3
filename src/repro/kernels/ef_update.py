"""ef_update — fused EF residual axpy: e' = u - s·d (paper Eq. 6 line 2).

One streaming pass: reads u, d tiles from HBM, writes e' tiles. Fusing the
scale-and-subtract avoids materializing s·d (one full extra HBM round-trip
over an O(d) buffer). The scalar s rides along as a (1, 1) block mapped to
every grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 1024
BLOCK_ROWS = 256


def _kernel(u_ref, d_ref, s_ref, o_ref):
    s = s_ref[0, 0]
    o_ref[...] = u_ref[...].astype(jnp.float32) - s * d_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ef_update_2d(u2: jax.Array, d2: jax.Array, s: jax.Array, *,
                 block_rows: int = BLOCK_ROWS, interpret: bool) -> jax.Array:
    rows = u2.shape[0]
    assert rows % block_rows == 0 and u2.shape == d2.shape
    s2 = jnp.reshape(s.astype(jnp.float32), (1, 1))
    return pl.pallas_call(
        _kernel,
        name="ef_update",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(u2.shape, jnp.float32),
        interpret=interpret,
    )(u2, d2, s2)
