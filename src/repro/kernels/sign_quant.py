"""sign_quant — signSGD compression: signs (int8 wire format) + mean-|x| scale.

TPU has no efficient 1-bit type; the wire format is *accounted* as
1 bit/coord (budget math in core/baselines.py) while the on-chip payload is
int8 — matching how an ICI/NCCL implementation would pack before the wire.
One pass emits the sign tile and accumulates sum|x| for the scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 1024
BLOCK_ROWS = 256


def _kernel(x_ref, sign_ref, acc_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    sign_ref[...] = jnp.sign(x).astype(jnp.int8)
    acc_ref[0, 0] += jnp.sum(jnp.abs(x))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sign_quant_2d(x2: jax.Array, *, block_rows: int = BLOCK_ROWS,
                  interpret: bool):
    """Returns (signs int8 (rows, LANES), sum|x| (1,1) f32)."""
    rows = x2.shape[0]
    assert rows % block_rows == 0
    return pl.pallas_call(
        _kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, jnp.int8),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2)
