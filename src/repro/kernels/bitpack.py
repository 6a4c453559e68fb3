"""bitpack — 32→1 sign bit-packing kernel pair for the wire codec.

``repro.comm`` serializes signSGD's uplink as an actual bit stream (the
paper's "1 bit per coordinate" accounting, measured instead of assumed).
The hot operation is packing ``d`` float signs into ``ceil(d/32)`` uint32
words — a pure streaming transform, so it gets the same Pallas treatment as
the reduction engine: the pack kernel reads the float tile once and writes
the 32× smaller word tile, with no intermediate bool tensor in HBM (the
layout transpose below is one more XLA pass over the floats).

Layout (the wire contract): word lane ``w`` of a ``(rows, 128)`` word tile
packs float lanes ``[32w, 32w+32)`` of the ``(rows, 4096)`` tile LSB-first,
so flat element ``n`` lands in word ``n // 32`` bit ``n % 32``.

The kernels work in int32 (the TPU has no unsigned reductions or
unsigned-to-float casts); the words are bitcast to uint32 at the edges.
A TPU kernel cannot split or merge 32-lane groups of a lane dimension, so
both kernels work on a bit-major ``(32, rows, 128)`` view: slab ``b`` holds
the floats of bit ``b`` of every word, and each kernel moves 32 aligned
(8, 128) tiles. ``pack_signs_2d``/``unpack_signs_2d`` convert between that
view and the ``(rows, 4096)`` wire order with one XLA transpose each.
Every tile respects the (8, 128) f32/i32 TPU min-tile; ``ops._interpret``
decides whether the kernels run compiled or interpreted.

Sign convention (the wire contract, shared with ``comm.codec``): bit =
``x >= 0``; unpacking yields ±1, never 0. Exact zeros therefore decode to
+1 — the codec documents this as the 1-bit wire semantics (a 3-valued sign
does not fit in 1 bit; see ``comm.codec.SignCodec``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops

PACK_LANES = 4096                    # f32 lanes per packed row
WORD_LANES = PACK_LANES // 32        # = 128, uint32 lanes per packed row
BLOCK_ROWS = 8                       # f32/u32 min sublane tile


def _pack_kernel(x_ref, out_ref):
    # x_ref: (32, br, 128) f32, slab b = the sources of bit b
    acc = (x_ref[0] >= 0).astype(jnp.int32)
    for b in range(1, 32):
        acc = acc | ((x_ref[b] >= 0).astype(jnp.int32) << b)
    out_ref[...] = acc


def _unpack_kernel(w_ref, out_ref):
    w = w_ref[...]                                       # (br, 128) int32
    for b in range(32):
        out_ref[b] = jnp.where(((w >> b) & 1) == 1, jnp.float32(1.0),
                               jnp.float32(-1.0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_signs_2d(x2: jax.Array, *, interpret: bool) -> jax.Array:
    """(rows, 4096) f32 -> (rows, 128) uint32; bit = (x >= 0), LSB-first."""
    rows = x2.shape[0]
    assert rows % BLOCK_ROWS == 0 and x2.shape[1] == PACK_LANES, x2.shape
    x3 = x2.reshape(rows, WORD_LANES, 32).transpose(2, 0, 1)   # bit-major
    words = pl.pallas_call(
        _pack_kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((32, BLOCK_ROWS, WORD_LANES),
                               lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, WORD_LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, WORD_LANES), jnp.int32),
        interpret=interpret,
    )(x3)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_signs_2d(w2: jax.Array, *, interpret: bool) -> jax.Array:
    """(rows, 128) uint32 -> (rows, 4096) f32 in {-1, +1}."""
    rows = w2.shape[0]
    assert rows % BLOCK_ROWS == 0 and w2.shape[1] == WORD_LANES, w2.shape
    pm1 = pl.pallas_call(
        _unpack_kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, WORD_LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((32, BLOCK_ROWS, WORD_LANES),
                               lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((32, rows, WORD_LANES), jnp.float32),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(w2, jnp.int32))
    return pm1.transpose(1, 2, 0).reshape(rows, PACK_LANES)


# ---------------------------------------------------------------------------
# flat-vector wrappers (padding + interpret dispatch)
# ---------------------------------------------------------------------------


def pack_signs(x: jax.Array) -> jax.Array:
    """Flat f32 (n,) -> uint32 (ceil(n/32),) sign words.

    The tail is padded with +1.0 (bit 1) up to the tile grid; padded bits
    live only in the final word(s) the caller slices away by byte count.
    """
    n = x.size
    words = -(-n // 32)
    tile = BLOCK_ROWS * PACK_LANES
    rows = max(1, -(-n // tile)) * BLOCK_ROWS
    x2 = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, rows * PACK_LANES - n),
                 constant_values=1.0).reshape(rows, PACK_LANES)
    packed = pack_signs_2d(x2, interpret=ops._interpret())
    return packed.reshape(-1)[:words]


def unpack_signs(words: jax.Array, n: int) -> jax.Array:
    """uint32 (ceil(n/32),) -> f32 (n,) in {-1, +1} (inverse of pack_signs)."""
    w = words.size
    assert w == -(-n // 32), (w, n)
    tile = BLOCK_ROWS * WORD_LANES
    rows = max(1, -(-w // tile)) * BLOCK_ROWS
    w2 = jnp.pad(words.reshape(-1), (0, rows * WORD_LANES - w)) \
        .reshape(rows, WORD_LANES)
    pm1 = unpack_signs_2d(w2, interpret=ops._interpret())
    return pm1.reshape(-1)[:n]
