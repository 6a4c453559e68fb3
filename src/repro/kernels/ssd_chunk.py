"""ssd_chunk — Mamba2 SSD intra-chunk kernel.

Per (batch, chunk, head) grid cell, all the dense intra-chunk work runs on
one VMEM-resident tile set:

    L      = exp(segsum(dA))            (Q, Q)   causal decay matrix
    y_diag = ((C Bᵀ) ⊙ L) · xdt         (Q, Q)·(Q, P)  — MXU matmuls
    state  = (xdt ⊙ decay)ᵀ · B         (P, N)   end-of-chunk state
    decay  = exp(cumsum(dA))            (Q,)     incoming-state multiplier

Q = chunk = 128, N = state = 128, P = head_dim = 64 — every matmul dim is
MXU-aligned (multiples of 64/128). The O(S) inter-chunk recurrence and the
rank-1 state->output combine stay outside (ops.ssd_chunked): they are tiny
and sequential, exactly the split the SSD paper prescribes.

B/C are shared across heads (n_groups=1): their BlockSpec index_map ignores
the head coordinate, so the same (Q, N) tile is reused for all H head steps
— VMEM traffic for B/C is 1/H of the naive layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, dA_ref, B_ref, C_ref, y_ref, st_ref, dec_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dA = dA_ref[0, 0, 0].astype(jnp.float32)      # (Q,)
    B = B_ref[0, 0].astype(jnp.float32)           # (Q, N)
    C = C_ref[0, 0].astype(jnp.float32)           # (Q, N)
    Q = x.shape[0]

    cs = jnp.cumsum(dA)
    diff = cs[:, None] - cs[None, :]
    L = jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), jnp.exp(diff), 0.0)
    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32) * L
    y_ref[0, 0, 0] = jnp.dot(scores, x, preferred_element_type=jnp.float32)
    decay_states = jnp.exp(cs[-1] - cs)
    st_ref[0, 0, 0] = jnp.dot((x * decay_states[:, None]).T, B,
                              preferred_element_type=jnp.float32)
    dec_ref[0, 0, 0] = jnp.exp(cs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_call(xdt: jax.Array, dA: jax.Array, B: jax.Array, C: jax.Array,
                   *, interpret: bool):
    """xdt (b,h,nc,Q,P);  dA (b,h,nc,Q);  B,C (b,nc,Q,N).

    Returns (y_diag (b,h,nc,Q,P), states (b,h,nc,P,N), decay (b,h,nc,Q)).
    """
    b, h, nc, Q, P = xdt.shape
    N = B.shape[-1]
    grid = (b, h, nc)
    return pl.pallas_call(
        _kernel,
        name="ssd_chunk",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda ib, ih, ic: (ib, ic, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda ib, ih, ic: (ib, ih, ic, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nc, P, N), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nc, Q), jnp.float32),
        ],
        interpret=interpret,
    )(xdt, dA, B, C)
