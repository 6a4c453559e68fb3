"""Pure-jnp oracles for every kernel and for the 3SFC tree reductions
(the correctness contract)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def fused_cosine(x: jax.Array, y: jax.Array) -> jax.Array:
    """(3,) f32: [x·y, ||x||², ||y||²]."""
    xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.stack([jnp.sum(xf * yf), jnp.sum(xf * xf), jnp.sum(yf * yf)])


def ef_update(u: jax.Array, d: jax.Array, s: jax.Array) -> jax.Array:
    """e' = u - s·d."""
    return (u.astype(jnp.float32) - s.astype(jnp.float32) * d.astype(jnp.float32))


def sign_quant(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(signs int8, scale = mean|x| f32)."""
    xf = x.astype(jnp.float32)
    return jnp.sign(xf).astype(jnp.int8), jnp.mean(jnp.abs(xf))


def topk_mask(x: jax.Array, threshold: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(masked vector keeping |x| >= threshold, kept count f32)."""
    xf = x.astype(jnp.float32)
    keep = jnp.abs(xf) >= threshold
    return jnp.where(keep, xf, 0.0), jnp.sum(keep.astype(jnp.float32))


def ssd_chunk(xdt: jax.Array, dA: jax.Array, B: jax.Array, C: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Intra-chunk SSD for ONE chunk and ONE head.

    xdt (Q, P) = dt·x;  dA (Q,);  B, C (Q, N).
    Returns (y_diag (Q, P), state (P, N), state_decay_out (Q,)):
      y_diag   = (C B^T ⊙ L) xdt           with L_ij = exp(sum_{j<m<=i} dA_m)
      state    = sum_k exp(cs[-1] - cs[k]) B_k ⊗ xdt_k   (end-of-chunk state)
      decay    = exp(cs)  (per-position multiplier for the incoming state)
    """
    Q = xdt.shape[0]
    cs = jnp.cumsum(dA)
    diff = cs[:, None] - cs[None, :]
    L = jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), jnp.exp(diff), 0.0)
    scores = (C @ B.T) * L                                   # (Q, Q)
    y_diag = scores @ xdt                                    # (Q, P)
    decay_states = jnp.exp(cs[-1] - cs)                      # (Q,)
    state = (xdt * decay_states[:, None]).T @ B              # (P, N)
    return y_diag, state, jnp.exp(cs)
