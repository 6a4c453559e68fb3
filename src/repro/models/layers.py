"""Shared primitive layers: RMSNorm, dense FFN (SwiGLU), embedding, conv."""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import params as P_


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype=jnp.float32) -> Dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> Dict:
    return {"table": P_.embed_init(key, vocab, d, dtype)}


def embed(p: Dict, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return p["table"].astype(dtype)[tokens]


def unembed(p: Dict, x: jax.Array) -> jax.Array:
    """Tied-embedding logits: x (.., d) @ table.T (d, V), f32 accumulate."""
    return jnp.einsum(
        "...d,vd->...v", x.astype(jnp.float32), p["table"].astype(jnp.float32)
    )


def lm_head_init(key, d: int, vocab: int, dtype=jnp.float32) -> Dict:
    return {"w": P_.dense_init(key, d, (d, vocab), dtype)}


def lm_head(p: Dict, x: jax.Array) -> jax.Array:
    return jnp.einsum("...d,dv->...v", x.astype(jnp.float32), p["w"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------


def ffn_init(key, d: int, ff: int, dtype=jnp.float32) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_in": P_.dense_init(k1, d, (d, ff), dtype),
        "w_gate": P_.dense_init(k2, d, (d, ff), dtype),
        "w_out": P_.dense_init(k3, ff, (ff, d), dtype),
    }


def ffn(p: Dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = jnp.einsum("...d,df->...f", x, p["w_in"].astype(dt))
    g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(dt))
    h = jax.nn.silu(g) * h
    return jnp.einsum("...f,fd->...d", h, p["w_out"].astype(dt))


# ---------------------------------------------------------------------------
# Conv (paper CNN models + SSM/RG-LRU temporal conv)
# ---------------------------------------------------------------------------


def conv2d_init(key, cin: int, cout: int, k: int, dtype=jnp.float32) -> Dict:
    kw, kb = jax.random.split(key)
    w = P_.dense_init(kw, cin * k * k, (k, k, cin, cout), dtype)
    return {"w": w, "b": jnp.zeros((cout,), dtype)}


# Named scope of every convolution with its bias add and activation. It
# adds op_name metadata only (the compiled instructions are unchanged), so a
# profiler trace can tell the convolutions' time apart inside a round's
# phases.
CONV_SCOPE = "cnn.conv"


def conv2d(p: Dict, x: jax.Array, stride: int = 1, padding: str = "SAME",
           act: Optional[Callable[[jax.Array], jax.Array]] = None) -> jax.Array:
    """``act(conv(x, w) + b)``, all under ``CONV_SCOPE``. The activation
    belongs inside: with per-client weights under vmap, its backward moves
    the client axis of the input gradient back with a transpose, which XLA
    folds into the strided convolution, and the convolution then carries
    the transpose's op_name."""
    with jax.named_scope(CONV_SCOPE):
        y = jax.lax.conv_general_dilated(
            x, p["w"].astype(x.dtype),
            window_strides=(stride, stride), padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = y + p["b"].astype(x.dtype)
        return y if act is None else act(y)


def causal_conv1d_init(key, channels: int, width: int, dtype=jnp.float32) -> Dict:
    w = P_.dense_init(key, width, (width, channels), dtype)
    return {"conv_w": w, "conv_b": jnp.zeros((channels,), dtype)}


def causal_conv1d(p: Dict, x: jax.Array) -> jax.Array:
    """Depthwise causal conv over time. x: (B, S, C)."""
    width = p["conv_w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    # depthwise: stack width shifted copies (small width => cheap, fusable)
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + xp[:, i : i + x.shape[1], :] * p["conv_w"][i].astype(x.dtype)
    return out + p["conv_b"].astype(x.dtype)


def causal_conv1d_step(p: Dict, x_t: jax.Array, buf: jax.Array):
    """Single decode step. x_t: (B, C); buf: (B, width-1, C) past inputs.

    Returns (y_t, new_buf).
    """
    width = p["conv_w"].shape[0]
    full = jnp.concatenate([buf, x_t[:, None, :]], axis=1)       # (B, width, C)
    y = jnp.einsum("bwc,wc->bc", full.astype(jnp.float32),
                   p["conv_w"].astype(jnp.float32))
    y = (y + p["conv_b"].astype(jnp.float32)).astype(x_t.dtype)
    return y, full[:, 1:, :]
