"""Public jit'd wrappers around the Pallas kernels.

Each wrapper handles flattening/padding to the (rows, 1024)-lane layout
the kernels tile over and reduces kernel partials to the user-facing
result. ``_interpret()`` is the one switch that picks the kernels' mode:
compiled on a TPU backend, the Pallas interpreter on any other. The kernel
signatures take ``interpret`` without a default, so every caller goes
through this switch or states its mode.

The 3SFC encode's pytree reductions are not here: they run leaf by leaf
in plain XLA (``core.flat.tree_stats`` / ``tree_ef_update``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.sign_quant import sign_quant_2d
from repro.kernels.ssd_chunk import ssd_chunk_call
from repro.kernels.topk_mask import topk_mask_2d

LANES = 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _to_2d(v: jax.Array, block_rows: int) -> Tuple[jax.Array, int]:
    """Flatten + zero-pad to (rows, LANES), rows % block_rows == 0."""
    n = v.size
    tile = block_rows * LANES
    rows = max(1, -(-n // tile)) * block_rows
    pad = rows * LANES - n
    v2 = jnp.pad(v.reshape(-1), (0, pad)).reshape(rows, LANES)
    return v2, n


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
