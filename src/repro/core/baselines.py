"""Baseline gradient compressors the paper compares against.

All operate on *flat* float32 vectors (see ``flat.Flattener``) and return
``(payload, recon)`` where ``recon`` is the server-side reconstruction —
exactly what the decoder would produce from the payload. Budget accounting
(``payload_floats``) follows the paper's conventions:

* top-k (DGC):  k values + k indices  -> 2k float-equivalents
* rand-k:       k values + 1 seed     -> k + 1 (indices regenerable from seed)
* signSGD(+EF): 1 bit/coord + 1 scale -> d/32 + 1
* STC:          top-k + binarized values -> k (indices) + k/32 (signs) + 1 (mu)
* identity (FedAvg): d

These float counts are *conventions*, not measurements. The real wire
format — each payload serialized into one framed ``uint8`` buffer
(bit-packed signs, ``ceil(log2 d)``-bit index streams, dtype-policied
synthetic payloads) with a measured byte size — lives in ``repro.comm``;
``compression_rate_bytes`` below is the bytes-based sibling of Eq. 1 that
the FL harness reports next to the accounted-float rate.

On TPU, exact global top-k over O(d) is sort-bound; we use the Pallas
threshold-select kernel (``repro.kernels.topk_mask``) when available and fall
back to ``jax.lax.top_k`` here. Reconstruction semantics are identical.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import flat


class Payload(NamedTuple):
    """Accounted-size stand-in, NOT the wire format. ``floats`` is the
    paper-convention payload size; the serialized frame (actual bytes on
    the wire, header included) is produced by ``repro.comm.codec``."""

    data: tuple
    floats: float


# ---------------------------------------------------------------------------
# identity (FedAvg)
# ---------------------------------------------------------------------------


def identity_compress(vec: jax.Array) -> Tuple[Payload, jax.Array]:
    return Payload((vec,), float(vec.size)), vec


# ---------------------------------------------------------------------------
# top-k (DGC)
# ---------------------------------------------------------------------------


def topk_compress(vec: jax.Array, k: int) -> Tuple[Payload, jax.Array]:
    """Keep the k largest-magnitude coordinates (DGC sparsifier)."""
    k = max(1, min(int(k), vec.size))
    mag = jnp.abs(vec)
    _, idx = jax.lax.top_k(mag, k)
    vals = vec[idx]
    recon = jnp.zeros_like(vec).at[idx].set(vals)
    return Payload((vals, idx), 2.0 * k), recon


# ---------------------------------------------------------------------------
# rand-k
# ---------------------------------------------------------------------------


def randk_compress(key: jax.Array, vec: jax.Array, k: int) -> Tuple[Payload, jax.Array]:
    k = max(1, min(int(k), vec.size))
    idx = jax.random.choice(key, vec.size, shape=(k,), replace=False)
    vals = vec[idx]
    recon = jnp.zeros_like(vec).at[idx].set(vals)
    return Payload((vals, idx), float(k) + 1.0), recon


# ---------------------------------------------------------------------------
# signSGD (with mean-|x| scale, as in EF-signSGD)
# ---------------------------------------------------------------------------


def signsgd_compress(vec: jax.Array) -> Tuple[Payload, jax.Array]:
    scale = jnp.mean(jnp.abs(vec))
    signs = jnp.sign(vec)
    # 0-sign coords reconstruct to 0 (sign(0) == 0): harmless and exact.
    recon = scale * signs
    return Payload((signs, scale), vec.size / 32.0 + 1.0), recon


# ---------------------------------------------------------------------------
# STC: sparse ternary compression = top-k + binarize kept values to mean
# ---------------------------------------------------------------------------


def stc_compress(vec: jax.Array, k: int) -> Tuple[Payload, jax.Array]:
    k = max(1, min(int(k), vec.size))
    mag = jnp.abs(vec)
    _, idx = jax.lax.top_k(mag, k)
    vals = vec[idx]
    mu = jnp.mean(jnp.abs(vals))
    tern = mu * jnp.sign(vals)
    recon = jnp.zeros_like(vec).at[idx].set(tern)
    return Payload((jnp.sign(vals), idx, mu), k + k / 32.0 + 1.0), recon


# ---------------------------------------------------------------------------
# reconstruction quality (fused single-pass accounting)
# ---------------------------------------------------------------------------


def reconstruction_stats(vec: jax.Array, recon: jax.Array,
                         eps: float = 1e-12) -> Tuple[jax.Array, jax.Array]:
    """(cosine, relative L2 error) of a reconstruction in two fused passes.

    The cosine is scalar algebra on the ``(⟨r,v⟩, ||r||², ||v||²)`` triple
    ``flat.tree_stats`` returns in a single HBM sweep. The
    error term is deliberately NOT derived from that triple —
    ``||r−v||² = ||r||² − 2⟨r,v⟩ + ||v||²`` cancels catastrophically in f32
    once the error drops below ~3e-4 relative — but from a direct sum over
    the streamed difference (XLA fuses it into one more pass, nothing
    materialized). Two passes total vs the naive route's four.
    """
    d, rr, vv = flat.tree_stats(recon, vec)
    cos = d / (jnp.sqrt(rr) * jnp.sqrt(vv) + eps)
    sq = jnp.sum(jnp.square(recon.astype(jnp.float32) - vec.astype(jnp.float32)))
    return cos, jnp.sqrt(sq) / (jnp.sqrt(vv) + eps)


# ---------------------------------------------------------------------------
# budget helpers
# ---------------------------------------------------------------------------


def keep_k_for_budget(d: int, budget_floats: float) -> int:
    """k such that a top-k payload (2k floats) fits the budget."""
    return max(1, int(budget_floats // 2))


def compression_rate(payload_floats: float, d: int) -> float:
    """Paper Eq. 1: compressed size / uncompressed size (accounted floats)."""
    return payload_floats / float(d)


def compression_rate_bytes(payload_bytes: float, d: int,
                           bytes_per_param: int = 4) -> float:
    """Eq. 1 on *measured* wire bytes (``repro.comm.wire_bytes``): encoded
    frame size (header included) over the raw f32 tree size."""
    return payload_bytes / (bytes_per_param * float(d))
