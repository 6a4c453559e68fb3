"""CompressionStrategy: ONE protocol object per compression method.

This module is the single extension point for adding a compressor to the
repo. A strategy carries everything the runtime needs to host a method in
the paper's comparison — under identical FL rounds, fan-outs and wire
modes — behind one registered class:

* ``client_encode(key, u, params) -> TreeCompressed`` — the per-client
  encoder (3SFC's S-step synthesis, top-k selection, sign quantization...).
* ``server_decode(payload, params)`` — reconstruct one client's update from
  the *canonical wire payload* (what ``repro.comm`` codecs decode).
* ``server_aggregate(params, payloads)`` (optional, declared by
  ``supports_fused_aggregate``) — aggregate straight from the batched
  payloads without materializing per-client reconstructions; this is how
  3SFC's fused decode (one batched backward over the gathered ``(D_syn,
  s)``) is expressed as a *capability* instead of a special case inside
  ``fl/round.py``.
* ``wire_codec(params, policy=...)`` — the method's serialized byte format
  (``repro.comm.codec`` registry), raising ``KeyError`` for accounted-only
  methods.
* ``payload_floats(params)`` — the accounted uplink size (paper Eq. 1).
* ``init_ef_state(params)`` — the per-client error-feedback residual.

The base class also provides the three derived *steps* the FL round
pipeline consumes — ``step`` (float mode), ``payload_step`` (fused mode,
the wire payload is the message) and ``wire_step`` (codec mode, a framed
``uint8`` buffer is the message) — all sharing ONE copy of the Eq. 6 EF
algebra, so a new method only implements the protocol methods above.

Registering a new method is one class::

    from repro.core import strategy as S

    @S.register_strategy("meansign")
    class MeanSign(S.CompressionStrategy):
        def payload_floats(self, params):
            return 2.0 * len(jax.tree_util.tree_leaves(params))
        def client_encode(self, key, u, params):
            recon = jax.tree_util.tree_map(
                lambda l: jnp.mean(jnp.abs(l)) * jnp.sign(l), u)
            return S.TreeCompressed(
                recon, jnp.float32(self.payload_floats(params)),
                jnp.float32(0))

Duplicate kinds are rejected; ``make_strategy`` lists the registered kinds
on an unknown one, and ``strategy_kinds()`` is the introspection surface
used by the budget tables and the benchmark orchestrator.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, NamedTuple, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CompressorConfig
from repro.core import flat

PyTree = Any


class CompressMetrics(NamedTuple):
    cosine: jax.Array                # compression efficiency (Fig. 7)
    payload_floats: jax.Array        # accounted wire size this round
    aux: jax.Array                   # method-specific (3SFC: objective; else 0)


class TreeCompressed(NamedTuple):
    """What a strategy's ``client_encode`` hands back to the shared steps.

    ``cosine`` (when not None) is the already-computed cos(recon, u), so the
    EF step skips its own ``tree_cosine`` pass; ``direction``/``scale``
    (when not None) factor ``recon = scale · direction``, letting the EF
    update run as one fused ``e' = u − s·direction`` pass per leaf
    (``flat.tree_ef_update``) instead of reading the materialized
    recon again. ``wire`` is the method-specific wire payload (what a
    ``repro.comm.codec`` codec serializes and what ``server_decode`` /
    ``server_aggregate`` consume — value/index streams, sign sources, the
    (D_syn, s) pair); ``None`` for kinds without a wire format. Unused
    fields cost nothing (dead-code eliminated under jit).
    """

    recon: Any
    floats: jax.Array
    aux: jax.Array
    cosine: Optional[jax.Array] = None
    direction: Any = None
    scale: Optional[jax.Array] = None
    wire: Any = None


def leaf_k(n: int, ratio: float) -> int:
    """Kept entries for a size-n leaf at ``keep_ratio`` — the single source
    of truth for per-leaf budgets (the wire codecs derive their static
    layouts from the same function)."""
    return max(1, int(round(ratio * n)))


def _leaf_k(leaf, ratio: float) -> int:
    return leaf_k(leaf.size, ratio)


# ---------------------------------------------------------------------------
# deprecation bookkeeping (shared by the compressor/round shims)
# ---------------------------------------------------------------------------

_DEPRECATION_SEEN: set = set()


def warn_deprecated_once(name: str, replacement: str) -> None:
    """Emit one DeprecationWarning per process per shim name."""
    if name in _DEPRECATION_SEEN:
        return
    _DEPRECATION_SEEN.add(name)
    warnings.warn(f"{name} is deprecated; use {replacement}",
                  DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


class CompressionStrategy:
    """Base class for registered compression methods (see module docstring).

    Instances are constructed by ``make_strategy(cfg, ...)`` with a uniform
    signature so third-party strategies plug in without touching the
    callers; ``loss_fn``/``syn_spec`` are the synthetic-payload hooks (3SFC
    family) and may stay None for methods that don't use them.
    """

    kind: str = ""
    # capability: server_aggregate can consume the batched wire payloads
    # directly (no per-client reconstruction, no O(d) collective)
    supports_fused_aggregate: bool = False

    def __init__(self, cfg: CompressorConfig, *, loss_fn=None, syn_spec=None,
                 local_lr: float = 0.01):
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.syn_spec = syn_spec
        self.local_lr = local_lr

    # -- protocol ----------------------------------------------------------
    def init_ef_state(self, params: PyTree) -> PyTree:
        """EF residual pytree (zeros, f32) mirroring params."""
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def payload_floats(self, params: PyTree) -> float:
        """Accounted per-round uplink size in floats (paper Eq. 1)."""
        raise NotImplementedError

    def client_encode(self, key, u: PyTree, params: PyTree) -> TreeCompressed:
        """Compress one client's accumulated update ``u`` at ``params``."""
        raise NotImplementedError

    def server_decode(self, payload, params: PyTree) -> PyTree:
        """Canonical wire payload -> one client's reconstruction tree."""
        raise NotImplementedError(
            f"strategy {self.kind!r} has no payload decode")

    def server_aggregate(self, params: PyTree, payloads) -> PyTree:
        """Batched (leading client axis) payloads -> aggregated update.

        Only meaningful when ``supports_fused_aggregate``; the returned
        tree is what the server applies (mean semantics, matching
        ``fl.server.aggregate`` over the per-client reconstructions).
        """
        raise NotImplementedError(
            f"strategy {self.kind!r} does not support fused aggregation")

    def mask_payloads(self, payloads, w: jax.Array):
        """Weight the batched (leading client axis N) wire payloads by the
        (N,) f32 mask ``w`` so that ``server_aggregate`` of the masked batch
        equals the weighted sum / N of per-client contributions.

        The fault pipeline (``fl/round.py`` under ``run.has_faults``) uses
        this with ``w ∈ {0, 1}`` to zero out dropped clients inside the
        fused aggregate, then rescales by N/Σw. Only meaningful together
        with ``supports_fused_aggregate``; the default refuses so a fused
        strategy without fault support fails loudly at build time.
        """
        raise NotImplementedError(
            f"strategy {self.kind!r} does not support masked fused "
            f"aggregation (mask_payloads)")

    def wire_codec(self, params: PyTree, *, policy: Optional[str] = None):
        """Build this method's registered byte codec over a params template.

        Raises ``KeyError`` for kinds without a wire format (their budgets
        stay accounted-only).
        """
        from repro.comm.codec import CODECS  # lazy: keep core import-light
        if self.cfg.kind not in CODECS:
            raise KeyError(
                f"no wire codec registered for compressor kind "
                f"{self.cfg.kind!r} (have: {sorted(CODECS)})")
        policy = policy or getattr(self.cfg, "wire_dtype", "fp32")
        return CODECS[self.cfg.kind](self.cfg, params, policy, strategy=self)

    # -- shared EF algebra (Eq. 6) — the ONE copy every entry path uses ----
    def _accumulate(self, g_tree: PyTree, e_tree: PyTree) -> PyTree:
        return flat.tree_add(g_tree, e_tree) if self.cfg.error_feedback \
            else g_tree

    def _ef_update(self, u, e_tree, recon, direction, scale) -> PyTree:
        """Eq. 6 residual on a (recon | direction·scale) view — shared by
        the float path (the strategy's own recon) and the wire path (the
        codec's dequantized view)."""
        if not self.cfg.error_feedback:
            return e_tree
        if direction is not None:
            return flat.tree_ef_update(u, direction, scale)
        return flat.tree_sub(u, recon)

    @staticmethod
    def _efficiency_cosine(out: TreeCompressed, recon, u) -> jax.Array:
        """cos(recon, u) unless the method already computed it fused."""
        return out.cosine if out.cosine is not None \
            else flat.tree_cosine(recon, u)

    # -- derived steps (what fl.round's pipeline calls) --------------------
    def step(self, key, g_tree, e_tree, params):
        """Float mode: (recon_tree, new_e_tree, CompressMetrics)."""
        u = self._accumulate(g_tree, e_tree)
        out = self.client_encode(key, u, params)
        e_new = self._ef_update(u, e_tree, out.recon, out.direction, out.scale)
        cos = self._efficiency_cosine(out, out.recon, u)
        return out.recon, e_new, CompressMetrics(cos, out.floats, out.aux)

    def payload_step(self, key, g_tree, e_tree, params):
        """Fused mode: (wire payload, new_e_tree, CompressMetrics).

        The wire payload is the message that crosses the client/server
        boundary (``server_aggregate`` consumes the batch of them); the
        reconstruction never does — with a (direction, scale) factorization
        it is never materialized client-side either.
        """
        u = self._accumulate(g_tree, e_tree)
        out = self.client_encode(key, u, params)
        if out.wire is None:
            raise ValueError(
                f"compressor kind {self.cfg.kind!r} emits no wire payload")
        e_new = self._ef_update(u, e_tree, out.recon, out.direction, out.scale)
        cos = self._efficiency_cosine(out, out.recon, u)
        return out.wire, e_new, CompressMetrics(cos, out.floats, out.aux)

    def wire_step(self, key, g_tree, e_tree, params, *, codec,
                  round_idx=0, client_idx=0):
        """Codec mode: (framed uint8 buffer, new_e_tree, CompressMetrics).

        Same EF algebra as ``step`` but everything downstream of the
        strategy sees only the serialized frame; the reconstruction used
        for EF/cosine is the codec's *dequantized view*
        (``Codec.client_view``), so the client stays consistent with what
        the server will decode — identical to the float path wherever the
        codec is lossless.
        """
        u = self._accumulate(g_tree, e_tree)
        out = self.client_encode(key, u, params)
        if out.wire is None:
            raise ValueError(
                f"compressor kind {self.cfg.kind!r} emits no wire payload")
        buf = codec.encode(out.wire, round_idx=round_idx,
                           client_idx=client_idx)
        recon, direction, scale = codec.client_view(out)
        e_new = self._ef_update(u, e_tree, recon, direction, scale)
        cos = self._efficiency_cosine(out, recon, u)
        return buf, e_new, CompressMetrics(cos, out.floats, out.aux)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

STRATEGIES: Dict[str, Type[CompressionStrategy]] = {}


def register_strategy(kind: str):
    """Class decorator registering a ``CompressionStrategy`` under ``kind``.

    Third-party code calls this too — a new compressor is one registered
    class, not an edit to the runtime. Duplicate kinds are rejected so two
    packages can't silently shadow each other.
    """

    def deco(cls: Type[CompressionStrategy]) -> Type[CompressionStrategy]:
        if kind in STRATEGIES:
            raise ValueError(
                f"strategy kind {kind!r} already registered "
                f"(by {STRATEGIES[kind].__name__})")
        cls.kind = kind
        STRATEGIES[kind] = cls
        return cls

    return deco


def strategy_kinds():
    """Sorted registered kinds — the introspection surface for budget
    tables and the benchmark orchestrator."""
    return sorted(STRATEGIES)


def make_strategy(cfg: CompressorConfig, *, loss_fn=None, syn_spec=None,
                  local_lr: float = 0.01) -> CompressionStrategy:
    """Instantiate the registered strategy for ``cfg.kind``."""
    if cfg.kind not in STRATEGIES:
        raise ValueError(
            f"unknown compressor kind {cfg.kind!r} "
            f"(registered: {strategy_kinds()})")
    return STRATEGIES[cfg.kind](cfg, loss_fn=loss_fn, syn_spec=syn_spec,
                                local_lr=local_lr)


# ---------------------------------------------------------------------------
# the paper's methods, as registered strategies
# ---------------------------------------------------------------------------


@register_strategy("identity")
class IdentityStrategy(CompressionStrategy):
    """FedAvg: the update itself is the payload (4d wire bytes)."""

    def payload_floats(self, params) -> float:
        return float(sum(l.size for l in jax.tree_util.tree_leaves(params)))

    def client_encode(self, key, u, params):
        # recon == u exactly, so the efficiency cosine is 1 by identity —
        # no reduction pass needed. The wire payload is the tree itself.
        return TreeCompressed(u, jnp.float32(self.payload_floats(params)),
                              jnp.float32(0), cosine=jnp.float32(1.0),
                              wire=u)

    def server_decode(self, payload, params):
        return payload


@register_strategy("topk")
class TopKStrategy(CompressionStrategy):
    """DGC-style magnitude top-k per leaf: exact values + indices."""

    def payload_floats(self, params) -> float:
        return float(sum(2 * _leaf_k(l, self.cfg.keep_ratio)
                         for l in jax.tree_util.tree_leaves(params)))

    def client_encode(self, key, u, params):
        leaves, treedef = jax.tree_util.tree_flatten(u)
        recs, wires = [], []
        for l in leaves:
            k = _leaf_k(l, self.cfg.keep_ratio)
            v = l.ravel()
            _, idx = jax.lax.top_k(jnp.abs(v), k)
            vals = v[idx]
            recs.append(jnp.zeros_like(v).at[idx].set(vals)
                        .reshape(l.shape))
            wires.append((vals, idx))
        recon = jax.tree_util.tree_unflatten(treedef, recs)
        return TreeCompressed(recon, jnp.float32(self.payload_floats(params)),
                              jnp.float32(0), wire=tuple(wires))

    def server_decode(self, payload, params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for (vals, idx), leaf in zip(payload, leaves):
            shape = jnp.shape(leaf)
            n = int(np.prod(shape)) if len(shape) else 1
            out.append(jnp.zeros((n,), jnp.float32).at[idx].set(vals)
                       .reshape(shape))
        return jax.tree_util.tree_unflatten(treedef, out)


@register_strategy("randk")
class RandKStrategy(CompressionStrategy):
    """Random-k per leaf (accounted-only: no wire format registered)."""

    def payload_floats(self, params) -> float:
        leaves = jax.tree_util.tree_leaves(params)
        return float(sum(_leaf_k(l, self.cfg.keep_ratio)
                         for l in leaves) + 1)

    def client_encode(self, key, u, params):
        leaves, treedef = jax.tree_util.tree_flatten(u)
        keys = jax.random.split(key, len(leaves))
        out = []
        for l, k_i in zip(leaves, keys):
            k = _leaf_k(l, self.cfg.keep_ratio)
            v = l.ravel()
            idx = jax.random.choice(k_i, v.size, shape=(k,), replace=False)
            kept = jnp.zeros_like(v).at[idx].set(v[idx])
            out.append(kept.reshape(l.shape))
        recon = jax.tree_util.tree_unflatten(treedef, out)
        return TreeCompressed(recon, jnp.float32(self.payload_floats(params)),
                              jnp.float32(0))


@register_strategy("signsgd")
class SignSGDStrategy(CompressionStrategy):
    """signSGD with per-leaf mean-|x| scale; 1 bit/coordinate on the wire."""

    def payload_floats(self, params) -> float:
        leaves = jax.tree_util.tree_leaves(params)
        return sum(l.size for l in leaves) / 32.0 + len(leaves)

    def client_encode(self, key, u, params):
        leaves, treedef = jax.tree_util.tree_flatten(u)
        scales = [jnp.mean(jnp.abs(l)) for l in leaves]
        recon = jax.tree_util.tree_unflatten(
            treedef, [s * jnp.sign(l) for s, l in zip(scales, leaves)])
        # wire: the sign *source* tree + per-leaf scales; the codec packs
        # one bit per coordinate from it (bit = coord >= 0).
        return TreeCompressed(recon, jnp.float32(self.payload_floats(params)),
                              jnp.float32(0),
                              wire=(u, jnp.stack(scales)))

    def server_decode(self, payload, params):
        # the canonical payload is already the reconstructed tree (signs
        # re-scaled by the codec's unpack)
        return payload


@register_strategy("stc")
class STCStrategy(CompressionStrategy):
    """STC: ternary top-k (single magnitude mu per leaf + signs)."""

    def payload_floats(self, params) -> float:
        leaves = jax.tree_util.tree_leaves(params)
        ks = [_leaf_k(l, self.cfg.keep_ratio) for l in leaves]
        return float(sum(ks)) + sum(ks) / 32.0 + len(leaves)

    def client_encode(self, key, u, params):
        leaves, treedef = jax.tree_util.tree_flatten(u)
        recs, wires = [], []
        for l in leaves:
            k = _leaf_k(l, self.cfg.keep_ratio)
            v = l.ravel()
            _, idx = jax.lax.top_k(jnp.abs(v), k)
            vals = v[idx]
            mu = jnp.mean(jnp.abs(vals))
            sgn = jnp.sign(vals)
            recs.append(jnp.zeros_like(v).at[idx].set(mu * sgn)
                        .reshape(l.shape))
            wires.append((sgn, idx, mu))
        recon = jax.tree_util.tree_unflatten(treedef, recs)
        return TreeCompressed(recon, jnp.float32(self.payload_floats(params)),
                              jnp.float32(0), wire=tuple(wires))

    def server_decode(self, payload, params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = []
        for (pm1, idx, mu), leaf in zip(payload, leaves):
            shape = jnp.shape(leaf)
            n = int(np.prod(shape)) if len(shape) else 1
            out.append(jnp.zeros((n,), jnp.float32).at[idx].set(mu * pm1)
                       .reshape(shape))
        return jax.tree_util.tree_unflatten(treedef, out)


@register_strategy("threesfc")
class ThreeSFCStrategy(CompressionStrategy):
    """The paper's method: single-step synthetic-features compression.

    The (D_syn, s) payload is the wire; the server decode is one backward
    of the global model on the synthetic batch (Eq. 10), and — because
    every client encodes at the same w^t — the batched payloads aggregate
    in ONE replicated backward (``server_aggregate``), which is what makes
    the fused fan-out's O(N·payload) collective possible.
    """

    supports_fused_aggregate = True

    def __init__(self, cfg, *, loss_fn=None, syn_spec=None, local_lr=0.01):
        super().__init__(cfg, loss_fn=loss_fn, syn_spec=syn_spec,
                         local_lr=local_lr)
        assert syn_spec is not None, \
            f"{cfg.kind} strategy needs syn_spec (synthetic payload shapes)"

    def payload_floats(self, params) -> float:
        return self.syn_spec.floats + 1.0

    def client_encode(self, key, u, params):
        from repro.core import threesfc
        assert self.loss_fn is not None, \
            f"{self.cfg.kind} encode needs the model's syn loss_fn"
        syn0 = threesfc.init_syn(key, self.syn_spec)
        res = threesfc.encode(
            self.loss_fn, params, u, syn0,
            steps=self.cfg.syn_steps, lr=self.cfg.syn_lr,
            lam=self.cfg.l2_coef,
        )
        # encode's fused stats triple already carries cos(recon, u) and
        # the (gw, s) factorization — EF and metrics add no extra passes.
        return TreeCompressed(res.recon,
                              jnp.float32(self.payload_floats(params)),
                              res.objective, cosine=res.cosine,
                              direction=res.gw, scale=res.s,
                              wire=(res.syn, res.s))

    def server_decode(self, payload, params):
        assert self.loss_fn is not None, \
            "threesfc decode-side reconstruction needs syn_loss_fn"
        syn, s = payload
        gw = jax.grad(self.loss_fn)(params, syn)
        return flat.tree_scale(gw, s)

    def server_aggregate(self, params, payloads):
        """ONE replicated batched backward over the gathered (D_syn, s):

            G(ĝ_1..ĝ_N) = ∇_w (1/N) Σ_i s_i F(D_syn,i, w^t)
        """
        assert self.loss_fn is not None, \
            "threesfc fused aggregation needs syn_loss_fn"
        syns, ss = payloads

        def total_loss(w):
            per = jax.vmap(lambda sy: self.loss_fn(w, sy))(syns)   # (N,)
            return jnp.mean(jax.lax.stop_gradient(ss) * per)

        return jax.grad(total_loss)(params)

    def mask_payloads(self, payloads, w):
        """(D_syn, s) is linear in s, so masking a client is exactly
        ``s_i <- w_i * s_i`` — a dropped payload contributes a zero term to
        the batched backward; ``w == 1`` everywhere is ``s * 1.0``, bitwise
        the unmasked payload (the zero-fault gate's fused leg)."""
        syns, ss = payloads
        return syns, ss * w


@register_strategy("fedsynth")
class FedSynthStrategy(ThreeSFCStrategy):
    """FedSynth baseline: K-step unrolled synthesis (accounted-only wire)."""

    supports_fused_aggregate = False

    def client_encode(self, key, u, params):
        from repro.core import fedsynth, threesfc
        assert self.loss_fn is not None, \
            f"{self.cfg.kind} encode needs the model's syn loss_fn"
        syn0 = threesfc.init_syn(key, self.syn_spec)
        res = fedsynth.encode(
            self.loss_fn, params, u, syn0,
            unroll_steps=self.cfg.unroll_steps,
            opt_steps=max(self.cfg.syn_steps, 10),
            lr=self.local_lr, syn_lr=self.cfg.syn_lr,
        )
        return TreeCompressed(res.recon,
                              jnp.float32(self.payload_floats(params)),
                              res.l2)

    def server_decode(self, payload, params):
        raise NotImplementedError(
            "fedsynth has no payload decode (unrolled recon is client-side)")

    def server_aggregate(self, params, payloads):
        raise NotImplementedError(
            "strategy 'fedsynth' does not support fused aggregation")
