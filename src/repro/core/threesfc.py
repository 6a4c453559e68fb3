"""3SFC — Single-Step Synthetic Features Compressor (the paper's method).

Encoder (client, Eq. 7-9): compress the accumulated local update
``g + e`` into a tiny synthetic dataset ``D_syn = (x_syn, y_syn)`` plus one
scalar ``s`` by maximizing the |cosine| between ``∇_w F(D_syn, w^t)`` and the
target. The scale is factored out analytically (Eq. 8):

    s = <g+e, ∇F> / ||∇F||²         (least-squares optimal coefficient)

so the synthetic-data objective (Eq. 9) only cares about *direction*:

    min_{D_syn}  1 - |cos(∇_w F(D_syn, w^t), g+e)| + λ ||D_syn||²

optimized for S steps (paper: S=1 suffices — hence "single-step") of GD via
grad-of-grad. Decoder (server, Eq. 10): one backward of the *global* model on
``D_syn`` scaled by ``s``. Both sides evaluate at the same ``w^t`` so the
reconstruction is exact on the server.

Synthetic features generalize beyond the paper's image classifiers:
* classifier:  x (n, *input_shape) raw pixels, y (n, C) soft-label logits
* LM family:   x (n, L, d_model) *soft input embeddings*, y soft labels over
  the vocab — optionally low-rank factored (u (n,L,r) @ v (r,V)) so the
  payload stays tiny for 100k+ vocabs (beyond-paper extension).

Budget: ||D_syn||₀ + 1 ≤ B, counting every transmitted float.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import flat


class SynData(NamedTuple):
    """The transmitted synthetic dataset. ``y_rank`` empty => dense labels."""

    x: jax.Array                     # synthetic inputs (or soft embeddings)
    y: jax.Array                     # soft label logits, dense or factor u
    y_rank: jax.Array                # low-rank factor v (r, C); shape (0,0) if dense

    @property
    def floats(self) -> float:
        return float(self.x.size + self.y.size + self.y_rank.size)

    def labels(self) -> jax.Array:
        """Dense soft-label logits."""
        if self.y_rank.size == 0:
            return self.y
        return jnp.einsum("...r,rc->...c", self.y, self.y_rank)


@dataclasses.dataclass(frozen=True)
class SynSpec:
    """Static description of the synthetic payload's shapes."""

    x_shape: Tuple[int, ...]         # e.g. (n, 28, 28, 1) or (n, L, d_model)
    num_classes: int                 # C (classifier) or vocab (LM)
    label_rank: int = 0              # 0 => dense (n, ..., C) labels
    label_lead: Tuple[int, ...] = () # leading label dims, default x_shape[:-1]

    @property
    def floats(self) -> float:
        import numpy as np

        lead = self.label_lead or self.x_shape[:1]
        x = float(np.prod(self.x_shape))
        if self.label_rank:
            return x + float(np.prod(lead)) * self.label_rank + self.label_rank * self.num_classes
        return x + float(np.prod(lead)) * self.num_classes


def init_syn(key: jax.Array, spec: SynSpec, scale: float = 0.1) -> SynData:
    kx, ky, kv = jax.random.split(key, 3)
    x = scale * jax.random.normal(kx, spec.x_shape, jnp.float32)
    lead = spec.label_lead or spec.x_shape[:1]
    if spec.label_rank:
        y = scale * jax.random.normal(ky, (*lead, spec.label_rank), jnp.float32)
        v = scale * jax.random.normal(kv, (spec.label_rank, spec.num_classes), jnp.float32)
    else:
        y = scale * jax.random.normal(ky, (*lead, spec.num_classes), jnp.float32)
        v = jnp.zeros((0, 0), jnp.float32)
    return SynData(x, y, v)


# ``loss_fn(params, syn: SynData) -> scalar`` — the model's empirical risk on
# the synthetic batch (soft-label cross-entropy for every model family here).
LossFn = Callable[[flat.PyTree, SynData], jax.Array]


def soft_xent(logits: jax.Array, label_logits: jax.Array) -> jax.Array:
    """Cross-entropy against softmax(label_logits); mean over leading dims."""
    target = jax.nn.softmax(label_logits, axis=-1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.sum(target * logp, axis=-1))


_EPS = 1e-12


def _objective(
    loss_fn: LossFn, params: flat.PyTree, syn: SynData, target: flat.PyTree, lam: float
) -> Tuple[jax.Array, Tuple[flat.PyTree, jax.Array]]:
    """Eq. 9 value plus aux ``(gw, stats)``.

    ``stats = (⟨gw,t⟩, ||gw||², ||t||²)`` comes from ONE HBM pass
    (``flat.tree_stats``: one multi-output reduction per leaf pair, in the
    leaf's own layout); Eq. 9's cosine, Eq. 8's scale and the reported
    compression efficiency are all scalar algebra on this same triple, so
    each objective evaluation reads the gradient trees exactly once.
    """
    gw = jax.grad(loss_fn)(params, syn)
    stats = flat.tree_stats(gw, target)
    dot, gg, tt = stats[0], stats[1], stats[2]
    cos = dot / (jnp.sqrt(gg) * jnp.sqrt(tt) + _EPS)
    reg = lam * (flat.tree_sqnorm([syn.x, syn.y, syn.y_rank]))
    return 1.0 - jnp.abs(cos) + reg, (gw, stats)


class EncodeResult(NamedTuple):
    syn: SynData
    s: jax.Array                     # scaling coefficient (Eq. 8)
    gw: flat.PyTree                  # ∇_w F(D_syn, w^t) at the final D_syn
    cosine: jax.Array                # compression efficiency (Fig. 7 metric)
    objective: jax.Array             # final Eq. 9 value
    stats: jax.Array                 # (⟨gw,t⟩, ||gw||², ||t||²) fused triple

    @property
    def recon(self) -> flat.PyTree:
        """s · ∇_w F(D_syn, w^t) — what the server sees (Eq. 10).

        Materialized on demand: EF paths that only need ``e' = u − s·gw``
        (``flat.tree_ef_update``, one elementwise pass per leaf)
        never instantiate this tree.
        """
        return flat.tree_scale(self.gw, self.s)


def encode(
    loss_fn: LossFn,
    params: flat.PyTree,
    target: flat.PyTree,
    syn0: SynData,
    *,
    steps: int = 1,
    lr: float = 0.1,
    lam: float = 0.0,
    normalize_updates: bool = True,
) -> EncodeResult:
    """Run S optimization steps on D_syn (Algorithm 1 lines 7-9), then Eq. 8.

    ``normalize_updates=True`` rescales each GD step by the syn-grad RMS —
    a per-tensor Adam-like normalization that makes one step land at a useful
    distance regardless of model scale. The paper's plain-GD update is
    recovered with ``normalize_updates=False``; both are exposed because the
    normalized variant is markedly more robust across the 10 assigned
    architectures (recorded as a beyond-paper change in DESIGN.md).

    Perf: every objective evaluation reduces the gradient trees exactly once,
    leaf by leaf in each leaf's own layout (the fused ``flat.tree_stats``
    triple); s, the efficiency cosine and the Eq. 9 value are scalar algebra
    on that triple, and the reconstruction is returned factored as (gw, s)
    so EF consumers compute ``e' = u − s·gw`` in one pass without
    materializing s·gw (see ``EncodeResult.recon``).
    """

    def obj_aux(syn: SynData):
        return _objective(loss_fn, params, syn, target, lam)

    vag = jax.value_and_grad(obj_aux, has_aux=True)

    def update(syn: SynData, g: SynData) -> SynData:
        if normalize_updates:
            def upd(p, gi):
                rms = jnp.sqrt(jnp.mean(gi * gi) + 1e-12)
                return p - lr * gi / rms
            return SynData(*[upd(p, gi) for p, gi in zip(syn, g)])
        return SynData(*[p - lr * gi for p, gi in zip(syn, g)])

    # One scan of steps+1 evaluations: iterations 0..S-1 run grad-of-grad
    # and apply the GD update; the final iteration evaluates (obj, gw, stats)
    # at the *returned* D_syn with a plain inner backward (cond keeps the
    # outer backward off that step — the predicate is the unbatched scan
    # index, so vmap'd clients keep the branch, not a select). The last
    # carry therefore already holds everything Eq. 8/9 need — no separate
    # `_objective` recompute after the loop, and since the final branch's
    # zero gradient makes `update` the identity, the carry's syn is exactly
    # the one gw was evaluated at (decode exactness, Eq. 10).
    def step(carry, i):
        syn = carry[0]

        def eval_and_grad(syn):
            (val, (gw, st)), g = vag(syn)
            return val, gw, st, g

        def eval_only(syn):
            val, (gw, st) = obj_aux(syn)
            return val, gw, st, jax.tree_util.tree_map(jnp.zeros_like, syn)

        val, gw, st, g = jax.lax.cond(i < steps, eval_and_grad, eval_only, syn)
        return (update(syn, g), val, gw, st), None

    gw0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.result_type(p)), params)
    init = (syn0, jnp.zeros((), jnp.float32), gw0, jnp.zeros((3,), jnp.float32))
    (syn, obj_val, gw, stats), _ = jax.lax.scan(step, init, jnp.arange(steps + 1))

    dot, gg = stats[0], stats[1]
    s = dot / (gg + _EPS)                                    # Eq. 8
    # cos(s·gw, target) = sign(s) · cos(gw, target), from the same triple
    cos = jnp.sign(s) * dot / (jnp.sqrt(gg) * jnp.sqrt(stats[2]) + _EPS)
    return EncodeResult(syn, s, gw, cos, obj_val, stats)


def decode(loss_fn: LossFn, params: flat.PyTree, syn: SynData, s: jax.Array) -> flat.PyTree:
    """Server-side reconstruction (Eq. 10): s · ∇_w F(D_syn, w^t)."""
    gw = jax.grad(loss_fn)(params, syn)
    return flat.tree_scale(gw, s)
