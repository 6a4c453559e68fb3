"""Kernel-level benchmark: HBM-pass accounting for the fused reductions.

No wall-clock on CPU — the structural metric is bytes-accessed from
``cost_analysis`` of the lowered fused vs unfused reductions, at two levels:

* vector level: the fused triple's contract (ONE pass over 2d floats for
  the (x·y, ||x||², ||y||²) triple instead of three separate reductions);
* encoder level: the 3SFC objective-evaluation hot path. The seed encoder
  ran ~8 O(d) reduction sweeps plus a materialized s·∇F tree per
  evaluation; the fused ``tree_stats`` path reads each leaf of each
  gradient tree exactly once (2d·4 bytes, no padding) and derives Eq. 8's
  scale, Eq. 9's value and the efficiency cosine as scalar algebra on the
  triple.

Also validates ``flat.tree_stats`` on flat vectors and on a ragged tree
against its oracle, and emits ``BENCH_kernels.json``
(fused vs unfused bytes + pass counts) so the perf trajectory is tracked
round over round by ``benchmarks/run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flat
from repro.kernels import ref

# ragged, non-tile-aligned leaves — sums to d below
TREE_SHAPES = [(300, 1000), (1025,), (7,), (), (64, 1024), (123, 45)]


def _tree_pair(key):
    ks = jax.random.split(key, 2 * len(TREE_SHAPES))
    a = {f"p{i}": jax.random.normal(ks[2 * i], s)
         for i, s in enumerate(TREE_SHAPES)}
    b = {f"p{i}": jax.random.normal(ks[2 * i + 1], s)
         for i, s in enumerate(TREE_SHAPES)}
    return a, b


def _bytes(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost.get("bytes accessed", 0.0))


def _leafwise_stats_bytes(tree) -> int:
    """HBM bytes ``flat.tree_stats`` reads for a pair of trees shaped and
    typed like ``tree``, by its contract: each leaf of each operand read
    once, in its own dtype, with no padding.

    A formula, not a measurement: it does not look at the implementation,
    so the single-pass gate below restates the contract. What holds the
    implementation to it is the lowering test in
    ``tests/test_tree_stats.py`` (no pad, no tree-sized concatenate or
    reshape) and the v5e compile test of the round."""
    return 2 * sum(jnp.size(l) * jnp.result_type(l).itemsize
                   for l in jax.tree_util.tree_leaves(tree))


def _seed_encoder_reductions(gw, t):
    """The seed encode's post-scan reduction sequence (structural baseline):
    tree_cosine(gw,t) inside the objective, Eq. 8's dot + sqnorm, a
    materialized recon tree, and a second tree_cosine(recon, t)."""
    def dot(x, y):
        return sum(jnp.sum(xi * yi) for xi, yi in
                   zip(jax.tree.leaves(x), jax.tree.leaves(y)))

    def sq(x):
        return sum(jnp.sum(jnp.square(xi)) for xi in jax.tree.leaves(x))

    obj_cos = dot(gw, t) / (jnp.sqrt(sq(gw)) * jnp.sqrt(sq(t)) + 1e-12)
    num = dot(t, gw)
    den = sq(gw) + 1e-12
    s = num / den
    recon = jax.tree.map(lambda x: s * x, gw)
    cos = dot(recon, t) / (jnp.sqrt(sq(recon)) * jnp.sqrt(sq(t)) + 1e-12)
    return s, cos, 1.0 - jnp.abs(obj_cos)


def _fused_encoder_reductions(gw, t):
    """The rewritten path: ONE stats triple per objective evaluation."""
    st = flat.tree_stats(gw, t)
    d, gg, tt = st[0], st[1], st[2]
    s = d / (gg + 1e-12)
    cos = jnp.sign(s) * d / (jnp.sqrt(gg) * jnp.sqrt(tt) + 1e-12)
    return s, cos, 1.0 - jnp.abs(d / (jnp.sqrt(gg) * jnp.sqrt(tt) + 1e-12))


def run(quick: bool = True, out_dir: str = "experiments/results") -> Dict:
    n = 1 << 20 if quick else 1 << 24
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))
    y = jax.random.normal(jax.random.PRNGKey(1), (n,))

    def unfused(x, y):
        return jnp.stack([jnp.vdot(x, y), jnp.vdot(x, x), jnp.vdot(y, y)])

    ideal = 2 * n * 4          # one read of x + one read of y
    results = {
        "n": n,
        "ideal_bytes": ideal,
        "unfused_bytes": _bytes(unfused, x, y),
        "fused_oracle_bytes": _bytes(ref.fused_cosine, x, y),
    }
    print("\n== Kernel pass accounting (fused triple, flat vectors) ==")
    print(f"  ideal single-pass bytes : {ideal:,}")
    print(f"  unfused (3x vdot)       : {results['unfused_bytes']:,.0f}")
    print(f"  fused oracle            : {results['fused_oracle_bytes']:,.0f}")

    # ---- encoder hot path: bytes per 3SFC objective evaluation ------------
    # Two accountings, both recorded:
    #  * cost_analysis of the lowered jnp stand-ins — what XLA charges on
    #    THIS backend (CPU charges every unfused elementwise intermediate,
    #    so both numbers are inflated; the ratio is still structural);
    #  * the leafwise contract — each leaf of each tree is read once, with
    #    no padding (_leafwise_stats_bytes, a formula). That is the
    #    single-pass gate.
    gw, t = _tree_pair(jax.random.PRNGKey(2))
    d_tree = sum(l.size for l in jax.tree.leaves(gw))
    tree_ideal = 2 * d_tree * 4
    seed_bytes = _bytes(_seed_encoder_reductions, gw, t)
    fused_xla_bytes = _bytes(_fused_encoder_reductions, gw, t)
    kernel_bytes = _leafwise_stats_bytes(gw)
    tol = 0.02 * tree_ideal
    results.update({
        "tree_d": d_tree,
        "tree_ideal_bytes": tree_ideal,
        "encoder_seed_bytes": seed_bytes,
        "encoder_fused_xla_bytes": fused_xla_bytes,
        "encoder_fused_kernel_bytes": kernel_bytes,
        "encoder_seed_passes": seed_bytes / (d_tree * 4),
        "encoder_fused_xla_passes": fused_xla_bytes / (d_tree * 4),
        "encoder_fused_kernel_passes": kernel_bytes / (d_tree * 4),
        "encoder_fused_single_pass": bool(kernel_bytes <= tree_ideal + tol),
        "encoder_bytes_ratio": seed_bytes / max(kernel_bytes, 1.0),
        "encoder_xla_bytes_ratio": seed_bytes / max(fused_xla_bytes, 1.0),
    })
    print("\n== Encoder stats path (per objective evaluation, tree of "
          f"d={d_tree:,}) ==")
    print(f"  ideal (read gw + read t): {tree_ideal:,}")
    print(f"  seed reductions + recon : {seed_bytes:,.0f} "
          f"({results['encoder_seed_passes']:.1f} passes, cost_analysis)")
    print(f"  fused path (XLA)        : {fused_xla_bytes:,.0f} "
          f"({results['encoder_fused_xla_passes']:.1f} passes, cost_analysis; "
          f"{results['encoder_xla_bytes_ratio']:.1f}x less than seed)")
    print(f"  fused leafwise contract : {kernel_bytes:,.0f} "
          f"({results['encoder_fused_kernel_passes']:.2f} passes, leaf "
          f"accounting)")
    print(f"  [{'PASS' if results['encoder_fused_single_pass'] else 'FAIL'}] "
          f"fused stats path <= one read of each tree (+2% tolerance); "
          f"{results['encoder_bytes_ratio']:.1f}x fewer bytes than the seed "
          f"encoder reductions")

    # correctness sweep (also covered in tests/)
    checks = []
    for size in (1000, 131072, 300001):
        xs = jax.random.normal(jax.random.PRNGKey(size), (size,))
        ys = jax.random.normal(jax.random.PRNGKey(size + 1), (size,))
        got = flat.tree_stats(xs, ys)
        want = ref.fused_cosine(xs, ys)
        checks.append(bool(np.allclose(got, want, rtol=2e-4)))
    st_got = flat.tree_stats(gw, t)
    st_want = ref.fused_cosine(
        *[jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(tree)])
          for tree in (gw, t)])
    checks.append(bool(np.allclose(st_got, st_want, rtol=2e-4)))
    results["allclose"] = all(checks)
    print(f"  [{'PASS' if results['allclose'] else 'FAIL'}] "
          f"leafwise stats == oracle on flat vectors and a ragged tree")

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernels.json"), "w") as f:
        json.dump(results, f, indent=2)
    # trajectory artifact tracked from this PR onward (see ROADMAP) —
    # anchored to the repo root so any launch cwd updates the same file
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo_root, "BENCH_kernels.json"), "w") as f:
        json.dump(results, f, indent=2)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--quick", dest="quick", action="store_true", default=True,
                   help="small sizes, CPU-friendly (default)")
    g.add_argument("--full", dest="quick", action="store_false",
                   help="paper-scale sizes")
    args = ap.parse_args()
    run(quick=args.quick)
