"""Smoke run of the federated round on TPU chips, through the user entry points.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # the shard_map client fan-out only

One chip:
  kernels    the Pallas kernels at the paper MLP's width (d = 199,210),
             compiled (``tpu_custom_call`` in the executable), and the
             leafwise tree stats and EF update of the 3SFC encode, all
             checked against ``repro.kernels.ref``;
  3SFC       ``repro.launch.train.main`` at the paper's MLP/MNIST round
             (10 clients, 5 local steps, batch 32) for 20 rounds, float
             wire and codec wire;
  signSGD    the same round through the 1-bit codec (the bitpack kernels);
  convnet    3SFC on the CIFAR-10 convnet (grad-of-grad through convs).
Four chips (``--chips 4``): 8 clients fanned out with shard_map over a
4-chip mesh for 3SFC (float, codec, fused decode) and FedAvg, each compared
with the vmap round on one chip in the same process.

Each phase prints one line; the last line is one JSON object naming the
device. Wall times printed are smoke numbers from a single run, not
benchmark results. Outputs go to ``chiprun_out/chip_smoke/``. Without a TPU
the script exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ROUNDS, EVAL_EVERY = 20, 10
PAPER_ROUND = ["--clients", "10", "--local-steps", "5", "--batch", "32"]
MLP = ["--model", "mlp", "--dataset", "mnist"]
SHARD_TOL = 1e-5     # 3SFC shard_map vs vmap: the repo's width tolerance


def _ravel(tree) -> jax.Array:
    return jnp.concatenate([jnp.ravel(l) for l in jax.tree_util.tree_leaves(tree)])


def _max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def phase_kernels() -> str:
    """The Pallas kernels, compiled, and the leafwise tree reductions,
    against the plain-jnp oracles."""
    from repro.core import flat
    from repro.kernels import bitpack, ref
    from repro.models.cnn import MNIST_SPEC, make_paper_model

    params = make_paper_model("mlp", MNIST_SPEC).init(jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 3 * len(leaves)))

    def rand_tree():
        return jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(next(keys), l.shape, jnp.float32) for l in leaves])

    a, b, c = rand_tree(), rand_tree(), rand_tree()
    d = int(_ravel(a).size)
    s = jnp.float32(0.37)
    x = _ravel(c).at[::97].set(0.0)        # exact zeros: the bit = x >= 0 rule

    va, vb = _ravel(a), _ravel(b)
    stats = np.asarray(jax.jit(flat.tree_stats)(a, b))
    want = np.asarray(ref.fused_cosine(va, vb))
    scale = math.sqrt(want[1] * want[2])
    np.testing.assert_allclose(stats, want, rtol=1e-5, atol=1e-5 * scale)

    ef = np.asarray(_ravel(jax.jit(flat.tree_ef_update)(a, b, s)))
    ef_want = np.asarray(ref.ef_update(va, vb, s))
    np.testing.assert_allclose(ef, ef_want, rtol=1e-6, atol=1e-6)

    unpack = jax.jit(lambda w: bitpack.unpack_signs(w, d))
    words = jax.jit(bitpack.pack_signs)(x)
    xn = np.asarray(x)
    # the codec pads the last word with +1.0 floats, i.e. set bits
    want_words = np.packbits(np.pad(xn >= 0, (0, -d % 32), constant_values=True),
                             bitorder="little").view("<u4")
    np.testing.assert_array_equal(np.asarray(words), want_words)
    np.testing.assert_array_equal(np.asarray(unpack(words)),
                                  np.asarray(jnp.where(x >= 0, 1.0, -1.0)))

    compiled = {
        "pack_signs": (bitpack.pack_signs, (x,)),
        "unpack_signs": (lambda w: bitpack.unpack_signs(w, d), (words,)),
    }
    for name, (f, args) in compiled.items():
        if "tpu_custom_call" not in jax.jit(f).lower(*args).compile().as_text():
            raise AssertionError(f"{name}: no tpu_custom_call in the "
                                 f"compiled program (kernel not compiled)")
    return (f"[kernels] d={d}: tree stats {stats.tolist()} vs ref "
            f"{want.tolist()}; EF max |err| "
            f"{float(np.max(np.abs(ef - ef_want)))}; pack/unpack bit-exact "
            f"({words.size} words, {int(np.sum(xn == 0))} exact zeros); "
            f"tpu_custom_call in {len(compiled)}/{len(compiled)} kernels")


def phase_train(tag: str, flags) -> str:
    """``train.main`` for ROUNDS rounds; the loss must be finite and fall."""
    from repro.launch import train

    out = os.path.join(OUT, tag)
    os.makedirs(out, exist_ok=True)
    argv = (["--rounds", str(ROUNDS), "--eval-every", str(EVAL_EVERY),
             "--out", out] + PAPER_ROUND + list(flags))
    with open(os.path.join(out, "stdout.log"), "w") as log, \
            contextlib.redirect_stdout(log):
        train.main(argv)
    with open(os.path.join(out, "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    losses = [r["loss"] for r in recs]
    if not all(math.isfinite(v) for v in losses) or losses[-1] >= losses[0]:
        raise AssertionError(f"{tag}: loss not finite and falling: {losses}")
    first = recs[0]["elapsed_s"]
    per_round = (recs[-1]["elapsed_s"] - first) / (ROUNDS - EVAL_EVERY)
    return (f"[{tag}] {' '.join(flags)}: loss {losses} at rounds "
            f"{[r['round'] for r in recs]}, acc {[r['acc'] for r in recs]}; "
            f"smoke timing, not a benchmark: first {EVAL_EVERY} rounds "
            f"{first} s incl. compile (~{first - EVAL_EVERY * per_round} s "
            f"compile), settled {per_round} s/round incl. eval")


def phase_shard4() -> str:
    """shard_map over a 4-chip mesh vs the vmap round on one chip."""
    from repro.configs.base import FLConfig
    from repro.configs.run import RunConfig
    from repro.core import flat
    from repro.core.strategy import make_strategy
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.budget import matched_compressors
    from repro.fl.engine import RoundEngine, device_pools, vision_batcher
    from repro.fl.round import build_fl_round
    from repro.fl.sharding import make_fl_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import MNIST_SPEC, make_paper_model

    n_clients, steps, batch, rounds, lr = 8, 5, 32, 3, 0.01
    model = make_paper_model("mlp", MNIST_SPEC)
    params = model.init(jax.random.PRNGKey(0))
    comps = matched_compressors("mlp", MNIST_SPEC, flat.tree_size(params))
    data = make_class_image_dataset(jax.random.PRNGKey(0), 4000,
                                    MNIST_SPEC.input_shape,
                                    MNIST_SPEC.num_classes)
    parts = dirichlet_partition(data.y, n_clients, alpha=0.5, seed=0,
                                min_per_client=batch)
    mesh = make_host_mesh()
    sh = make_fl_shardings(mesh)
    devices = set(jax.devices())

    def run(kind, wire, fused, sharded):
        comp = comps[kind]
        strategy = make_strategy(comp, loss_fn=model.syn_loss,
                                 syn_spec=vision_syn_spec(MNIST_SPEC, comp),
                                 local_lr=lr)
        fl = FLConfig(num_clients=n_clients, local_steps=steps, local_lr=lr,
                      local_batch=batch, compressor=comp, seed=0)
        rc = RunConfig(fl=fl, wire=wire, fused_decode=fused,
                       client_parallel="shard_map" if sharded else "vmap",
                       mesh=mesh if sharded else None)
        codec = strategy.wire_codec(params, policy=rc.wire_policy) \
            if wire == "codec" else None
        pools = device_pools(parts)
        if sharded:
            pools = sh.place_pools(pools)
        engine = RoundEngine(
            build_fl_round(model.loss, strategy, rc, codec=codec),
            vision_batcher(data.x, data.y, pools, steps, batch),
            seed=0, shardings=sh if sharded else None)
        state, metrics = engine.run_block(
            engine.init_state(params, n_clients, strategy), rounds)
        if not np.isfinite(np.asarray(metrics.loss)).all():
            raise AssertionError(f"{kind}/{wire}: non-finite loss")
        return state

    results, failed = [], False
    for kind, wire, fused in (("fedavg", "float", False),
                              ("threesfc", "float", False),
                              ("threesfc", "codec", False),
                              ("threesfc", "float", True)):
        tag = f"{kind}/{wire}{'/fused' if fused else ''}"
        ref_state = run(kind, wire, fused, sharded=False)
        state = run(kind, wire, fused, sharded=True)
        for leaf in jax.tree_util.tree_leaves((state.params, state.ef)):
            if leaf.sharding.device_set != devices:
                raise AssertionError(f"{tag}: a state leaf lives on "
                                     f"{leaf.sharding.device_set} only")
        for leaf in jax.tree_util.tree_leaves(state.ef):
            if leaf.sharding.is_fully_replicated:
                raise AssertionError(f"{tag}: EF is replicated, not sharded")
        dp = _max_abs_diff(ref_state.params, state.params)
        de = _max_abs_diff(ref_state.ef, state.ef)
        tol = 0.0 if kind == "fedavg" else SHARD_TOL
        ok = dp <= tol and de <= tol          # False on NaN too
        failed = failed or not ok
        results.append(f"{tag} params {dp} EF {de}"
                       + ("" if ok else f" NOT <= {tol}"))
    line = (f"[shard_map x{len(devices)}] {n_clients} clients, {rounds} "
            f"rounds vs vmap on one chip, max |diff| (fedavg must be 0, "
            f"3SFC <= {SHARD_TOL}): " + "; ".join(results))
    if failed:
        raise AssertionError(line)
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every single-chip phase; 4: only the shard_map "
                         "fan-out over a 4-chip mesh and its vmap reference")
    args = ap.parse_args(argv)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{device['platform']!r}); nothing was run")
    if device["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {device['count']}")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(OUT, exist_ok=True)
    if args.chips == 4:
        phases = [phase_shard4]
    else:
        phases = [
            phase_kernels,
            lambda: phase_train("3sfc_float", MLP + [
                "--compressor", "threesfc", "--wire", "float"]),
            lambda: phase_train("3sfc_codec", MLP + [
                "--compressor", "threesfc", "--wire", "codec"]),
            lambda: phase_train("signsgd_codec", MLP + [
                "--compressor", "signsgd", "--wire", "codec"]),
            lambda: phase_train("convnet_3sfc", [
                "--model", "convnet", "--dataset", "cifar10",
                "--compressor", "threesfc"]),
        ]
    for phase in phases:
        t0 = time.perf_counter()
        line = phase()
        print(f"{line} [phase wall {time.perf_counter() - t0} s]", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
