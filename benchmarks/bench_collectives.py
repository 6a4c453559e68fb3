"""On-mesh wire-bytes accounting for the sharded client fan-out.

The fused-decode path's claim (fl/round.py) is a *collective-bill* claim:
with clients sharded over ``client_axes(mesh)``, the naive server path must
move O(d) bytes per device per round (the full-gradient gather — FedAvg's
bill), while the fused 3SFC path moves only the O(N·payload) ``(D_syn, s)``
trees. This benchmark compiles BOTH shard_map round functions on a forced
8-device host-CPU mesh and reads the bill off the optimized HLO with the
trip-count-aware analyzer (``repro.utils.hlo_analyzer.collectives``) —
measured bytes, not a docstring. Gated:

* fused per-round collective bytes ≤ 1% of the naive path's (observed
  ~240x: 4d ≈ 797 KB vs ~3 KB at the paper MLP/MNIST shapes);
* fused bytes stay O(N·payload): ≤ 2x the local clients' (D_syn, s)
  payload bytes + 1 KiB of metrics-gather slack;
* the per-client local-train+encode region (the ``CLIENT_SCOPE`` named
  scope) contains ZERO collectives on either path;
* shard_map ≡ vmap oracle over 3 scanned rounds, all five compressors:
  bitwise for fedavg/dgc/signsgd/stc (their per-client math is
  vmap-width-invariant), and for 3SFC bitwise on a width-matched mesh
  (client axis 1) plus ≤1e-5 max |Δparams| on the 8-way mesh — XLA CPU
  lowers batched dots differently per vmap width (~1e-8 observed), so
  gradient-in-the-loop encoders are exact only at matched width; the
  width-matched case isolates the shard_map plumbing itself.

The 8-device mesh needs ``--xla_force_host_platform_device_count=8`` BEFORE
jax initializes, so the measurement runs in a child process (``--child``)
and the orchestrator-facing ``run()`` parses its JSON. Emits
``BENCH_collectives.json`` (repo root) + ``experiments/results/
collectives.json`` for the ``scripts/check_bench.py`` trajectory gate.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def multidev_env() -> Dict[str, str]:
    """Child environment for forced-8-device host-CPU runs: the XLA device
    flag (must precede jax init), CPU platform pin, and src+repo on
    PYTHONPATH. Shared with the tests' ``multidev`` subprocess runner
    (tests/conftest.py) so the recipe lives in one place."""
    env = os.environ.copy()
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), REPO]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


N_CLIENTS = 8                      # divisible over the 8-device client axis
LOCAL_STEPS, LOCAL_BATCH = 5, 32   # paper MLP/MNIST round shape
EXACT_ROUNDS = 3
THREESFC_TOL = 1e-5


def _child() -> Dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import CompressorConfig, FLConfig
    from repro.configs.run import RunConfig
    from repro.core import flat
    from repro.core.strategy import make_strategy
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.budget import matched_compressors
    from repro.fl.engine import RoundEngine, device_pools, vision_batcher
    from repro.analysis import collective_summary
    from repro.fl.round import build_fl_round, fl_init
    from repro.fl.sharding import make_fl_shardings
    from repro.launch.mesh import make_mesh
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import MNIST_SPEC, make_paper_model

    assert len(jax.devices()) == 8, \
        f"child expected 8 forced host devices, got {len(jax.devices())}"
    mesh = make_mesh((8, 1), ("data", "model"))
    sh = make_fl_shardings(mesh)
    # width-matched mesh: client axis of size 1 -> each "shard" runs the
    # full vmap width, isolating the shard_map plumbing from XLA's
    # width-dependent batched-dot lowering
    mesh_w = make_mesh((1, 8), ("data", "model"))
    sh_w = make_fl_shardings(mesh_w)

    model = make_paper_model("mlp", MNIST_SPEC)
    params = model.init(jax.random.PRNGKey(0))
    d = flat.tree_size(params)

    # ---- wire accounting at paper round shapes ---------------------------
    ccfg = matched_compressors("mlp", MNIST_SPEC, d)["threesfc"]
    spec = vision_syn_spec(MNIST_SPEC, ccfg)
    payload_floats = float(spec.floats + 1)
    strat = make_strategy(ccfg, loss_fn=model.syn_loss, syn_spec=spec,
                          local_lr=0.01)
    cfg = FLConfig(num_clients=N_CLIENTS, local_steps=LOCAL_STEPS,
                   local_lr=0.01, local_batch=LOCAL_BATCH, compressor=ccfg)
    run_sh = RunConfig(fl=cfg, client_parallel="shard_map", mesh=mesh)
    naive_rf = build_fl_round(model.loss, strat, run_sh)
    fused_rf = build_fl_round(model.loss, strat,
                              run_sh.replace(fused_decode=True))

    state = fl_init(params, N_CLIENTS)
    batches = {
        "x": jax.ShapeDtypeStruct(
            (N_CLIENTS, LOCAL_STEPS, LOCAL_BATCH, *MNIST_SPEC.input_shape),
            jnp.float32),
        "y": jax.ShapeDtypeStruct((N_CLIENTS, LOCAL_STEPS, LOCAL_BATCH),
                                  jnp.int32),
    }
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def wire(rf) -> Dict:
        compiled = jax.jit(
            rf,
            in_shardings=(sh.state, sh.client, sh.replicated),
            out_shardings=(sh.state, sh.replicated),
        ).lower(state, batches, key).compile()
        # scope filter + byte census live ONCE, in repro.analysis — the
        # same extraction the check_static contract matrix gates on
        return collective_summary(compiled.as_text())

    print("compiling naive shard_map round...", file=sys.stderr)
    naive = wire(naive_rf)
    print("compiling fused shard_map round...", file=sys.stderr)
    fused = wire(fused_rf)

    # ---- shard_map == vmap oracle, 3 scanned rounds, 5 compressors -------
    EN, EK, EB = N_CLIENTS, 2, 8
    train = make_class_image_dataset(jax.random.PRNGKey(1), 512,
                                     MNIST_SPEC.input_shape, 10)
    parts = dirichlet_partition(train.y, EN, alpha=0.5, seed=0,
                                min_per_client=16)
    kinds = {
        "fedavg": CompressorConfig(kind="identity", error_feedback=False),
        "dgc": CompressorConfig(kind="topk", keep_ratio=0.05),
        "signsgd": CompressorConfig(kind="signsgd"),
        "stc": CompressorConfig(kind="stc", keep_ratio=0.05),
        "threesfc": CompressorConfig(kind="threesfc", syn_steps=2, syn_lr=0.1),
    }

    def engine_for(kcfg, shardings, mode, m):
        kspec = vision_syn_spec(MNIST_SPEC, kcfg)
        kstrat = make_strategy(kcfg, loss_fn=model.syn_loss, syn_spec=kspec,
                               local_lr=0.05)
        kfl = FLConfig(num_clients=EN, local_steps=EK, local_lr=0.05,
                       local_batch=EB, compressor=kcfg)
        pools = device_pools(parts)
        if shardings is not None:
            pools = shardings.place_pools(pools)
        eng = RoundEngine(
            build_fl_round(model.loss, kstrat,
                           RunConfig(fl=kfl, client_parallel=mode, mesh=m)),
            vision_batcher(train.x, train.y, pools, EK, EB),
            seed=0, shardings=shardings)
        return eng, eng.init_state(params, EN)

    def run3(kcfg, shardings, mode, m):
        eng, st = engine_for(kcfg, shardings, mode, m)
        return eng.run_block(st, EXACT_ROUNDS)

    def tree_equal(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    def tree_maxdiff(a, b):
        return max(float(jnp.max(jnp.abs(x - y)))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    exact: Dict[str, Dict] = {}
    for name, kcfg in kinds.items():
        print(f"exactness sweep: {name}...", file=sys.stderr)
        sv, mv = run3(kcfg, None, "vmap", None)
        ss, ms = run3(kcfg, sh, "shard_map", mesh)
        rec = {
            "params_bitexact": tree_equal(sv.params, ss.params),
            "ef_bitexact": tree_equal(sv.ef, ss.ef),
            "metrics_bitexact": all(
                np.array_equal(np.asarray(getattr(mv, f)),
                               np.asarray(getattr(ms, f)))
                for f in mv._fields),
            "max_abs_param_diff": tree_maxdiff(sv.params, ss.params),
        }
        if name == "threesfc":
            sw, _ = run3(kcfg, sh_w, "shard_map", mesh_w)
            rec["width_matched_bitexact"] = (
                tree_equal(sv.params, sw.params) and tree_equal(sv.ef, sw.ef))
        exact[name] = rec

    payload_bytes_local = 4.0 * payload_floats * (N_CLIENTS // sh.client_shards)
    return {
        "config": {
            "devices": 8, "mesh_shape": [8, 1], "client_axes": list(sh.axes),
            "model": "mlp", "dataset": "mnist", "model_params": d,
            "num_clients": N_CLIENTS, "local_steps": LOCAL_STEPS,
            "local_batch": LOCAL_BATCH, "payload_floats": payload_floats,
            "exact_rounds": EXACT_ROUNDS,
        },
        "naive": naive,
        "fused": fused,
        "payload_bytes_local": payload_bytes_local,
        "exact": exact,
    }


WIDTH_STABLE = ("fedavg", "dgc", "signsgd", "stc")


def _gate(results: Dict) -> Dict:
    # the fused-gather bound is the contract's, stated once in
    # repro.analysis.contracts and shared with the check_static matrix
    from repro.analysis.contracts import (FUSED_GATHER_FACTOR,
                                          FUSED_GATHER_SLACK_BYTES)
    naive_b = results["naive"]["collective_bytes_per_round"]
    fused_b = results["fused"]["collective_bytes_per_round"]
    exact = results["exact"]
    results["wire_ratio"] = naive_b / max(fused_b, 1.0)
    results["pass_wire_ratio"] = bool(fused_b <= 0.01 * naive_b)
    results["pass_payload_scaling"] = bool(
        fused_b <= FUSED_GATHER_FACTOR * results["payload_bytes_local"]
        + FUSED_GATHER_SLACK_BYTES)
    results["pass_encode_region_clean"] = bool(
        results["naive"]["encode_region_collectives"] == 0
        and results["fused"]["encode_region_collectives"] == 0)
    results["pass_bitexact"] = bool(
        all(exact[k]["params_bitexact"] and exact[k]["ef_bitexact"]
            and exact[k]["metrics_bitexact"] for k in WIDTH_STABLE)
        and exact["threesfc"]["width_matched_bitexact"])
    results["pass_threesfc_tol"] = bool(
        exact["threesfc"]["max_abs_param_diff"] <= THREESFC_TOL)
    results["pass"] = all(results[k] for k in (
        "pass_wire_ratio", "pass_payload_scaling", "pass_encode_region_clean",
        "pass_bitexact", "pass_threesfc_tol"))
    return results


def run(quick: bool = True, out_dir: str = "experiments/results") -> Dict:
    # ``quick`` is accepted for orchestrator symmetry but has no effect:
    # every number here is compile-time/deterministic (HLO bytes, bitwise
    # oracle over 3 short rounds) — there is no heavier "full" variant.
    del quick
    cmd = [sys.executable, "-m", "benchmarks.bench_collectives", "--child"]
    p = subprocess.run(cmd, env=multidev_env(), cwd=REPO, capture_output=True,
                       text=True, timeout=1800)
    if p.returncode != 0:
        sys.stderr.write(p.stdout + p.stderr)
        raise RuntimeError(
            f"bench_collectives child failed (exit {p.returncode})")
    results = _gate(json.loads(p.stdout))

    nb = results["naive"]["collective_bytes_per_round"]
    fb = results["fused"]["collective_bytes_per_round"]
    d = results["config"]["model_params"]
    print(f"\n== Per-round collective bytes (8-device host mesh, "
          f"mlp/mnist d={d}) ==")
    print(f"  naive decode : {nb:12.0f} B  "
          f"({results['naive']['collective_count']} collectives; "
          f"O(d) full-gradient gather, 4d = {4 * d} B)")
    print(f"  fused decode : {fb:12.0f} B  "
          f"({results['fused']['collective_count']} collectives; payload = "
          f"{results['payload_bytes_local']:.0f} B/device)")
    print(f"  [{'PASS' if results['pass_wire_ratio'] else 'FAIL'}] fused <= 1% "
          f"of naive wire bytes ({results['wire_ratio']:.0f}x less)")
    print(f"  [{'PASS' if results['pass_payload_scaling'] else 'FAIL'}] fused "
          f"bytes are O(N*payload) (<= 2x payload + 1KiB slack)")
    print(f"  [{'PASS' if results['pass_encode_region_clean'] else 'FAIL'}] "
          f"zero collectives inside the per-client encode region "
          f"(naive {results['naive']['encode_region_collectives']}, "
          f"fused {results['fused']['encode_region_collectives']})")
    ex = results["exact"]
    stable = all(ex[k]["params_bitexact"] for k in WIDTH_STABLE)
    print(f"  [{'PASS' if results['pass_bitexact'] else 'FAIL'}] shard_map == "
          f"vmap oracle over {results['config']['exact_rounds']} rounds "
          f"(bitwise: {', '.join(WIDTH_STABLE)} = {stable}; threesfc "
          f"width-matched = {ex['threesfc']['width_matched_bitexact']})")
    print(f"  [{'PASS' if results['pass_threesfc_tol'] else 'FAIL'}] threesfc "
          f"8-way max |dparams| = {ex['threesfc']['max_abs_param_diff']:.1e} "
          f"<= {THREESFC_TOL:.0e} (XLA batched-dot lowering is vmap-width-"
          f"dependent; exactness is defined width-matched)")

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "collectives.json"), "w") as f:
        json.dump(results, f, indent=2)
    with open(os.path.join(REPO, "BENCH_collectives.json"), "w") as f:
        json.dump(results, f, indent=2)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="measurement half (needs the 8-device XLA flag "
                         "already in the environment); prints JSON to stdout")
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--quick", dest="quick", action="store_true", default=True,
                   help="accepted for orchestrator symmetry; the measurement "
                        "is deterministic, quick == full")
    g.add_argument("--full", dest="quick", action="store_false")
    args = ap.parse_args()
    if args.child:
        json.dump(_child(), sys.stdout)
        return
    run(quick=args.quick)


if __name__ == "__main__":
    main()
