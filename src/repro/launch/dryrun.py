import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-lower + compile every (arch x input-shape) pair on
the production mesh and harvest memory/cost/collective analyses.

The two XLA_FLAGS lines above MUST run before any other import (jax locks
the device count on first init) — that is why they sit above the docstring.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Per pair the run writes experiments/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, collective bytes, and the roofline terms.
Failures (sharding mismatch, unsupported collective) are bugs in this repo's
sharding rules — they raise, they are not skipped.
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.utils import roofline as rl

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def tokens_for(arch: str, shape_name: str) -> float:
    s = INPUT_SHAPES[shape_name]
    if s.mode == "train":
        return float(s.global_batch * s.seq_len)
    if s.mode == "prefill":
        return float(s.global_batch * s.seq_len)
    return float(s.global_batch)      # decode: one token per sequence


def run_pair(arch: str, shape_name: str, multi_pod: bool = False,
             save: bool = True, verbose: bool = True,
             variant: Optional[dict] = None, tag: str = "",
             mesh_shape: Optional[tuple] = None) -> Optional[dict]:
    if mesh_shape:                      # §Perf mesh reshape (e.g. (4, 64))
        mesh = make_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    made = specs_lib.make_entry(arch, shape_name, mesh, variant=variant)
    if made is None:
        if verbose:
            print(f"SKIP {arch} x {shape_name} (documented skip, DESIGN.md §5)")
        return None
    entry, args = made
    t0 = time.time()
    with mesh:
        lowered = jax.jit(entry).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    shape = INPUT_SHAPES[shape_name]
    mode = "train" if shape.mode == "train" else "serve"
    cfg = specs_lib.serving_config(get_config(arch), shape)
    mf = rl.model_flops_estimate(cfg, tokens_for(arch, shape_name), mode)
    roof = rl.from_compiled(compiled, chips, mf, hlo_text=hlo)
    mesh_name = ("x".join(map(str, mesh_shape)) if mesh_shape
                 else ("2x16x16" if multi_pod else "16x16"))
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "variant": variant or {},
        "tag": tag,
        "chips": chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # CompiledMemoryStats is PER-DEVICE (verified empirically)
        "memory_per_dev": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
        # raw cost_analysis (NOTE: while bodies counted once — reference only)
        "xla_cost": {k: cost.get(k, 0.0) for k in
                     ("flops", "bytes accessed", "transcendentals")},
        "roofline": roof.as_dict(),
    }
    if verbose:
        args_gib = result["memory_per_dev"]["argument_bytes"] / 2**30
        peak_gib = result["memory_per_dev"]["peak_bytes"] / 2**30
        print(f"OK   {arch} x {shape_name} [{result['mesh']}]  "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s  "
              f"args/dev {args_gib:.2f} GiB peak/dev {peak_gib:.2f} GiB  "
              f"dominant={roof.dominant}  "
              f"C/M/X = {roof.compute_s:.3e}/{roof.memory_s:.3e}/"
              f"{roof.collective_s:.3e} s")
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = f"{arch}__{shape_name}__{result['mesh']}{suffix}"
        with open(os.path.join(OUT_DIR, fn + ".json"), "w") as f:
            json.dump(result, f, indent=2)
        # keep the per-device HLO so rooflines can be re-derived without
        # recompiling (analyzer iterations are free afterwards)
        import gzip
        with gzip.open(os.path.join(OUT_DIR, fn + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", type=str, default="",
                    help='JSON §Perf knobs, e.g. \'{"fused_decode": true}\'')
    ap.add_argument("--tag", type=str, default="")
    ap.add_argument("--mesh", type=str, default="",
                    help="override mesh shape, e.g. 4,64")
    args = ap.parse_args()
    variant = json.loads(args.variant) if args.variant else None
    mesh_shape = tuple(int(x) for x in args.mesh.split(",")) if args.mesh else None

    pairs = []
    if args.all:
        pairs = [(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    failures = []
    for arch, shape in pairs:
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        out = os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh_name}.json")
        if args.skip_existing and os.path.exists(out):
            print(f"CACHED {arch} x {shape}")
            continue
        try:
            run_pair(arch, shape, multi_pod=args.multi_pod, variant=variant,
                     tag=args.tag, mesh_shape=mesh_shape)
        except Exception as e:                     # noqa: BLE001
            traceback.print_exc()
            failures.append((arch, shape, str(e)[:200]))
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall pairs lowered + compiled")


if __name__ == "__main__":
    main()
