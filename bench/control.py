"""Readings that the limits of ``correct`` are set from; not part of a run.

    python3 bench/control.py --workload mlp-3sfc --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --out readings/mlp-3sfc.json

On the chip, at the cell's own size, in one process:

* lower readings: for each of ``--seeds``, the program's first
  ``correct.STEPS`` blocks (as a run drives them in set-up) against the
  reference, the numbers of ``bench/correct.py``;
* upper readings: for each of ``--control-seeds``, the reference put in
  the program's place and computed in bfloat16 (the control: the precision
  below the configuration's float32), and the reference with each planted
  fault of ``vision_ref.FAULTS`` that the cell can have, each against the
  float32 reference;
* the witness of amplification: the float32 reference with one rounding
  changed (its dot products summed over the leaves in reverse order)
  against itself.

Writes the raw readings to ``--out`` (JSON) and prints, per number,
the largest lower and the smallest upper reading.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import correct, run, spec  # noqa: E402


def _reversed_vdot():
    import jax
    import jax.numpy as jnp

    def vdot(a, b):
        parts = [jnp.sum(x * y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                                 jax.tree_util.tree_leaves(b))]
        return sum(reversed(parts))
    return vdot


def readings(cell, seeds, control_seeds):
    import jax.numpy as jnp
    fam = cell.config["family"]
    family = importlib.import_module(f"bench.families.{fam}")
    ref_mod = importlib.import_module(f"bench.families.{fam}_ref")
    cfg, trf, chips = cell.config, cell.traffic, cell.chips
    raw = {"program": {}, "reference": {}, "control": {}, "perturbed": {}}
    faults = [f for f in ref_mod.FAULTS if f != "no_exchange" or chips > 1]
    raw.update({f"fault:{f}": {} for f in faults})

    def reference(seed, **kw):
        return correct.summarize(ref_mod.run_reference(
            cfg, trf, seed, blocks=correct.STEPS, chips=chips, **kw))

    for seed in seeds:
        s = seed % run.SEED_MOD
        t = time.perf_counter()
        program = family.Program(cfg, trf, s)
        raw["program"][seed] = run.first_steps(program)
        program.close()
        del program
        gc.collect()
        raw["reference"][seed] = reference(s)
        print(f"seed {seed}: program {_numbers(raw, 'program', seed)} "
              f"({time.perf_counter() - t:.1f} s)", flush=True)
    for seed in control_seeds:
        s = seed % run.SEED_MOD
        if seed not in raw["reference"]:
            raw["reference"][seed] = reference(s)
        raw["control"][seed] = reference(s, dtype=jnp.bfloat16)
        for f in faults:
            raw[f"fault:{f}"][seed] = reference(s, fault=f)
        orig = ref_mod._vdot
        ref_mod._vdot = _reversed_vdot()
        try:
            raw["perturbed"][seed] = reference(s)
        finally:
            ref_mod._vdot = orig
        for kind in raw:
            if kind not in ("program", "reference"):
                print(f"seed {seed}: {kind} {_numbers(raw, kind, seed)}",
                      flush=True)
    return {"raw": raw, "summary": summary(raw)}


def _numbers(raw, kind, seed):
    return correct.numbers(raw[kind][seed], raw["reference"][seed])


def summary(raw):
    """Per number: the largest reading of the program, and the smallest of
    the control, of each fault and the largest of the perturbed reference."""
    out = {}
    for k in correct.NUMBERS:
        row = {}
        for kind, runs in raw.items():
            if kind == "reference" or not runs:
                continue
            vals = [_numbers(raw, kind, seed)[k] for seed in runs]
            row[kind] = max(vals) if kind in ("program", "perturbed") \
                else min(vals)
        out[k] = row
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", required=True, dest="control_seeds")
    ap.add_argument("--out", required=True, help="where the raw readings go")
    args = ap.parse_args(argv)
    os.environ[run.HOIST_ENV] = "1"
    run.hoist_constants()
    cell = spec.Cell(spec.benchmark(), args.workload)
    run.tpu_devices(cell.chips)
    run.enable_cache()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    out = readings(cell, ints(args.seeds), ints(args.control_seeds))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["summary"]))


if __name__ == "__main__":
    main()
