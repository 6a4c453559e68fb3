"""A cell's per-layer metrics from a trace of its own, with the seconds
under each of the program's named scopes and the block boundary's idle
time by the program's own host span.

    python3 bench/phasetrace.py --workload mlp-3sfc --seed 7      # on a TPU
    python3 bench/phasetrace.py --workload mlp-3sfc --seed 7 --tiny \\
        --out bench/testdata/scoped        # re-record the committed fixture

Builds the cell's program as ``bench/run.py`` does, warms it, traces a
``run.TRACE_SECONDS`` window of back-to-back blocks, attaches the block
executable's optimized HLO text (the program's ``block_hlo``) to the trace
after the window, and prints one JSON object: the cell's per-layer metrics
as its readers read them in a ``--trace 1`` run (no reference runs, so no
``correct``), the self seconds per round under each named scope, the
boundary's idle milliseconds per block by innermost span
(``engine.dispatch``, ``engine.sync``, ``bench.eval``, ...), the share of
the window's block operations found in the HLO text, the breakdown, and
the unscoped operations with the most self time. The reduction is
``bench/devtrace.py``'s. With ``--out PREFIX`` it also writes
``PREFIX.xplane.pb.gz`` and ``PREFIX.hlo.txt.gz``; ``--tiny`` builds the
cell at ``bench/record_testdata.py``'s toy size and traces three blocks.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import importlib
import json
import os
import shutil
import sys
import tempfile
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import devtrace, run, spec  # noqa: E402


def record(workload: str, seed: int, tiny: bool, out: Optional[str]):
    """Trace a window of ``workload`` on the chip; returns the cell (at the
    toy size where ``tiny``), the view with the block's HLO text attached,
    the window's rounds and the device's peaks."""
    os.environ[run.HOIST_ENV] = "1"
    import jax
    cell = spec.Cell(spec.benchmark(), workload)
    devices = run.tpu_devices(cell.chips)
    peaks = run.device_peaks(devices[0].device_kind)
    run.enable_cache()
    run.hoist_constants()
    if tiny:
        from bench.record_testdata import TINY_CONFIG, TINY_TRAFFIC
        cell.config = dict(cell.config, **TINY_CONFIG)
        cell.traffic = dict(cell.traffic, **TINY_TRAFFIC)
    family = importlib.import_module(f"bench.families.{cell.config['family']}")
    program = family.Program(cell.config, cell.traffic, seed % run.SEED_MOD)
    run.window(program, 0.0, 2)
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tdir)
        try:
            w = run.window(program, 0.0 if tiny else run.TRACE_SECONDS,
                           run.MIN_TRACED_BLOCKS)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True)
        text = program.block_hlo()
        view = devtrace.load(path, cell.chips)
        if out:
            with open(path, "rb") as f, \
                    gzip.open(out + ".xplane.pb.gz", "wb") as g:
                g.write(f.read())
            with gzip.open(out + ".hlo.txt.gz", "wt") as g:
                g.write(text)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    program.close()
    view.attach_hlo(text)
    return cell, view, w.rounds, peaks


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell, view, rounds, peaks = record(args.workload, args.seed, args.tiny,
                                       args.out)
    blocks = len(view.blocks)
    ctx = run.layer_context(cell, view, rounds, blocks, peaks)
    result = {name: m["value"]
              for name, m in run.layer_metrics(cell, ctx).items()}
    result.update(
        rounds=rounds, blocks=blocks, hlo_matched=list(view.hlo_matched()),
        scope_ms_per_round={s: 1e3 * t / rounds for s in view.scope_names()
                            if (t := view.scope_s(s)) is not None},
        boundary_idle_ms_per_block={
            k: 1e3 * v / blocks for k, v in view.boundary_idle_s().items()},
        breakdown=view.breakdown(), unscoped_ops=view.unscoped_ops())
    print(json.dumps(result))


if __name__ == "__main__":
    main()
