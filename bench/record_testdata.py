"""Record the small chip trace that ``bench/tests`` reduce by hand.

    python3 bench/record_testdata.py OUT.xplane.pb.gz   # on a TPU; not part of a run

Builds the ``mlp-3sfc`` cell at a toy size (2,000 images, 2 clients, one
local step of batch 8, 2-round blocks), warms it, and traces three blocks
with the benchmark's own spans. Writes the gzipped trace to ``OUT``; copy
it to ``bench/testdata/tiny.xplane.pb.gz`` to replace the committed one.
"""
from __future__ import annotations

import glob
import gzip
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, spec  # noqa: E402

TINY_CONFIG = {"train_size": 2000, "test_size": 100}
TINY_TRAFFIC = {"clients": 2, "local_steps": 1, "batch": 8, "eval_every": 2}


def main(dst: str) -> None:
    os.environ[run.HOIST_ENV] = "1"
    import jax
    cell = spec.Cell(spec.benchmark(), "mlp-3sfc")
    run.tpu_devices(1)
    run.enable_cache()
    run.hoist_constants()
    from bench.families import vision
    program = vision.Program(dict(cell.config, **TINY_CONFIG),
                             dict(cell.traffic, **TINY_TRAFFIC), 7)
    run.window(program, 0.0, 2)
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tdir)
    try:
        run.window(program, 0.0, 3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f, gzip.open(dst, "wb") as g:
        g.write(f.read())
    shutil.rmtree(tdir)
    print(f"{dst}: {os.path.getsize(dst)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
