"""A run of a cell with the program exactly as ``train_vision`` builds it.

    python3 bench/unhoisted.py --workload mlp-3sfc --seed 7 --seconds 30 --trace 0

The same run as ``bench/run.py``, except that closed-over device arrays
stay embedded in the executables as constants (JAX's default) and each
client pool keeps the engine's own width, the largest pool. Every seed is
then a program of its own, compiled in set-up. Not part of the benchmark:
it shows that hoisting and padding change neither the rounds per second
nor the peak memory of the window.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402

if __name__ == "__main__":
    run.HOIST_ENV = "BENCH_UNHOISTED"    # main sets this, which JAX never reads
    run.hoist_constants = lambda: None
    from bench.families import vision
    vision.FIXED_POOL_WIDTH = False
    run.main()
