"""Dev scratch: validate entry lowering on a small host mesh + smoke configs."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import time

import jax

from repro.configs import base
from repro.configs.base import ARCH_IDS, ShapeConfig, get_smoke_config
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_mesh
from repro.utils import roofline as rl

# shrink the shape matrix + swap in smoke configs
SMALL_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 64, 8, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 128, 4, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 128, 8, "decode"),
    "long_500k": ShapeConfig("long_500k", 256, 1, "decode"),
}
specs_lib.INPUT_SHAPES = SMALL_SHAPES
specs_lib.LONG_CTX_WINDOW = 64
specs_lib.get_config = get_smoke_config

mesh = make_mesh((4, 2), ("data", "model"))

archs = sys.argv[1:] or ARCH_IDS
for arch in archs:
    for shape in SMALL_SHAPES:
        t0 = time.time()
        try:
            made = specs_lib.make_entry(arch, shape, mesh)
            if made is None:
                print(f"SKIP {arch} x {shape}")
                continue
            entry, args = made
            lowered = jax.jit(entry).lower(*args)
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            coll = rl.collective_bytes(compiled.as_text())
            print(f"OK {arch:24s} {shape:12s} {time.time()-t0:5.1f}s "
                  f"flops={cost.get('flops', 0):.3g} coll={sum(coll.values()):,}")
        except Exception as e:
            import traceback; traceback.print_exc()
            print(f"FAIL {arch} x {shape}: {type(e).__name__}: {str(e)[:300]}")
            sys.exit(1)
print("ALL LOWERED")
