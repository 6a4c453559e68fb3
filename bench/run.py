"""Chip benchmark of the scanned FL round, one cell per run.

    python3 bench/run.py --workload mlp-3sfc --seed 7 --seconds 30 --trace 0

A run is one process: it finds the cell in ``BENCHMARK.json`` (see
``bench/spec.py`` for the files it names), checks that JAX sees a TPU with
as many chips as the cell asks for, builds the cell's program from the seed,
drives its first ``correct.STEPS`` eval blocks through the window's own
call (set-up, compilation included), then measures back-to-back blocks for
``--seconds`` seconds. Each block is the engine's scanned, donated
``run_block`` and the held-out eval after it, one block in flight.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs a
short window under ``jax.profiler`` and prints its per-layer metrics (one
reader per metric in ``bench/metrics/``) and a breakdown; after that
window, where the family's program gives its block executable's optimized
HLO text (``block_hlo``), the trace's operations are named by the
program's scopes from it (``bench/devtrace.py``). Either way the
run then frees the program, follows the same first blocks with the plain
reference and compares (``bench/correct.py``). The last stdout line is one
JSON object; the numbers compared, each beside its limit, are also the last
lines on stderr. The trace is reduced in the run and deleted.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero before it builds anything and prints no result.
"""
from __future__ import annotations

import os
import time

_T0 = time.perf_counter()

import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import argparse  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import correct, devtrace, flops, spec  # noqa: E402

TRACE_SECONDS = 2.0          # the traced window; tracing slows the host
MIN_TRACED_BLOCKS = 3
SEED_MOD = 2 ** 31 - 1       # PRNGKey keeps 32 bits; a --seed may be wider
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HOIST_ENV = "JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS"   # read when JAX is imported


class BenchError(SystemExit):
    """Ends the run with a non-zero exit and no result line."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def hoist_constants() -> None:
    """Closed-over device arrays become arguments of a compiled program.

    The engine's batcher closes over the training set. By JAX's default a
    closed-over array is embedded in the executable as a constant, which
    makes every seed a new program (about 230 MB of constants for MNIST,
    750 MB for CIFAR-10) that compiles anew and misses the cache. With
    ``HOIST_ENV`` set before JAX is imported (``main`` sets it) they are
    hoisted to arguments, so one cached program serves every seed. Only a
    benchmark process (``main``) hoists; ``run`` alone compiles as JAX
    does by default. JAX 0.9 fails to hoist host (numpy) constants, such
    as the codec's tables, so only device arrays are hoisted and host
    constants stay embedded, as by default. Without ``HOIST_ENV`` nothing
    is hoisted."""
    import jax
    from jax._src import core
    if getattr(core.jaxpr_const_args, "device_only", False):
        return
    hoisted = core.jaxpr_const_args

    def device_const_args(jaxpr):
        return [(c, aval) for c, aval in hoisted(jaxpr)
                if isinstance(c, jax.Array)]

    device_const_args.device_only = True
    core.jaxpr_const_args = device_const_args


def tpu_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r}); "
                         f"nothing was run")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def device_peaks(kind: str):
    table = spec.load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise BenchError(f"device kind {kind!r} has no peaks in "
                         f"bench/peaks.json")
    return table[kind]


def load_reader(name: str):
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", spec.metric_path(name))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def layer_context(cell, view, rounds: int, blocks: int, peaks):
    """What a per-layer reader reads: the traced window's ``view``, its
    ``rounds`` and ``blocks``, the cell's ``chips``, ``config`` and
    ``traffic``, the device's ``peaks``, and ``round_flops``, the
    operations a round requires by the family's count
    (``flops.for_family``), or None where the family brings none."""
    counter = flops.for_family(cell.config)
    round_flops = None if counter is None else \
        counter.round_flops(cell.config, cell.traffic)
    return SimpleNamespace(view=view, rounds=rounds, blocks=blocks,
                           chips=cell.chips, config=cell.config,
                           traffic=cell.traffic, peaks=peaks,
                           round_flops=round_flops)


def layer_metrics(cell, ctx):
    """The cell's per-layer metrics, each read by its own reader; one whose
    reader finds nothing to read (None) is left out."""
    metrics = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


class CompileCounter:
    """Counts backend compiles (cache hits included) while armed."""

    def __init__(self):
        import jax.monitoring
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def window(program, seconds: float, min_blocks: int = 1):
    """Back-to-back blocks until ``seconds`` have passed (whole blocks)."""
    import jax
    times, rounds, failed = [], 0, 0
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.block"):
            ms, _ = program.run_block(jax.profiler.TraceAnnotation)
        t1 = time.perf_counter()
        times.append(t1 - t0)
        loss = np.asarray(ms.loss)
        rounds += loss.size
        failed += int(np.sum(~np.isfinite(loss)))
        if t1 - start >= seconds and len(times) >= min_blocks:
            return SimpleNamespace(block_s=times, rounds=rounds, failed=failed,
                                   wall_s=t1 - start, last=ms)


def first_steps(program):
    """Drive the program's first ``correct.STEPS`` blocks through the
    window's own call; returns their ``correct.summarize`` summary."""
    import jax
    losses, aggs, params = [], [], [program.params0]
    for _ in range(correct.STEPS):
        ms, _ = program.run_block(jax.profiler.TraceAnnotation)
        losses.append(np.asarray(ms.loss, np.float32))
        aggs.append(np.asarray(ms.update_norm, np.float32))
        params.append(program.params())
    return correct.summarize({"loss": np.concatenate(losses),
                              "agg": np.concatenate(aggs), "params": params})


def peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def traced_window(program, chips: int):
    """The window under ``jax.profiler``; the trace is written to a
    temporary directory, reduced, and deleted."""
    import jax
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tdir)
        try:
            w = window(program, TRACE_SECONDS, MIN_TRACED_BLOCKS)
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise BenchError(f"expected one trace under {tdir}, found {paths}")
        return w, devtrace.load(paths[0], chips)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def run(cell, seed: int, seconds: float, trace: bool, devices, peaks):
    """One run of ``cell`` on ``devices``; returns (result, check lines)."""
    fam = cell.config["family"]
    family = importlib.import_module(f"bench.families.{fam}")
    reference = importlib.import_module(f"bench.families.{fam}_ref")
    used = devices[:cell.chips]
    seed_e = seed % SEED_MOD
    counter = CompileCounter()

    t_build = time.perf_counter()
    program = family.Program(cell.config, cell.traffic, seed_e)
    t_first = time.perf_counter()
    readings = first_steps(program)
    setup_s = time.perf_counter() - _T0
    phases = dict(start=t_build - _T0, **program.phases,
                  first_block=setup_s - (t_first - _T0))
    setup_peak = peak_bytes(used)

    counter.armed = True
    if trace:
        w, view = traced_window(program, cell.chips)
    else:
        w, view = window(program, seconds), None
    counter.close()
    peak = peak_bytes(used)
    print(f"device: {used[0].platform} {used[0].device_kind} x{len(used)} "
          f"(of {len(devices)})")
    print(f"{program.describe()}; window {len(w.block_s)} blocks, {w.rounds} "
          f"rounds, {w.wall_s!r} s; compiles in window {counter.count}; "
          f"uplink bytes per client {float(np.asarray(w.last.wire_bytes_up)[-1])!r}")
    print(f"peak bytes: after set-up {setup_peak}, after window {peak}")
    print("set-up s: " + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()))
    if trace:
        ops = view.pallas_ops()
        print(f"pallas: {len(ops)} calls, {sum(o.nbytes > 0 for o in ops)} "
              f"with HBM operands, {sum(o.nbytes for o in ops)} HBM bytes")

    if trace and hasattr(program, "block_hlo"):
        view.attach_hlo(program.block_hlo())
        print("block HLO: {} of {} traced operations found; ".format(
            *view.hlo_matched()) + f"self time {view.block_self_s()!r} s, "
              f"unscoped {view.unscoped_s()!r} s")
    program.close()
    del program
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.run_reference(cell.config, cell.traffic, seed_e,
                                  blocks=correct.STEPS, chips=cell.chips)
    values = correct.numbers(readings, correct.summarize(ref))
    ok = correct.judge(values, cell.limits)
    print(f"reference: {time.perf_counter() - t_ref!r} s; numbers "
          + ", ".join(f"{k} {v!r}" for k, v in values.items()))

    if trace:
        metrics = layer_metrics(
            cell, layer_context(cell, view, w.rounds, len(w.block_s), peaks))
    else:
        values_e2e = {
            "rounds_per_s": w.rounds / w.wall_s,
            "block_ms.p95": 1e3 * float(np.percentile(w.block_s, 95)),
            "peak_hbm_mib": None if peak is None else peak / 2 ** 20,
            "setup_s": setup_s,
        }
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values_e2e.items() if k in units and v is not None}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": w.rounds, "failed": w.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s()
        result["breakdown"] = view.breakdown()
    result["checks"] = {k: {"value": values[k], "limit": limit}
                        for k, limit in cell.limits.items()}
    return result, correct.report(values, cell.limits)


def enable_cache() -> None:
    """JAX's persistent compile cache at the fixed ``<checkout>/.jax_cache``,
    given to the program through ``JAX_COMPILATION_CACHE_DIR`` (which its
    ``enable_compile_cache`` takes), holding every program of the cell
    however quick to compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ[HOIST_ENV] = "1"
    hoist_constants()
    cell = spec.Cell(spec.benchmark(), args.workload)
    devices = tpu_devices(cell.chips)
    peaks = device_peaks(devices[0].device_kind)

    enable_cache()
    result, lines = run(cell, args.seed, args.seconds, bool(args.trace),
                        devices, peaks)
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
