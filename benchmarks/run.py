"""CPU experiment orchestrator: the paper's tables and figures, and the
correctness drivers (collectives, wire, faults, transport, recovery,
observability). Each writes ``experiments/results/<name>.json``; speed is
the chip benchmark's (``BENCHMARK.json``, ``bench/``).

    PYTHONPATH=src python -m benchmarks.run            # quick (CPU-friendly)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale rounds
    PYTHONPATH=src python -m benchmarks.run --only table2,fig7

Exits 1, naming the driver, when a driver's result has a false ``"pass"``.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_collectives, bench_faults, bench_fedsynth,
                        bench_fig1, bench_fig7, bench_observability,
                        bench_recovery, bench_ssweep, bench_table2,
                        bench_table3, bench_table4, bench_transport,
                        bench_wire)

BENCHES = {
    "fig1": bench_fig1.run,          # convergence vs rate
    "table2": bench_table2.run,      # 5-method accuracy x ratio grid
    "table3": bench_table3.run,      # 3SFC budget scaling vs STC
    "table4": bench_table4.run,      # EF / B / K ablation
    "fig7": bench_fig7.run,          # compression efficiency curves
    "fedsynth": bench_fedsynth.run,  # table1 + fig2/3 collapse
    "ssweep": bench_ssweep.run,      # encoder-iteration knob (Algorithm 1 S)
    "collectives": bench_collectives.run,    # sharded fan-out wire bytes
    "wire": bench_wire.run,                  # serialized codec bytes + parity
    "faults": bench_faults.run,              # dropout/staleness degradation
    "transport": bench_transport.run,        # live socket rounds vs oracle
    "recovery": bench_recovery.run,          # chaos-kill: resume + rejoin
    "observability": bench_observability.run,  # trace completeness + bytes
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--strategies", action="store_true",
                    help="list the registered compression strategies (and "
                         "which have a wire codec) instead of running")
    args = ap.parse_args(argv)
    if args.strategies:
        from repro.comm.codec import CODECS
        from repro.core.strategy import STRATEGIES, strategy_kinds
        for kind in strategy_kinds():
            cls = STRATEGIES[kind]
            tags = [t for t, on in (
                ("fused-aggregate", cls.supports_fused_aggregate),
                ("wire-codec", kind in CODECS)) if on]
            print(f"{kind:12s} {cls.__module__}.{cls.__name__}"
                  + (f"  [{', '.join(tags)}]" if tags else ""))
        return
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        print(f"unknown bench name(s): {', '.join(unknown)}", file=sys.stderr)
        print(f"valid names: {', '.join(BENCHES)}", file=sys.stderr)
        sys.exit(2)
    t0 = time.time()
    failed = []
    for name in names:
        print(f"\n######## {name} " + "#" * (70 - len(name)))
        if not BENCHES[name](quick=not args.full).get("pass", True):
            failed.append(name)
    print(f"\nall benchmarks done in {time.time()-t0:.0f}s")
    if failed:
        print(f"failed gate(s): {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
