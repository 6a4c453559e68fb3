"""Observability gates: trace completeness and byte parity.

PR 9 added ``repro.obs`` — host-side spans around every hot boundary the
driver crosses (engine dispatch/sync, live round phases, transport frames,
codec bytes, checkpoint fsync) plus cross-process span piggybacking and an
offset-corrected merge. Telemetry that distorts what it measures, or that
silently loses rounds, is worse than none, so two gates:

* **complete trace**: a live socket run (3 workers, real subprocesses) with
  one worker straggling past the deadline every round and the wire eating
  one frame (``rx_filter``) yields a merged trace in which EVERY executed
  round carries the full server phase set (encode/broadcast/collect/ack/
  aggregate), and the outcome tags match what actually happened: the
  straggler undelivered-not-dead each measured round and attributed as a
  straggler (its own worker-side straggle spans overrun the server
  deadline), the eaten frame attributed as ``frame_lost`` — both read back
  through ``scripts/trace_report.py --json``, not from bench-internal
  state.
* **bytes parity**: data-frame bytes summed from trace events equal the
  transport ledger's billed bytes EXACTLY, both directions — every
  ``LinkStats`` bill emits exactly one rx_frame/tx_frame event, so the
  trace is a complete record of the wire, not a sample of it.

Speed, the tracer's cost included, is the chip benchmark's
(``BENCHMARK.json``), not a gate here. ``--quick`` == ``--full``. Writes
``experiments/results/observability.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- live trace scenario ----------------------------------------------------
LIVE_N = 3
LIVE_ROUNDS = 5                                      # measured, after warm 0
STRAGGLE_CID, STRAGGLE_S = 1, 2.0
DEADLINE_S = 0.75
DROP = (2, 0)                                        # (round, cid) eaten frame
WARM_DEADLINE_S = 600.0                              # round-0 jit in workers
SPAN_DRAIN_TIMEOUT_S = 90.0                          # straggler backlog drain


def _live_trace_scenario(out_dir: str) -> Dict:
    """Live socket run with a straggler + an eaten frame, tracing on end to
    end; returns the trace_report --json analysis plus raw parity numbers."""
    from benchmarks.bench_transport import _build, _tiny_problem
    from repro.comm.transport import SocketServer, spawn_local_workers
    from repro.configs.run import RunConfig
    from repro.fl.engine import LiveRoundLoop, RetryPolicy
    from repro.launch.worker import vision_setup
    from repro.obs import (configure_tracer, get_tracer, merge_traces,
                           set_tracer, write_chrome_trace)

    spec, fl = _tiny_problem()
    run = RunConfig(fl=fl, wire="codec", transport="socket",
                    round_deadline_s=DEADLINE_S, recv_timeout_s=DEADLINE_S,
                    recv_backoff=1.5, transport_retries=0,
                    heartbeat_s=0.2, liveness_timeout_s=5.0)
    _, params, strategy, codec = _build("mlp", spec, fl, run)

    def rx_filter(cid, rnd, buf):
        return None if (rnd, cid) == DROP else buf

    prev = get_tracer()
    configure_tracer(True, proc="server", capacity=1 << 17)
    server = SocketServer(LIVE_N, heartbeat_s=run.heartbeat_s,
                          liveness_timeout_s=run.liveness_timeout_s,
                          rx_filter=rx_filter)
    procs = spawn_local_workers(server.address, range(LIVE_N))
    try:
        server.wait_ready(60)
        server.send_setup(vision_setup(run, model="mlp", spec=spec,
                                       train_size=120,
                                       straggle={STRAGGLE_CID: STRAGGLE_S},
                                       trace=True))
        loop = LiveRoundLoop(server, strategy, codec, run, params)
        warm = RetryPolicy(max_retries=0, recv_timeout_s=WARM_DEADLINE_S,
                           max_timeout_s=WARM_DEADLINE_S)
        loop.run(1, deadline_s=WARM_DEADLINE_S, policy=warm)
        loop.run(LIVE_ROUNDS)

        # the straggler is still chewing through its round backlog; its
        # spans ride the (late) MSG_METRICs, so wait until its final-round
        # spans have landed before draining
        worker_spans: Dict[str, list] = {}
        last = LIVE_ROUNDS                            # absolute round index
        key = f"client-{STRAGGLE_CID}"
        deadline = time.monotonic() + SPAN_DRAIN_TIMEOUT_S
        while time.monotonic() < deadline:
            for k, v in server.pop_worker_spans().items():
                worker_spans.setdefault(k, []).extend(v)
            if any(s.get("round") == last and s.get("name") == "worker.compute"
                   for s in worker_spans.get(key, ())):
                break
            time.sleep(0.25)
        time.sleep(0.5)                               # trailing straggle span
        for k, v in server.pop_worker_spans().items():
            worker_spans.setdefault(k, []).extend(v)
        offsets = server.clock_offsets()
        ledger = server.ledger()
        history = list(loop.history)
    finally:
        server.stop()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()
        tracer = get_tracer()
        set_tracer(prev)

    merged = merge_traces(tracer.drain(), worker_spans, offsets)
    trace_path = os.path.join(out_dir, "trace.jsonl")
    with open(trace_path, "w") as f:
        for rec in merged:
            f.write(json.dumps(rec) + "\n")
    write_chrome_trace(merged, os.path.join(out_dir, "trace.chrome.json"))
    ledger_path = os.path.join(out_dir, "ledger.json")
    with open(ledger_path, "w") as f:
        json.dump(ledger, f)

    # the gates read the trace the way a user would: through the analyzer
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         trace_path, "--ledger", ledger_path, "--json",
         "--replay", os.path.join(out_dir, "replay.json")],
        capture_output=True, text=True, timeout=120)
    if proc.returncode != 0:
        raise RuntimeError(f"trace_report failed:\n{proc.stderr}")
    report = json.loads(proc.stdout)

    return {
        "config": {"clients": LIVE_N, "rounds": 1 + LIVE_ROUNDS,
                   "straggle_cid": STRAGGLE_CID, "straggle_s": STRAGGLE_S,
                   "deadline_s": DEADLINE_S, "drop": list(DROP)},
        "history": [{"round": r["round"],
                     "delivered": np.asarray(r["delivered"]).tolist(),
                     "dead": r["dead"], "wall_s": float(r["wall_s"])}
                    for r in history],
        "worker_span_counts": {k: len(v) for k, v in worker_spans.items()},
        "clock_offsets_ns": offsets,
        "ledger": {"uplink_bytes": int(ledger["uplink"]["total_bytes"]),
                   "downlink_bytes": int(ledger["downlink"]["total_bytes"]),
                   "overhead_up": int(ledger["overhead_up"]),
                   "overhead_down": int(ledger["overhead_down"])},
        "report": report,
    }


def _gate(results: Dict) -> Dict:
    live = results["live"]
    rep = live["report"]

    executed = [r["round"] for r in live["history"]]
    rounds_ok = sorted(rep["rounds"]) == sorted(executed)
    att = rep["attribution"]
    straggler_rounds = att["stragglers"].get(str(STRAGGLE_CID), [])
    # every measured round (the warm round has no deadline pressure)
    straggle_ok = set(straggler_rounds) >= set(executed[1:])
    # ... and nobody else blamed
    straggle_ok &= set(att["stragglers"]) <= {str(STRAGGLE_CID)}
    straggle_ok &= not att["dead_workers"]            # alive the whole run
    drop_ok = att["frame_lost"].get(str(DROP[1]), []) == [DROP[0]]
    unknown = [c for c in att["undelivered"] if c["cause"] == "unknown"]
    results["pass_complete_trace"] = bool(
        rounds_ok and rep["phase_complete"] and straggle_ok and drop_ok
        and not unknown)

    rec = rep["reconciliation"]
    results["pass_bytes_parity"] = bool(
        rec["uplink_exact"] and rec["downlink_exact"]
        and rec["uplink_billed"] > 0 and rec["downlink_billed"] > 0)

    results["pass"] = all(results[k] for k in (
        "pass_complete_trace", "pass_bytes_parity"))
    return results


def run(quick: bool = True, out_dir: str = "experiments/results") -> Dict:
    del quick                                 # deterministic; quick == full
    print(f"live trace: {LIVE_N} workers, cid {STRAGGLE_CID} sleeps "
          f"{STRAGGLE_S:.1f}s/round under a {DEADLINE_S:.2f}s deadline, wire "
          f"eats frame {DROP}...")
    os.makedirs(out_dir, exist_ok=True)
    with tempfile.TemporaryDirectory(prefix="obs_bench_") as tmp:
        live = _live_trace_scenario(tmp)

    results = _gate({"live": live})

    rep = live["report"]
    print("\n== Observability ==")
    att = rep["attribution"]
    print(f"  [{'PASS' if results['pass_complete_trace'] else 'FAIL'}] "
          f"merged trace complete + correctly attributed: rounds "
          f"{rep['rounds']}, phases complete={rep['phase_complete']}, "
          f"stragglers={att['stragglers']}, frame_lost={att['frame_lost']}")
    rec = rep["reconciliation"]
    print(f"  [{'PASS' if results['pass_bytes_parity'] else 'FAIL'}] trace "
          f"bytes == ledger bytes exactly: up {rec['uplink_trace']}/"
          f"{rec['uplink_billed']}, down {rec['downlink_trace']}/"
          f"{rec['downlink_billed']}")

    with open(os.path.join(out_dir, "observability.json"), "w") as f:
        json.dump(results, f, indent=2)
    return results


def main():
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--quick", dest="quick", action="store_true", default=True,
                   help="accepted for orchestrator symmetry; quick == full")
    g.add_argument("--full", dest="quick", action="store_false")
    args = ap.parse_args()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
