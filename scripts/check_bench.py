"""Perf-trajectory gate: freshly emitted BENCH_*.json vs the committed ones.

Run the benches first (they rewrite the repo-root ``BENCH_*.json``
artifacts), then this script; it diffs each fresh artifact against the
version committed at git HEAD and FAILS (exit 1) on a regression:

* ``BENCH_kernels.json``: any increase in HBM passes per 3SFC objective
  evaluation (``encoder_fused_kernel_passes``, the leafwise byte
  contract — a formula on the tree's leaves, immune to CPU noise; the
  lowering tests hold the implementation to it), or the single-pass gate
  flipping false.
* ``BENCH_collectives.json``: any increase in the fused path's per-round
  collective bytes, any drop in the naive/fused wire-bytes ratio beyond
  1% (HLO byte totals are compile-deterministic; the slack only absorbs
  jax-version drift), any collective appearing inside the per-client
  encode region, or any ``pass_*`` gate flipping false.
* ``BENCH_wire.json``: any round-trip loss (decode∘encode no longer
  bit-exact, fresh-run absolute — a lossy codec is a bug regardless of
  HEAD), any growth in a method's measured wire bytes, any header-overhead
  regression >1% (relative), or any ``pass_*`` gate flipping false.
* ``BENCH_round_engine.json``: >5% drop in the engine's driver-path
  rounds/sec relative to the same run's python-loop baseline (the
  ``driver.speedup`` ratio — absolute rounds/sec swings 2x+ with load on
  the shared CI box, but the interleaved per-pair ratio cancels box speed;
  tolerance configurable with ``--tolerance`` / ``CHECK_BENCH_TOLERANCE``),
  any new host sync or dispatch per round (structural counters, exact),
  any per-round upload bytes, or any ``pass_*`` gate flipping false.

* ``BENCH_faults.json``: the zero-fault bitwise gate false (fresh-run
  absolute — a fault pipeline that perturbs healthy rounds is a bug
  regardless of HEAD), any increase in fedavg/threesfc 30%-dropout
  rounds-to-target vs HEAD, or the dropout-convergence gate flipping
  false.
* ``BENCH_transport.json``: the byte-match, socket-bitwise, residual-
  conservation, or straggle-isolation gate false (all fresh-run absolute —
  a wire that bills more than the codec bytes, diverges from the
  in-process oracle, leaks EF mass, or lets one straggler stall the round
  is a bug regardless of HEAD), any growth in the settled per-round
  uplink bytes vs HEAD (tiny or mlp scenario), or any ``pass_*`` gate
  flipping false.

* ``BENCH_observability.json``: the tracing-overhead gate false (traced
  driver throughput below 97% of untraced — telemetry that distorts what
  it measures), the complete-trace gate false (an executed round missing
  from the merged trace, a phase missing from a round, a straggler or
  eaten frame mis-attributed), or the bytes-parity gate false (trace-
  summed frame bytes != ledger-billed bytes — all fresh-run absolute), a
  drop in the traced-throughput ratio beyond the tolerance vs HEAD, or
  any ``pass_*`` gate flipping false.

* ``BENCH_recovery.json``: the bitwise-resume, rejoin-EF-conservation, or
  previous-checkpoint-survives gate false (all fresh-run absolute — a
  resume that diverges from the uninterrupted run, a rejoiner whose
  residual leaks mass, or a crash that corrupts the last recovery point is
  a bug regardless of HEAD), the rejoin 2x-convergence gate false, any
  growth in the chaos run's rounds-to-target vs HEAD, or any ``pass_*``
  gate flipping false.

* ``BENCH_static.json``: any static-analysis violation (IR contracts,
  repo lint, protocol rules — fresh-run absolute: a violation is a bug
  regardless of HEAD, and the ``pass`` flag must hold), any shrink vs
  HEAD in the rules-evaluated count or in the IR combo-matrix coverage
  (``configs_evaluated`` — the strategy × fan-out × wire matrix may only
  grow), or ruff flipping from clean to failing while available.

Artifacts present in the working tree but not at HEAD are new benches:
reported and skipped. Exit 2 on usage/setup errors (not a git checkout,
malformed JSON).

    PYTHONPATH=src python -m benchmarks.run --only kernels,round_engine
    python scripts/check_bench.py
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class GitUnavailable(Exception):
    pass


def _check_git():
    """HEAD must resolve, else every artifact would look 'new' and the gate
    would pass vacuously — that's a setup error (exit 2), not a clean run."""
    p = subprocess.run(["git", "rev-parse", "--verify", "HEAD"], cwd=REPO,
                       capture_output=True, text=True)
    if p.returncode != 0:
        raise GitUnavailable(p.stderr.strip() or "git rev-parse HEAD failed")


def _committed(name: str):
    """The artifact as committed at HEAD, or None if it's new at HEAD
    (_check_git has already ruled out a broken checkout)."""
    p = subprocess.run(["git", "cat-file", "-e", f"HEAD:{name}"], cwd=REPO,
                       capture_output=True, text=True)
    if p.returncode != 0:
        return None
    p = subprocess.run(["git", "show", f"HEAD:{name}"], cwd=REPO,
                       capture_output=True, text=True)
    if p.returncode != 0:
        raise GitUnavailable(f"git show HEAD:{name}: {p.stderr.strip()}")
    return json.loads(p.stdout)


def _get(d, path):
    for k in path.split("."):
        if not isinstance(d, dict) or k not in d:
            return None
        d = d[k]
    return d


def check_kernels(fresh, base, tol):
    probs = []
    f_passes = _get(fresh, "encoder_fused_kernel_passes")
    b_passes = _get(base, "encoder_fused_kernel_passes")
    if f_passes is not None and b_passes is not None and \
            f_passes > b_passes + 1e-9:
        probs.append(f"HBM passes per objective evaluation increased: "
                     f"{b_passes:.3f} -> {f_passes:.3f}")
    if _get(base, "encoder_fused_single_pass") and \
            not _get(fresh, "encoder_fused_single_pass"):
        probs.append("encoder_fused_single_pass gate flipped to false")
    if _get(base, "allclose") and not _get(fresh, "allclose"):
        probs.append("kernel-vs-oracle allclose flipped to false")
    return probs


def check_round_engine(fresh, base, tol):
    probs = []
    f_sp = _get(fresh, "driver.speedup")
    b_sp = _get(base, "driver.speedup")
    if f_sp is not None and b_sp is not None and f_sp < (1 - tol) * b_sp:
        probs.append(f"driver-path rounds/sec (vs same-run loop baseline) "
                     f"dropped >{tol:.0%}: {b_sp:.2f}x -> {f_sp:.2f}x")
    for field in ("driver.engine.host_syncs_per_round",
                  "driver.engine.dispatches_per_round",
                  "driver.engine.upload_guard_violations"):
        f_v, b_v = _get(fresh, field), _get(base, field)
        if f_v is not None and b_v is not None and f_v > b_v + 1e-9:
            probs.append(f"{field} increased: {b_v:.3f} -> {f_v:.3f}")
    for gate in ("pass", "pass_driver_speedup", "pass_syncs_per_eval_block",
                 "pass_no_per_round_upload"):
        if _get(base, gate) and not _get(fresh, gate):
            probs.append(f"{gate} gate flipped to false")
    return probs


def check_collectives(fresh, base, tol):
    probs = []
    f_b = _get(fresh, "fused.collective_bytes_per_round")
    b_b = _get(base, "fused.collective_bytes_per_round")
    if f_b is not None and b_b is not None and f_b > 1.01 * b_b:
        probs.append(f"fused-decode per-round collective bytes increased: "
                     f"{b_b:.0f} -> {f_b:.0f}")
    f_r, b_r = _get(fresh, "wire_ratio"), _get(base, "wire_ratio")
    if f_r is not None and b_r is not None and f_r < 0.99 * b_r:
        probs.append(f"naive/fused wire-bytes ratio dropped: "
                     f"{b_r:.0f}x -> {f_r:.0f}x")
    for path in ("naive.encode_region_collectives",
                 "fused.encode_region_collectives"):
        v = _get(fresh, path)
        if v:
            probs.append(f"{path}: {v} collective(s) inside the per-client "
                         f"encode region (must be 0)")
    for gate in ("pass", "pass_wire_ratio", "pass_payload_scaling",
                 "pass_encode_region_clean", "pass_bitexact",
                 "pass_threesfc_tol"):
        if _get(base, gate) and not _get(fresh, gate):
            probs.append(f"{gate} gate flipped to false")
    return probs


def check_wire(fresh, base, tol):
    probs = []
    # round-trip loss fails absolutely: a codec that stopped being
    # bit-exact is broken even if HEAD's artifact predates the gate
    for flag in ("pass_roundtrip", "pass_recon_consistency"):
        if _get(fresh, flag) is False:
            probs.append(f"{flag} is false: decode∘encode round-trip loss")
    f_m, b_m = _get(fresh, "measure.methods"), _get(base, "measure.methods")
    if isinstance(f_m, dict) and isinstance(b_m, dict):
        for k in sorted(set(f_m) & set(b_m)):
            f_b, b_b = _get(f_m[k], "measured_bytes"), _get(b_m[k], "measured_bytes")
            if f_b is not None and b_b is not None and f_b > b_b:
                probs.append(f"{k}: measured wire bytes grew {b_b} -> {f_b}")
            f_h = _get(f_m[k], "header_overhead")
            b_h = _get(b_m[k], "header_overhead")
            if f_h is not None and b_h is not None and f_h > 1.01 * b_h:
                probs.append(f"{k}: header overhead regressed >1%: "
                             f"{b_h:.4f} -> {f_h:.4f}")
    # (pass_roundtrip/pass_recon_consistency are absolute above — not
    # repeated here, so one failure reports once)
    for gate in ("pass", "pass_signsgd_bytes", "pass_threesfc_bytes",
                 "pass_round_parity", "pass_channel_accounting"):
        if _get(base, gate) and not _get(fresh, gate):
            probs.append(f"{gate} gate flipped to false")
    return probs


def check_faults(fresh, base, tol):
    probs = []
    # absolute: the zero-fault bitwise identity is a correctness property
    # of the round pipeline, not a trajectory — losing it is a bug even in
    # the commit that introduces the bench
    if _get(fresh, "pass_zero_fault_bitwise") is False:
        bw = _get(fresh, "zero_fault_bitwise") or {}
        bad = sorted(k for k, v in bw.items() if not v)
        probs.append("pass_zero_fault_bitwise is false: null fault schedule "
                     f"no longer bitwise the unfaulted round ({bad})")
    # vs HEAD: 30%-dropout rounds-to-target must not regress per method
    for m in ("fedavg", "threesfc"):
        f_r = _get(fresh, f"grid.{m}.drop30_k0.rounds_to_target")
        b_r = _get(base, f"grid.{m}.drop30_k0.rounds_to_target")
        if b_r is not None and f_r is None:
            probs.append(f"{m}: no longer reaches target under 30% dropout "
                         f"(was {b_r} rounds)")
        elif f_r is not None and b_r is not None and f_r > b_r:
            probs.append(f"{m}: 30%-dropout rounds-to-target regressed "
                         f"{b_r} -> {f_r}")
    for gate in ("pass", "pass_dropout_convergence"):
        if _get(base, gate) and not _get(fresh, gate):
            probs.append(f"{gate} gate flipped to false")
    return probs


def check_transport(fresh, base, tol):
    probs = []
    # absolute: these are correctness properties of the socket transport
    # (exact billing, oracle parity, EF conservation, deadline isolation),
    # not trajectories — they fail even in the commit introducing the bench
    for flag, why in (
            ("pass_bytes_match", "wire bills more than N*nbytes (or "
             "diverges from BENCH_wire's measured bytes)"),
            ("pass_socket_bitwise", "live socket round no longer bitwise "
             "equal to the width-matched oracle on the same fault pattern, "
             "or off the vmapped engine by more than two roundings"),
            ("pass_residual_conservation", "EF residual mass not conserved "
             "on a dropped frame"),
            ("pass_straggle_isolation", "a straggler's sleep leaked into "
             "the round wall clock (deadline no longer isolates)")):
        if _get(fresh, flag) is False:
            probs.append(f"{flag} is false: {why}")
    # vs HEAD: settled-round uplink bytes must not grow
    for field in ("faulted.settled_null_round_bytes",
                  "bytes_mlp.per_message_bytes",
                  "bytes_mlp.n8_round_bytes"):
        f_v, b_v = _get(fresh, field), _get(base, field)
        if f_v is not None and b_v is not None and f_v > b_v:
            probs.append(f"{field} grew: {b_v} -> {f_v}")
    if _get(base, "pass") and not _get(fresh, "pass"):
        probs.append("pass gate flipped to false")
    return probs


def check_recovery(fresh, base, tol):
    probs = []
    # absolute: recovery correctness properties — bitwise resume, EF mass
    # conservation across a worker outage, and durability of the previous
    # recovery point — fail even in the commit introducing the bench
    for flag, why in (
            ("pass_bitwise_resume", "a SIGKILLed-and-resumed run no longer "
             "replays bitwise equal to the uninterrupted oracle"),
            ("pass_rejoin_ef_conserved", "a rejoining worker's EF residual "
             "is not bitwise the banked commit (mass leaked across the "
             "outage)"),
            ("pass_rejoin_convergence", "the crash+rejoin run needs more "
             "than 2x the no-crash rounds to the target loss"),
            ("pass_prev_ckpt_survives", "a crash during a checkpoint write "
             "corrupted the previously committed recovery point")):
        if _get(fresh, flag) is False:
            probs.append(f"{flag} is false: {why}")
    # vs HEAD: the chaos run's rounds-to-target must not regress
    f_r = _get(fresh, "worker_rejoin.rounds_to_target.chaos")
    b_r = _get(base, "worker_rejoin.rounds_to_target.chaos")
    if b_r is not None and f_r is None:
        probs.append(f"chaos run no longer reaches the target loss "
                     f"(was {b_r} rounds)")
    elif f_r is not None and b_r is not None and f_r > b_r:
        probs.append(f"chaos rounds-to-target regressed {b_r} -> {f_r}")
    if _get(base, "pass") and not _get(fresh, "pass"):
        probs.append("pass gate flipped to false")
    return probs


def check_observability(fresh, base, tol):
    probs = []
    # absolute: telemetry correctness properties — cheap-when-on, complete,
    # and byte-exact — fail even in the commit introducing the bench
    for flag, why in (
            ("pass_overhead", "tracing-on driver throughput fell below 97% "
             "of tracing-off (instrumentation distorts the hot path)"),
            ("pass_complete_trace", "merged trace missing rounds/phases or "
             "mis-attributing the straggler / eaten frame"),
            ("pass_bytes_parity", "trace-summed frame bytes != ledger-billed "
             "bytes (the trace is no longer a complete record of the wire)")):
        if _get(fresh, flag) is False:
            probs.append(f"{flag} is false: {why}")
    # vs HEAD: the traced/untraced throughput ratio must not sag
    f_r = _get(fresh, "overhead.traced_throughput_ratio")
    b_r = _get(base, "overhead.traced_throughput_ratio")
    if f_r is not None and b_r is not None and f_r < (1 - tol) * b_r:
        probs.append(f"traced-throughput ratio dropped >{tol:.0%}: "
                     f"{b_r:.3f} -> {f_r:.3f}")
    if _get(base, "pass") and not _get(fresh, "pass"):
        probs.append("pass gate flipped to false")
    return probs


def check_static(fresh, base, tol):
    probs = []
    # absolute: a static-analysis violation is a bug in the commit that
    # produced it, HEAD or not
    v = _get(fresh, "violations")
    if v:
        probs.append(f"{v} static-analysis violation(s) (must be 0)")
        for layer in ("ir", "lint", "protocol"):
            rules = _get(fresh, f"{layer}.contracts") \
                or _get(fresh, f"{layer}.rules") or {}
            for rname, r in sorted(rules.items()):
                for msg in r.get("violations", []):
                    probs.append(f"  [{layer}/{rname}] {msg}")
    if _get(fresh, "pass") is False and not v:
        probs.append("pass flag is false")
    # vs HEAD: coverage may only grow — fewer rule evaluations or a
    # smaller IR combo matrix means an invariant silently stopped being
    # checked
    for field, what in (("rules_evaluated", "rule evaluations"),
                        ("configs_evaluated", "IR matrix configs")):
        f_v, b_v = _get(fresh, field), _get(base, field)
        if f_v is not None and b_v is not None and f_v < b_v:
            probs.append(f"static-analysis coverage shrank: {what} "
                         f"{b_v} -> {f_v}")
    if _get(base, "ruff.available") and _get(base, "ruff.exit") == 0 \
            and _get(fresh, "ruff.available") \
            and _get(fresh, "ruff.exit") != 0:
        probs.append("ruff flipped from clean to failing")
    return probs


CHECKS = {
    "BENCH_kernels.json": check_kernels,
    "BENCH_round_engine.json": check_round_engine,
    "BENCH_collectives.json": check_collectives,
    "BENCH_wire.json": check_wire,
    "BENCH_faults.json": check_faults,
    "BENCH_transport.json": check_transport,
    "BENCH_recovery.json": check_recovery,
    "BENCH_observability.json": check_observability,
    "BENCH_static.json": check_static,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance", type=float,
                    default=float(os.environ.get("CHECK_BENCH_TOLERANCE",
                                                 "0.05")),
                    help="fractional rounds/sec drop allowed (default 0.05)")
    args = ap.parse_args(argv)

    artifacts = sorted(glob.glob(os.path.join(REPO, "BENCH_*.json")))
    if not artifacts:
        print("check_bench: no BENCH_*.json artifacts found", file=sys.stderr)
        return 2
    try:
        _check_git()
    except GitUnavailable as e:
        print(f"check_bench: not a usable git checkout ({e})", file=sys.stderr)
        return 2
    failures = 0
    for path in artifacts:
        name = os.path.basename(path)
        try:
            with open(path) as f:
                fresh = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"check_bench: cannot read {name}: {e}", file=sys.stderr)
            return 2
        try:
            base = _committed(name)
        except (GitUnavailable, json.JSONDecodeError) as e:
            print(f"check_bench: cannot read committed {name}: {e}",
                  file=sys.stderr)
            return 2
        checker = CHECKS.get(name)
        if checker is None:
            print(f"  {name}: no regression rules registered — skipped")
            continue
        # new-at-HEAD artifacts still get the checker's *absolute* rules
        # (every base-relative probe is None-guarded); otherwise a lossy
        # codec could land in the very commit that introduces its bench
        probs = checker(fresh, base, args.tolerance)
        label = "new artifact (absolute checks only)" if base is None else "ok"
        if probs:
            failures += len(probs)
            print(f"  {name}: REGRESSION")
            for p in probs:
                print(f"    - {p}")
        else:
            print(f"  {name}: {label}")
    if failures:
        print(f"check_bench: {failures} regression(s) vs HEAD", file=sys.stderr)
        return 1
    print("check_bench: no regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
