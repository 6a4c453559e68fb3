"""Chaos-kill gate: durable recovery points, bitwise resume, elastic rejoin.

Four scenarios, four absolute gates (emitted as ``pass_*`` flags; a false
one fails ``benchmarks.run``):

* **server SIGKILL + resume** (``pass_bitwise_resume``, socket half): a
  ``repro.launch.train`` socket run (mlp/mnist/stc, 2 workers,
  ``--ckpt-every 2``) is SIGKILLed — whole process group, server and
  workers — as soon as its first recovery point commits, then restarted
  with ``--resume``. Its FINAL recovery point must be bitwise identical to
  an uninterrupted oracle run's: every payload leaf (params + per-client
  EF bank), the per-round delivered/participate masks in the history, and
  the byte ledger. The resumed run must also have appended (not truncated)
  the metrics JSONL.
* **in-process resume** (``pass_bitwise_resume``, engine half): the
  faulted scanned engine (drops + stragglers + staleness buffer) resumed
  from a mid-run recovery point replays the remaining rounds bitwise
  against the uninterrupted ``FLState`` — params, N×d EF, ring buffer,
  round counter.
* **worker SIGKILL + rejoin** (``pass_rejoin_ef_conserved``,
  ``pass_rejoin_convergence``): a live worker is SIGKILLed mid-run; the
  loop drives on (its rounds map to delivered=False, its banked residual
  frozen); a restarted process rejoins and must come back with its EF
  bitwise equal to the banked commit (atol=0 — residual-mass conservation
  across the outage), after which the run must reach the no-crash run's
  final loss within 2x the no-crash round count.
* **crash during checkpoint write** (``pass_prev_ckpt_survives``): a kill
  at any point of a save — mid-payload, before the manifest, before the
  index rename — leaves the PREVIOUS recovery point committed and
  loadable, and a retried save over the debris succeeds.

Deterministic except wall clock; ``--quick`` == ``--full``. Writes
``experiments/results/recovery.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- server-kill scenario (train.py subprocesses) ---------------------------
SRV_ROUNDS = 6
SRV_CKPT_EVERY = 2
SRV_KILL_AFTER_STEP = 2              # SIGKILL once this step has committed
SRV_BOOT_TIMEOUT_S = 900             # worker jit compile inside the run

# -- worker-kill scenario (live loop in this process) -----------------------
WK_N = 3
WK_KILL = 2
WK_CLEAN_ROUNDS = 6                  # measured (after warm-up)
WK_PRE_KILL = 2                      # healthy rounds before the SIGKILL
WK_DEAD_ROUNDS = 2                   # rounds driven while the worker is dead
WK_WARM_S = 600.0


def _ravel(tree) -> np.ndarray:
    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


# ---------------------------------------------------------------------------
# scenario: in-process bitwise resume
# ---------------------------------------------------------------------------


def _inproc_resume(out_dir: str) -> Dict:
    """Faulted scanned engine, checkpoint at round 4, resume in a fresh
    engine, compare the full FLState to the uninterrupted oracle."""
    from repro.checkpoint import (CheckpointManager, load_fl_checkpoint,
                                  save_fl_checkpoint)
    from repro.configs.base import CompressorConfig, FLConfig
    from repro.configs.run import RunConfig
    from repro.core.strategy import make_strategy
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.engine import RoundEngine, device_pools, vision_batcher
    from repro.fl.round import build_fl_round
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import VisionSpec, make_paper_model

    N, R, CUT = 4, 8, 4
    spec = VisionSpec("tiny", (6, 6, 1), 3)
    comp = CompressorConfig(kind="stc", keep_ratio=0.1)
    fl = FLConfig(num_clients=N, local_steps=2, local_lr=0.05,
                  local_batch=4, compressor=comp, seed=0)
    run = RunConfig(fl=fl, drop_rate=0.3, straggler_rate=0.25,
                    staleness_max=2, fault_seed=7)
    model = make_paper_model("mlp", spec)
    params = model.init(jax.random.PRNGKey(fl.seed))
    strategy = make_strategy(comp, loss_fn=model.syn_loss,
                             syn_spec=vision_syn_spec(spec, comp),
                             local_lr=fl.local_lr)
    train = make_class_image_dataset(jax.random.PRNGKey(fl.seed), 120,
                                     spec.input_shape, spec.num_classes)
    parts = dirichlet_partition(train.y, N, alpha=fl.dirichlet_alpha,
                                seed=fl.seed, min_per_client=fl.local_batch)
    pools = device_pools(parts)

    def make_engine():
        return RoundEngine(
            build_fl_round(model.loss, strategy, run),
            vision_batcher(train.x, train.y, pools, fl.local_steps,
                           fl.local_batch),
            seed=fl.seed)

    oracle = make_engine()
    st = oracle.init_state(params, N, strategy, staleness_max=run.staleness_max)
    oracle_final, _ = oracle.run(st, R)

    mgr = CheckpointManager(os.path.join(out_dir, "inproc_ckpt"))
    eng = make_engine()
    st = eng.init_state(params, N, strategy, staleness_max=run.staleness_max)
    eng.run(st, CUT + 1, eval_every=3, ckpt_every=SRV_CKPT_EVERY,
            ckpt_fn=lambda s, r: save_fl_checkpoint(mgr, r, s, run=run))

    resumed = make_engine()
    template = resumed.init_state(params, N, strategy,
                                  staleness_max=run.staleness_max)
    state, _, meta = load_fl_checkpoint(mgr, template, step=CUT)
    resumed_final, _ = resumed.run(state, R - CUT)

    fields = {}
    for name in ("params", "ef", "buf", "buf_w"):
        a, b = getattr(oracle_final, name), getattr(resumed_final, name)
        fields[name] = bool(np.array_equal(_ravel(a), _ravel(b)))
    fields["round"] = int(oracle_final.round) == int(resumed_final.round) == R
    return {"rounds": R, "cut_round": CUT, "resumed_from": int(meta["round"]),
            "bitwise": fields, "bitwise_all": all(fields.values())}


# ---------------------------------------------------------------------------
# scenario: server SIGKILL mid-run + --resume (train.py subprocesses)
# ---------------------------------------------------------------------------


def _train_cmd(out: str, resume: Optional[str] = None) -> List[str]:
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--model", "mlp", "--dataset", "mnist", "--compressor", "stc",
           "--rounds", str(SRV_ROUNDS), "--clients", "2",
           "--local-steps", "1", "--batch", "8", "--train-size", "128",
           "--eval-every", "10", "--seed", "0",
           "--wire", "codec", "--transport", "socket",
           "--ckpt-every", str(SRV_CKPT_EVERY), "--out", out]
    if resume:
        cmd += ["--resume", resume]
    return cmd


def _spawn_train(out: str, resume: Optional[str] = None) -> subprocess.Popen:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    log = open(os.path.join(out, "driver.log"), "w")
    # its own session => one killpg takes out the server AND its workers,
    # exactly like a box losing power
    return subprocess.Popen(_train_cmd(out, resume), cwd=REPO, env=env,
                            stdout=log, stderr=subprocess.STDOUT,
                            start_new_session=True)


def _wait_step(ckpt_root: str, step: int, proc: subprocess.Popen,
               timeout: float) -> None:
    from repro.checkpoint import CheckpointManager

    mgr = CheckpointManager(ckpt_root)
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if proc.poll() is not None:
            raise RuntimeError(
                f"train run exited (code {proc.returncode}) before step "
                f"{step} committed — see driver.log")
        latest = mgr.latest()
        if latest is not None and latest >= step:
            return
        time.sleep(0.2)
    raise RuntimeError(f"step {step} never committed within {timeout}s")


def _final_ckpt(out: str):
    from repro.checkpoint import CheckpointManager, load_arrays, load_manifest

    mgr = CheckpointManager(os.path.join(out, "ckpt"))
    step = mgr.latest()
    flat, manifest = load_arrays(mgr.path(step))
    return step, flat, manifest["meta"]


def _data_plane(ledger) -> Optional[Dict]:
    if ledger is None:
        return None
    return {k: v for k, v in ledger.items()
            if k not in ("overhead_up", "overhead_down")}


def _server_kill_resume(out_dir: str) -> Dict:
    """Oracle run start-to-finish; chaos run SIGKILLed (whole group) after
    its first recovery point, restarted with --resume; final recovery
    points compared leaf-by-leaf."""
    oracle_out = os.path.join(out_dir, "server_oracle")
    chaos_out = os.path.join(out_dir, "server_chaos")
    for d in (oracle_out, chaos_out):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)

    print("  oracle run (uninterrupted)...")
    p = _spawn_train(oracle_out)
    rc = p.wait(timeout=SRV_BOOT_TIMEOUT_S)
    if rc != 0:
        raise RuntimeError(f"oracle train run failed (exit {rc}) — see "
                           f"{oracle_out}/driver.log")

    print(f"  chaos run: SIGKILL the process group once step "
          f"{SRV_KILL_AFTER_STEP} commits...")
    p = _spawn_train(chaos_out)
    _wait_step(os.path.join(chaos_out, "ckpt"), SRV_KILL_AFTER_STEP, p,
               SRV_BOOT_TIMEOUT_S)
    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
    p.wait(timeout=30)
    metrics_path = os.path.join(chaos_out, "metrics.jsonl")
    pre_lines = sum(1 for _ in open(metrics_path)) \
        if os.path.exists(metrics_path) else 0

    print("  resume run (--resume from the surviving recovery point)...")
    p = _spawn_train(chaos_out, resume=os.path.join(chaos_out, "ckpt"))
    rc = p.wait(timeout=SRV_BOOT_TIMEOUT_S)
    if rc != 0:
        raise RuntimeError(f"resumed train run failed (exit {rc}) — see "
                           f"{chaos_out}/driver.log")

    o_step, o_flat, o_meta = _final_ckpt(oracle_out)
    c_step, c_flat, c_meta = _final_ckpt(chaos_out)
    keys = sorted(set(o_flat) | set(c_flat))
    leaf_diffs = [k for k in keys
                  if k not in o_flat or k not in c_flat
                  or not np.array_equal(o_flat[k], c_flat[k])]
    o_hist = [(r["round"], r["participate"], r["delivered"])
              for r in o_meta.get("history", [])]
    c_hist = [(r["round"], r["participate"], r["delivered"])
              for r in c_meta.get("history", [])]
    post_lines = sum(1 for _ in open(metrics_path)) \
        if os.path.exists(metrics_path) else 0
    detail = {
        "rounds": SRV_ROUNDS,
        "kill_after_step": SRV_KILL_AFTER_STEP,
        "final_step": {"oracle": o_step, "resumed": c_step},
        "payload_leaves": len(keys),
        "leaf_diffs": leaf_diffs,
        "params_and_bank_bitwise": not leaf_diffs and o_step == c_step,
        "masks_match": o_hist == c_hist,
        # overhead_up/down count heartbeat/control traffic, whose volume is
        # wall-clock-dependent — only data-plane bytes are deterministic
        "ledger_match": _data_plane(o_meta.get("ledger"))
        == _data_plane(c_meta.get("ledger")),
        "ef_bank_rounds_match": (o_meta.get("ef_bank_rounds")
                                 == c_meta.get("ef_bank_rounds")),
        "metrics_appended": post_lines > pre_lines >= 0,
    }
    detail["bitwise_all"] = bool(
        detail["params_and_bank_bitwise"] and detail["masks_match"]
        and detail["ledger_match"] and detail["ef_bank_rounds_match"])
    return detail


# ---------------------------------------------------------------------------
# scenario: worker SIGKILL + rejoin (live loop in this process)
# ---------------------------------------------------------------------------


def _wk_problem():
    from repro.configs.base import CompressorConfig, FLConfig
    from repro.configs.run import RunConfig
    from repro.core.strategy import make_strategy
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import VisionSpec, make_paper_model

    spec = VisionSpec("tiny", (6, 6, 1), 3)
    comp = CompressorConfig(kind="stc", keep_ratio=0.1)
    fl = FLConfig(num_clients=WK_N, local_steps=2, local_lr=0.05,
                  local_batch=4, compressor=comp, seed=0)
    run = RunConfig(fl=fl, wire="codec", transport="socket",
                    round_deadline_s=60.0, recv_timeout_s=30.0,
                    transport_retries=0, heartbeat_s=0.2,
                    liveness_timeout_s=5.0)
    model = make_paper_model("mlp", spec)
    params = model.init(jax.random.PRNGKey(fl.seed))
    strategy = make_strategy(comp, loss_fn=model.syn_loss,
                             syn_spec=vision_syn_spec(spec, comp),
                             local_lr=fl.local_lr)
    codec = strategy.wire_codec(params, policy=run.wire_policy)
    return spec, run, params, strategy, codec


def _mean_losses(history) -> List[float]:
    """Per measured round (warm-up excluded): mean reported local loss over
    the workers that got one through."""
    out = []
    for rec in history[1:]:
        vals = list(rec["losses"].values())
        out.append(float(np.mean(vals)) if vals else float("inf"))
    return out


def _rounds_to(losses: List[float], target: float) -> Optional[int]:
    for i, v in enumerate(losses):
        if v <= target:
            return i + 1
    return None


def _worker_kill_rejoin(quick: bool) -> Dict:
    from repro.comm.transport import SocketServer, spawn_local_workers
    from repro.fl.engine import LiveRoundLoop, RetryPolicy
    from repro.launch.worker import vision_setup

    spec, run, params, strategy, codec = _wk_problem()
    warm = RetryPolicy(max_retries=0, recv_timeout_s=WK_WARM_S,
                       max_timeout_s=WK_WARM_S)

    def session(drive):
        server = SocketServer(WK_N, heartbeat_s=run.heartbeat_s,
                              liveness_timeout_s=run.liveness_timeout_s)
        procs = spawn_local_workers(server.address, range(WK_N))
        extra = []
        try:
            server.wait_ready(120)
            server.send_setup(vision_setup(run, model="mlp", spec=spec,
                                           train_size=96))
            loop = LiveRoundLoop(server, strategy, codec, run, params)
            loop.run(1, deadline_s=WK_WARM_S, policy=warm)   # jit warm-up
            out = drive(server, loop, procs, extra)
        finally:
            server.stop()
            for p in list(procs) + extra:
                try:
                    p.wait(timeout=15)
                except Exception:
                    p.kill()
        return out, loop.history

    print("  no-crash reference run...")

    def drive_clean(server, loop, procs, extra):
        loop.run(WK_CLEAN_ROUNDS)
        return {}

    _, clean_hist = session(drive_clean)
    clean_losses = _mean_losses(clean_hist)
    target = clean_losses[-1]

    print(f"  chaos run: SIGKILL worker {WK_KILL} after round "
          f"{WK_PRE_KILL}, rejoin after {WK_DEAD_ROUNDS} dead rounds...")

    def drive_chaos(server, loop, procs, extra):
        loop.run(WK_PRE_KILL)
        ok = server.wait_ef_bank(WK_PRE_KILL, range(WK_N), timeout=30.0)
        banked = server.ef_bank()
        procs[WK_KILL].send_signal(signal.SIGKILL)
        procs[WK_KILL].wait()
        end = time.monotonic() + 30
        while WK_KILL in server.live_workers() and time.monotonic() < end:
            time.sleep(0.05)
        loop.run(WK_DEAD_ROUNDS)
        dead_recs = loop.history[-WK_DEAD_ROUNDS:]

        extra.extend(spawn_local_workers(server.address, [WK_KILL]))
        end = time.monotonic() + 120
        while WK_KILL not in server.live_workers() \
                and time.monotonic() < end:
            time.sleep(0.05)
        rejoined = WK_KILL in server.live_workers()
        ef = server.request_ef(WK_KILL, timeout=120) if rejoined else None
        ef_bitwise = ef is not None and np.array_equal(ef, banked[WK_KILL][1])
        # rejoiner recompiles in its first round; then the configured pace.
        # budget: the 2x-convergence bound, minus what was already driven
        budget = 2 * WK_CLEAN_ROUNDS - WK_PRE_KILL - WK_DEAD_ROUNDS
        loop.run(1, deadline_s=WK_WARM_S, policy=warm)
        loop.run(budget - 1)
        return {
            "bank_settled": bool(ok),
            "banked_round": int(banked[WK_KILL][0]),
            "rejoined": bool(rejoined),
            "ef_bitwise_after_rejoin": bool(ef_bitwise),
            "missed_rounds_undelivered": bool(all(
                (not r["delivered"][WK_KILL]) and WK_KILL in r["dead"]
                for r in dead_recs)),
        }

    detail, chaos_hist = session(drive_chaos)
    chaos_losses = _mean_losses(chaos_hist)
    r_clean = _rounds_to(clean_losses, target)
    r_chaos = _rounds_to(chaos_losses, target)
    detail.update({
        "clean_rounds": WK_CLEAN_ROUNDS,
        "target_loss": target,
        "clean_losses": clean_losses,
        "chaos_losses": chaos_losses,
        "rounds_to_target": {"clean": r_clean, "chaos": r_chaos},
        "convergence_ok": (r_clean is not None and r_chaos is not None
                           and r_chaos <= 2 * r_clean),
        "rejoin_masks": [r["delivered"].tolist() for r in chaos_hist],
    })
    return detail


# ---------------------------------------------------------------------------
# scenario: crash during checkpoint write
# ---------------------------------------------------------------------------


def _crash_during_write(out_dir: str) -> Dict:
    """Every kill point of a save leaves the previous recovery point
    committed + loadable; a retried save over the debris succeeds."""
    import jax.numpy as jnp

    from repro.checkpoint import CheckpointManager, save_checkpoint

    root = os.path.join(out_dir, "crash_ckpt")
    shutil.rmtree(root, ignore_errors=True)
    mgr = CheckpointManager(root)
    tree = {"a": jnp.arange(12, dtype=jnp.float32)}
    mgr.save(2, tree, meta={"round": 2})

    checks = {}
    # kill mid-payload: step dir with a truncated arrays.npz, no manifest
    debris = mgr.path(4)
    os.makedirs(debris)
    with open(os.path.join(debris, "arrays.npz"), "wb") as f:
        f.write(b"PK\x03\x04 truncated by the crash")
    checks["mid_payload_prev_loadable"] = _loads_step(mgr, tree, 2)
    checks["mid_payload_debris_invisible"] = _rejects_step(mgr, tree, 4)

    # kill after the step dir, before the index rename
    save_checkpoint(mgr.path(6), tree, meta={"round": 6})
    checks["pre_index_prev_loadable"] = _loads_step(mgr, tree, 2)
    checks["pre_index_step_invisible"] = _rejects_step(mgr, tree, 6)

    # kill between index tmp write and rename
    with open(os.path.join(root, "MANIFEST.json.tmp"), "w") as f:
        f.write('{"version": 1, "steps": [2, 9')
    checks["index_tmp_prev_loadable"] = _loads_step(mgr, tree, 2)

    # a retried save over the mid-payload debris commits cleanly
    mgr.save(4, tree, meta={"round": 4})
    checks["retry_over_debris_commits"] = (mgr.latest() == 4
                                           and _loads_step(mgr, tree, 4))
    checks["all_ok"] = all(checks.values())
    return checks


def _loads_step(mgr, tree, step) -> bool:
    try:
        got, meta = mgr.load(tree, step=step)
        return (mgr.latest() is not None and meta.get("round") == step
                and bool(np.array_equal(_ravel(got), _ravel(tree))))
    except Exception:
        return False


def _rejects_step(mgr, tree, step) -> bool:
    from repro.checkpoint import CheckpointMissingError

    try:
        mgr.load(tree, step=step)
        return False
    except CheckpointMissingError:
        return True


# ---------------------------------------------------------------------------
# gate + entry
# ---------------------------------------------------------------------------


def _gate(results: Dict) -> Dict:
    srv, inp = results["server_kill"], results["inproc_resume"]
    rej, crash = results["worker_rejoin"], results["crash_write"]
    results["pass_bitwise_resume"] = bool(
        srv["bitwise_all"] and inp["bitwise_all"])
    results["pass_rejoin_ef_conserved"] = bool(
        rej["bank_settled"] and rej["rejoined"]
        and rej["ef_bitwise_after_rejoin"]
        and rej["missed_rounds_undelivered"])
    results["pass_rejoin_convergence"] = bool(rej["convergence_ok"])
    results["pass_prev_ckpt_survives"] = bool(crash["all_ok"])
    results["pass"] = all(results[k] for k in (
        "pass_bitwise_resume", "pass_rejoin_ef_conserved",
        "pass_rejoin_convergence", "pass_prev_ckpt_survives"))
    return results


def run(quick: bool = True, out_dir: str = "experiments/results") -> Dict:
    work = os.path.join(REPO, "experiments", "bench_recovery")
    os.makedirs(work, exist_ok=True)

    print("crash during checkpoint write: previous recovery point must "
          "survive every kill point...")
    crash = _crash_during_write(work)
    print("in-process faulted engine: checkpoint at round 4, resume in a "
          "fresh engine, compare bitwise...")
    inproc = _inproc_resume(work)
    print("server SIGKILL mid-run + --resume (train.py process groups)...")
    server_kill = _server_kill_resume(work)
    print(f"worker SIGKILL + rejoin (live loop, {WK_N} workers)...")
    rejoin = _worker_kill_rejoin(quick)

    results = _gate({
        "config": {
            "server": {"rounds": SRV_ROUNDS, "ckpt_every": SRV_CKPT_EVERY,
                       "kill_after_step": SRV_KILL_AFTER_STEP},
            "rejoin": {"clients": WK_N, "kill_cid": WK_KILL,
                       "pre_kill_rounds": WK_PRE_KILL,
                       "dead_rounds": WK_DEAD_ROUNDS,
                       "clean_rounds": WK_CLEAN_ROUNDS},
        },
        "crash_write": crash,
        "inproc_resume": inproc,
        "server_kill": server_kill,
        "worker_rejoin": rejoin,
    })

    s, i, r, c = server_kill, inproc, rejoin, crash
    print("\n== Crash-safe recovery & elastic membership ==")
    print(f"  [{'PASS' if results['pass_bitwise_resume'] else 'FAIL'}] "
          f"bitwise resume: server-kill leaf diffs {s['leaf_diffs'] or 'none'}"
          f", masks {s['masks_match']}, ledger {s['ledger_match']}; "
          f"inproc {i['bitwise']}")
    print(f"  [{'PASS' if results['pass_rejoin_ef_conserved'] else 'FAIL'}] "
          f"rejoin EF conserved (atol=0): banked@r{r['banked_round']}, "
          f"bitwise {r['ef_bitwise_after_rejoin']}, missed rounds "
          f"undelivered {r['missed_rounds_undelivered']}")
    print(f"  [{'PASS' if results['pass_rejoin_convergence'] else 'FAIL'}] "
          f"rejoin convergence: clean {r['rounds_to_target']['clean']} "
          f"rounds to loss {r['target_loss']:.4f}, chaos "
          f"{r['rounds_to_target']['chaos']} (bound 2x)")
    print(f"  [{'PASS' if results['pass_prev_ckpt_survives'] else 'FAIL'}] "
          f"crash-during-write: {c}")

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "recovery.json"), "w") as f:
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
