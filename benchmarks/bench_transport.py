"""Live socket transport: bytes, bitwise parity, conservation, straggle.

PR 6 made faults *first-class* but simulated; here the same round runs
over a real wire — ``repro.comm.transport`` sockets between this process
and worker subprocesses (``repro.launch.worker``) — and is gated against
the in-process oracle. Four gates:

* **bytes match**: data-frame bytes billed by the socket server equal
  ``N * codec.nbytes`` exactly on settled rounds (control traffic —
  heartbeats, ACKs, length prefixes — is accounted separately as
  overhead), at BOTH the tiny/stc scenario and the paper mlp/mnist
  3SFC config; the 8-client total must equal the wire record's
  (``experiments/results/wire.json``) measured
  ``channel.uplink_bytes_per_round`` (same codec, so the live
  wire carries not one byte more than the accounted one);
* **socket bitwise**: a live multi-process run — including injected frame
  drops (``rx_filter``) and a SIGKILLed worker — produces params, per-
  client EF, and delivered masks bitwise equal to a width-matched
  in-process oracle on the identical fault pattern (``replay_live_run``:
  each client's worker step at width 1, as its worker runs it, and the
  live loop's server step), and within two roundings of the largest
  weight of the vmapped engine (``build_fl_round`` + ``fault_schedule_fn``),
  which runs the clients at width N;
* **residual conservation**: for a round whose frame the wire ate, the
  EF identity ``e' = u - delivered`` holds exactly (``delivered = 0``,
  so ``e' == u``) — checked at ``atol=0`` on the masked in-process
  pipeline (``build_fl_round`` + ``fault_schedule_fn``) and transferred
  to the wire by the EF-bitwise gate;
* **straggle isolation**: with one worker sleeping ``STRAGGLE_S`` per
  round and a tight deadline, measured round wall clock stays bounded by
  the deadline (+ slack), NOT by the straggler — and the slow worker is
  marked undelivered, never dead (heartbeats flow during its sleep).

Worker round-0 jit compilation happens inside the live round, so every
scenario warms round 0 under a generous deadline and gates only the
settled rounds after it. Deterministic except the wall-clock gate
(slack-padded); ``--quick`` == ``--full``. Writes
``experiments/results/transport.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- tiny scenario (bitwise / faults / straggle) ----------------------------
TINY_N = 3
TINY_ROUNDS = 5                      # 0 = warm-up, 1 = settled null, 2-4 faulted
TINY_TRAIN = 120
DROPS = {(2, 1), (3, 0)}             # (round, cid) frames the wire eats
KILL_CID, KILL_AFTER_ROUND = 2, 3    # SIGKILL between rounds 3 and 4
CONS_ROUND, CONS_CID = 3, 0          # conservation checked on this drop

# -- paper-shape scenario (byte gate vs the wire record) --------------------
MLP_N = 2                            # live workers; scaled to the 8-client
MLP_MEASURED_ROUNDS = 2              # total by messages (frames are i.i.d.
MLP_TRAIN = 256                      # in size: codec.nbytes each)

# -- straggle scenario ------------------------------------------------------
STRAGGLE_CID, STRAGGLE_S = 1, 4.0
STRAGGLE_DEADLINE_S = 0.75
STRAGGLE_ROUNDS = 3                  # measured (after warm-up)
WALL_SLACK_S = 1.0                   # server-side decode/step overhead

WARM_DEADLINE_S = 600.0              # round-0 jit compile inside workers


def _ravel(tree) -> np.ndarray:
    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


def _tiny_problem():
    from repro.configs.base import CompressorConfig, FLConfig
    from repro.models.cnn import VisionSpec

    spec = VisionSpec("tiny", (6, 6, 1), 3)
    comp = CompressorConfig(kind="stc", keep_ratio=0.1)
    fl = FLConfig(num_clients=TINY_N, local_steps=2, local_lr=0.05,
                  local_batch=4, compressor=comp, seed=0)
    return spec, fl


def _build(model_name, spec, fl, run):
    from repro.core.strategy import make_strategy
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import make_paper_model

    model = make_paper_model(model_name, spec)
    params = model.init(jax.random.PRNGKey(fl.seed))
    strategy = make_strategy(fl.compressor, loss_fn=model.syn_loss,
                             syn_spec=vision_syn_spec(spec, fl.compressor),
                             local_lr=fl.local_lr)
    codec = strategy.wire_codec(params, policy=run.wire_policy)
    return model, params, strategy, codec


def _socket_run(run, model_name, spec, train_size, params, strategy, codec,
                *, rounds: int, rx_filter=None, straggle=None, on_round=None,
                collect_ef: bool = True):
    """Spawn workers, warm round 0 generously, drive the measured rounds.

    Returns (final_params, efs, history, stats) where ``efs[i]`` is the
    worker's flat EF dump (None for dead workers / collect_ef=False) and
    ``stats`` carries the server's byte buckets.
    """
    from repro.comm.transport import SocketServer, spawn_local_workers
    from repro.fl.engine import LiveRoundLoop, RetryPolicy
    from repro.launch.worker import vision_setup

    N = run.fl.num_clients
    server = SocketServer(N, heartbeat_s=run.heartbeat_s,
                          liveness_timeout_s=run.liveness_timeout_s,
                          rx_filter=rx_filter)
    procs = spawn_local_workers(server.address, range(N))
    efs = [None] * N
    try:
        server.wait_ready(60)
        server.send_setup(vision_setup(run, model=model_name, spec=spec,
                                       train_size=train_size,
                                       straggle=straggle))
        loop = LiveRoundLoop(server, strategy, codec, run, params,
                             on_round=on_round)
        warm = RetryPolicy(max_retries=0, recv_timeout_s=WARM_DEADLINE_S,
                           max_timeout_s=WARM_DEADLINE_S)
        loop.run(1, deadline_s=WARM_DEADLINE_S, policy=warm)
        final = jax.device_get(loop.run(rounds - 1))
        if collect_ef:
            live = set(server.live_workers())
            efs = [server.request_ef(i, timeout=30) if i in live else None
                   for i in range(N)]
        stats = {"uplink_per_round": list(server.uplink.per_round),
                 "downlink_per_round": list(server.downlink.per_round),
                 "overhead_up": int(server.overhead_up),
                 "overhead_down": int(server.overhead_down)}
    finally:
        server.stop()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()
    return final, efs, loop.history, stats


def _fault_plans():
    """(R, N) participate/delivered plans for the tiny fault scenario."""
    plan = np.ones((TINY_ROUNDS, TINY_N), bool)
    part = np.ones((TINY_ROUNDS, TINY_N), bool)
    for (r, c) in DROPS:
        plan[r, c] = False
    for r in range(KILL_AFTER_ROUND + 1, TINY_ROUNDS):
        plan[r, KILL_CID] = False
        part[r, KILL_CID] = False
    return plan, part


def _tiny_oracle(model, params, strategy, codec, fl, train, pools,
                 plan, part):
    """In-process masked pipeline under the identical fault pattern."""
    from repro.configs.run import RunConfig
    from repro.fl.engine import RoundEngine, vision_batcher
    from repro.fl.faults import null_schedule
    from repro.fl.round import build_fl_round

    plan_j, part_j = jnp.asarray(plan), jnp.asarray(part)

    def sched_fn(r, n):
        s = null_schedule(n)
        return s._replace(participate=part_j[r], delivered=plan_j[r])

    engine = RoundEngine(
        build_fl_round(model.loss, strategy, RunConfig(fl=fl, wire="codec"),
                       codec=codec, fault_schedule_fn=sched_fn),
        vision_batcher(train.x, train.y, pools, fl.local_steps,
                       fl.local_batch),
        seed=fl.seed)
    return engine


def _conservation(engine, model, params, strategy, fl, train, pools) -> Dict:
    """EF mass on the CONS_ROUND drop: replay the oracle to the round,
    recompute u = g + e on the engine-contract batch, run the round, and
    check e' == u exactly (the delivered payload is the zero tree)."""
    from repro.fl.client import local_train
    from repro.fl.faults import residual_mass_conserved

    state = engine.init_state(params, TINY_N, strategy)
    state, _ = engine.run_loop(state, CONS_ROUND)
    ef_before = jax.tree_util.tree_map(lambda l: l[CONS_CID], state.ef)
    data_key = jax.random.fold_in(jax.random.PRNGKey(fl.seed), 0)
    kr = jax.random.fold_in(data_key, jnp.int32(CONS_ROUND))
    k = jax.random.fold_in(kr, CONS_CID)
    pos = jax.random.randint(k, (fl.local_steps, fl.local_batch), 0,
                             pools.size[CONS_CID])
    idx = pools.index[CONS_CID, pos]
    batch = {"x": jnp.asarray(train.x)[idx], "y": jnp.asarray(train.y)[idx]}
    g, _ = local_train(model.loss, state.params, batch, fl.local_lr)
    u = jax.tree_util.tree_map(lambda a, b: a + b, g, ef_before)
    state, _ = engine.run_loop(state, 1)
    e_new = jax.tree_util.tree_map(lambda l: l[CONS_CID], state.ef)
    zero = jax.tree_util.tree_map(jnp.zeros_like, u)
    exact = bool(residual_mass_conserved(u, e_new, zero, atol=0.0))
    return {"round": CONS_ROUND, "cid": CONS_CID, "exact": exact,
            "max_abs_residual": float(max(
                float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree_util.tree_leaves(e_new),
                                jax.tree_util.tree_leaves(u))))}


def _tiny_scenarios() -> Dict:
    """Bitwise-vs-oracle under faults + conservation + tiny byte check."""
    from repro.configs.run import RunConfig
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.engine import device_pools
    from repro.launch.worker import replay_live_run, vision_setup

    spec, fl = _tiny_problem()
    run = RunConfig(fl=fl, wire="codec", transport="socket",
                    round_deadline_s=60.0, recv_timeout_s=1.0,
                    recv_backoff=1.5, transport_retries=1,
                    heartbeat_s=0.2, liveness_timeout_s=3.0)
    model, params, strategy, codec = _build("mlp", spec, fl, run)
    train = make_class_image_dataset(jax.random.PRNGKey(fl.seed), TINY_TRAIN,
                                     spec.input_shape, spec.num_classes)
    parts = dirichlet_partition(train.y, TINY_N, alpha=fl.dirichlet_alpha,
                                seed=fl.seed, min_per_client=fl.local_batch)
    pools = device_pools(parts)
    plan, part = _fault_plans()

    setup = vision_setup(run, model="mlp", spec=spec, train_size=TINY_TRAIN)
    oracle_params, oracle_efs = replay_live_run(setup, params, plan, part)

    # live: the wire eats DROPS frames; the worker dies mid-run
    def rx_filter(cid, rnd, buf):
        return None if (rnd, cid) in DROPS else buf

    killed = {"done": False}
    procs_box = {}

    def on_round(rec, rep):
        if rec["round"] == KILL_AFTER_ROUND and not killed["done"]:
            p = procs_box["procs"][KILL_CID]
            p.send_signal(signal.SIGKILL)
            p.wait()
            killed["done"] = True

    # _socket_run spawns procs internally; thread them out for the killer
    from repro.comm.transport import SocketServer, spawn_local_workers
    from repro.fl.engine import LiveRoundLoop, RetryPolicy

    server = SocketServer(TINY_N, heartbeat_s=run.heartbeat_s,
                          liveness_timeout_s=run.liveness_timeout_s,
                          rx_filter=rx_filter)
    procs = spawn_local_workers(server.address, range(TINY_N))
    procs_box["procs"] = procs
    efs = [None] * TINY_N
    try:
        server.wait_ready(60)
        server.send_setup(setup)
        loop = LiveRoundLoop(server, strategy, codec, run, params,
                             on_round=on_round)
        warm = RetryPolicy(max_retries=0, recv_timeout_s=WARM_DEADLINE_S,
                           max_timeout_s=WARM_DEADLINE_S)
        loop.run(1, deadline_s=WARM_DEADLINE_S, policy=warm)
        live_params = jax.device_get(loop.run(TINY_ROUNDS - 1))
        live = set(server.live_workers())
        efs = [server.request_ef(i, timeout=30) if i in live else None
               for i in range(TINY_N)]
        up_per_round = list(server.uplink.per_round)
        overhead = {"up": int(server.overhead_up),
                    "down": int(server.overhead_down)}
    finally:
        server.stop()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()

    masks_ok = all(
        rec["delivered"].tolist() == plan[rec["round"]].tolist()
        for rec in loop.history)
    params_ok = bool((_ravel(oracle_params) == _ravel(live_params)).all())
    ef_ok, ef_detail = True, {}
    for i in range(TINY_N):
        if i == KILL_CID:
            ef_detail[str(i)] = "dead" if efs[i] is None else "unexpected"
            ef_ok &= efs[i] is None
        else:
            same = efs[i] is not None and bool(
                (efs[i] == oracle_efs[i]).all())
            ef_detail[str(i)] = bool(same)
            ef_ok &= same
    # the vmapped engine on the same fault pattern runs the clients at
    # width N: within two roundings of the largest weight of the live run
    engine = _tiny_oracle(model, params, strategy, codec, fl, train, pools,
                          plan, part)
    state, _ = engine.run_loop(engine.init_state(params, TINY_N, strategy),
                               TINY_ROUNDS)
    vmap_params, vmap_ef = jax.device_get((state.params, state.ef))
    vmap_tol = 2 * float(np.spacing(np.max(np.abs(_ravel(vmap_params)))))
    vmap_diff = float(np.max(np.abs(_ravel(vmap_params)
                                    - _ravel(live_params))))
    for i in range(TINY_N):
        if efs[i] is not None:
            row = _ravel(jax.tree_util.tree_map(lambda l: l[i], vmap_ef))
            vmap_diff = max(vmap_diff, float(np.max(np.abs(row - efs[i]))))
    cons = _conservation(engine, model, params, strategy, fl, train, pools)
    # conservation transfers to the wire because the dropped client's EF
    # (CONS_CID survives the run) is bitwise equal to the oracle's
    cons["wire_ef_bitwise"] = ef_detail[str(CONS_CID)] is True

    nbytes = int(codec.nbytes)
    settled_bytes = int(loop.history[1]["bytes_up"])    # round 1: null, warm
    return {
        "codec_nbytes": nbytes,
        "delivered_masks": [r["delivered"].tolist() for r in loop.history],
        "expected_masks": plan.tolist(),
        "masks_match": bool(masks_ok),
        "params_bitwise": params_ok,
        "ef_bitwise": ef_detail,
        "ef_all_ok": bool(ef_ok),
        "vmap_max_abs_diff": vmap_diff,
        "vmap_tol": vmap_tol,
        "vmap_within_tol": vmap_diff <= vmap_tol,
        "dead_at_end": sorted(loop.history[-1]["dead"]),
        "retries_per_round": [r["retries"] for r in loop.history],
        "uplink_bytes_per_round": up_per_round,
        "settled_null_round_bytes": settled_bytes,
        "settled_null_round_expected": TINY_N * nbytes,
        "overhead_bytes": overhead,
        "conservation": cons,
    }


def _mlp_bytes_scenario() -> Dict:
    """Paper-shape byte gate: live mlp/mnist 3SFC frames over the socket
    must bill exactly ``codec.nbytes`` per message — the same measured
    bytes bench_wire accounts — so the 8-client round total equals the
    wire record's ``channel.uplink_bytes_per_round``."""
    from repro.configs.base import FLConfig
    from repro.configs.run import RunConfig
    from repro.core import flat
    from repro.fl.budget import matched_compressors
    from repro.models.cnn import MNIST_SPEC

    # the exact bench_wire codec config (syn_batch-matched 3SFC)
    from repro.models.cnn import make_paper_model
    model0 = make_paper_model("mlp", MNIST_SPEC)
    d = flat.tree_size(model0.init(jax.random.PRNGKey(0)))
    comp = matched_compressors("mlp", MNIST_SPEC, d)["threesfc"]
    fl = FLConfig(num_clients=MLP_N, local_steps=2, local_lr=0.05,
                  local_batch=8, compressor=comp, seed=0)
    run = RunConfig(fl=fl, wire="codec", transport="socket",
                    round_deadline_s=120.0, recv_timeout_s=60.0,
                    recv_backoff=1.5, transport_retries=0,
                    heartbeat_s=0.2, liveness_timeout_s=10.0)
    model, params, strategy, codec = _build("mlp", MNIST_SPEC, fl, run)
    nbytes = int(codec.nbytes)

    _, _, history, stats = _socket_run(
        run, "mlp", MNIST_SPEC, MLP_TRAIN, params, strategy, codec,
        rounds=1 + MLP_MEASURED_ROUNDS, collect_ef=False)

    measured = [int(r["bytes_up"]) for r in history[1:]]
    per_msg = measured[0] // MLP_N if measured else 0
    wire_ref: Optional[Dict] = None
    wire_path = os.path.join(REPO, "experiments", "results", "wire.json")
    if os.path.exists(wire_path):
        with open(wire_path) as f:
            wire = json.load(f)
        wire_ref = dict(wire["measure"]["channel"])
        wire_ref["threesfc_measured_bytes"] = \
            wire["measure"]["methods"]["threesfc"]["measured_bytes"]
    return {
        "codec_nbytes": nbytes,
        "live_clients": MLP_N,
        "uplink_bytes_per_round": stats["uplink_per_round"],
        "measured_round_bytes": measured,
        "per_message_bytes": int(per_msg),
        "n8_round_bytes": int(8 * per_msg),
        "overhead_bytes": {"up": stats["overhead_up"],
                           "down": stats["overhead_down"]},
        "wire_reference": wire_ref,
        "retries_per_round": [r["retries"] for r in history],
    }


def _straggle_scenario() -> Dict:
    """One worker sleeps STRAGGLE_S per round; a tight deadline must bound
    the round's wall clock — slow means undelivered, never waited-on and
    never dead."""
    from repro.configs.run import RunConfig

    spec, fl = _tiny_problem()
    run = RunConfig(fl=fl, wire="codec", transport="socket",
                    round_deadline_s=STRAGGLE_DEADLINE_S,
                    recv_timeout_s=STRAGGLE_DEADLINE_S,
                    recv_backoff=1.5, transport_retries=0,
                    heartbeat_s=0.2, liveness_timeout_s=3.0)
    _, params, strategy, codec = _build("mlp", spec, fl, run)
    _, _, history, _ = _socket_run(
        run, "mlp", spec, TINY_TRAIN, params, strategy, codec,
        rounds=1 + STRAGGLE_ROUNDS,
        straggle={STRAGGLE_CID: STRAGGLE_S}, collect_ef=False)

    measured = history[1:]
    expect = [True] * TINY_N
    expect[STRAGGLE_CID] = False
    rounds = [{
        "round": r["round"],
        "wall_s": float(r["wall_s"]),
        "delivered": r["delivered"].tolist(),
        "dead": r["dead"],
        "wall_bounded": bool(r["wall_s"] <= STRAGGLE_DEADLINE_S
                             + WALL_SLACK_S),
        "wall_below_straggle": bool(r["wall_s"] <= 0.5 * STRAGGLE_S),
        "mask_ok": r["delivered"].tolist() == expect,
        "straggler_not_dead": STRAGGLE_CID not in r["dead"],
    } for r in measured]
    return {
        "straggle_cid": STRAGGLE_CID,
        "straggle_s": STRAGGLE_S,
        "deadline_s": STRAGGLE_DEADLINE_S,
        "wall_slack_s": WALL_SLACK_S,
        "warmup_wall_s": float(history[0]["wall_s"]),
        "rounds": rounds,
    }


def _gate(results: Dict) -> Dict:
    tiny, mlp, strag = (results["faulted"], results["bytes_mlp"],
                        results["straggle"])
    bytes_ok = (tiny["settled_null_round_bytes"]
                == tiny["settled_null_round_expected"])
    bytes_ok &= all(b == MLP_N * mlp["codec_nbytes"]
                    for b in mlp["measured_round_bytes"])
    if mlp["wire_reference"] is not None:
        bytes_ok &= (mlp["n8_round_bytes"]
                     == mlp["wire_reference"]["uplink_bytes_per_round"])
        bytes_ok &= (mlp["per_message_bytes"]
                     == mlp["wire_reference"]["threesfc_measured_bytes"])
    results["pass_bytes_match"] = bool(bytes_ok)
    results["pass_socket_bitwise"] = bool(
        tiny["masks_match"] and tiny["params_bitwise"] and tiny["ef_all_ok"]
        and tiny["vmap_within_tol"])
    results["pass_residual_conservation"] = bool(
        tiny["conservation"]["exact"]
        and tiny["conservation"]["wire_ef_bitwise"])
    results["pass_straggle_isolation"] = bool(
        strag["rounds"]
        and all(r["wall_bounded"] and r["wall_below_straggle"]
                and r["mask_ok"] and r["straggler_not_dead"]
                for r in strag["rounds"]))
    results["pass"] = all(results[k] for k in (
        "pass_bytes_match", "pass_socket_bitwise",
        "pass_residual_conservation", "pass_straggle_isolation"))
    return results


def run(quick: bool = True, out_dir: str = "experiments/results") -> Dict:
    # deterministic modulo wall clock: quick == full (orchestrator symmetry)
    del quick
    print("live tiny/stc rounds with injected drops + SIGKILL vs the "
          "in-process oracle...")
    faulted = _tiny_scenarios()
    print("live mlp/mnist 3SFC frames over the socket (byte gate vs "
          "the wire record)...")
    bytes_mlp = _mlp_bytes_scenario()
    print(f"straggle: worker {STRAGGLE_CID} sleeps {STRAGGLE_S:.1f}s/round "
          f"under a {STRAGGLE_DEADLINE_S:.2f}s deadline...")
    straggle = _straggle_scenario()

    results = _gate({
        "config": {
            "tiny": {"clients": TINY_N, "rounds": TINY_ROUNDS,
                     "drops": sorted(list(DROPS)),
                     "kill_cid": KILL_CID,
                     "kill_after_round": KILL_AFTER_ROUND},
            "mlp": {"clients": MLP_N, "measured_rounds": MLP_MEASURED_ROUNDS},
            "straggle": {"cid": STRAGGLE_CID, "sleep_s": STRAGGLE_S,
                         "deadline_s": STRAGGLE_DEADLINE_S,
                         "rounds": STRAGGLE_ROUNDS},
        },
        "faulted": faulted,
        "bytes_mlp": bytes_mlp,
        "straggle": straggle,
    })

    t, m, s = faulted, bytes_mlp, straggle
    print("\n== Socket transport vs in-process oracle ==")
    print(f"  [{'PASS' if results['pass_bytes_match'] else 'FAIL'}] "
          f"wire bills exactly N*nbytes: tiny "
          f"{t['settled_null_round_bytes']}/{t['settled_null_round_expected']}"
          f" B, mlp {m['measured_round_bytes']} B "
          f"(n8 total {m['n8_round_bytes']} B == wire record "
          f"{(m['wire_reference'] or {}).get('uplink_bytes_per_round')})")
    print(f"  [{'PASS' if results['pass_socket_bitwise'] else 'FAIL'}] "
          f"live faulted run bitwise == oracle: masks "
          f"{t['masks_match']}, params {t['params_bitwise']}, "
          f"EF {t['ef_bitwise']}; vmapped engine max |diff| "
          f"{t['vmap_max_abs_diff']:.3g} <= {t['vmap_tol']:.3g}")
    print(f"  [{'PASS' if results['pass_residual_conservation'] else 'FAIL'}]"
          f" residual mass conserved on dropped frame (round "
          f"{CONS_ROUND}, cid {CONS_CID}): exact="
          f"{t['conservation']['exact']}, wire EF bitwise="
          f"{t['conservation']['wire_ef_bitwise']}")
    walls = [f"{r['wall_s']:.2f}" for r in s["rounds"]]
    print(f"  [{'PASS' if results['pass_straggle_isolation'] else 'FAIL'}] "
          f"straggler ({STRAGGLE_S:.1f}s sleep) bounded by the "
          f"{STRAGGLE_DEADLINE_S:.2f}s deadline: wall {walls} s, "
          f"undelivered-not-dead each round")

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "transport.json"), "w") as f:
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
