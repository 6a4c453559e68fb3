"""Federated training driver — the end-to-end entry point.

Runs a real (executed, not dry-run) FL training job on whatever devices
exist: paper vision models by name, or a reduced LM-family arch. The
production-mesh path is exercised by dryrun.py; this driver is the
"train a ~100M model for a few hundred rounds" deliverable and writes
checkpoints + a metrics JSONL.

Both paths drive ``repro.fl.engine.RoundEngine``: data and Dirichlet pools
are device-resident, each eval block of ``--eval-every`` rounds is ONE
scanned dispatch with the EF state donated in place, and compressor budgets
come from the shared ``repro.fl.budget`` module (the same construction the
benchmarks use). The flags are folded into ONE validated
``repro.configs.run.RunConfig`` (logged as ``run_config.json`` next to the
metrics) and the round is built by ``repro.fl.round.build_fl_round`` over
the compressor's registered strategy; ``--wire codec`` ships framed uint8
buffers across the client/server boundary instead of float trees.

    PYTHONPATH=src python -m repro.launch.train --model mlp --dataset mnist \
        --compressor threesfc --rounds 200 --clients 10
    PYTHONPATH=src python -m repro.launch.train --model mlp --wire codec \
        --rounds 50 --clients 10     # measured serialized uplink bytes
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --rounds 20          # reduced LM config, token data
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (CheckpointManager, load_fl_checkpoint,
                              save_checkpoint, save_fl_checkpoint)
from repro.configs.base import ARCH_IDS, CompressorConfig, get_smoke_config
from repro.configs.run import RunConfig
from repro.core import flat
from repro.core.strategy import make_strategy
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_class_image_dataset, make_token_dataset
from repro.fl.budget import matched_compressors
from repro.fl.engine import (RetryPolicy, RoundEngine, device_pools,
                             token_batcher, vision_batcher)
from repro.fl.round import build_fl_round
from repro.fl.sharding import make_fl_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.build import build_model, syn_loss_fn, syn_spec_for, vision_syn_spec
from repro.models.cnn import DATASETS, accuracy, make_paper_model
from repro.models.encdec import EncDec
from repro.obs import (configure_tracer, get_registry, get_tracer,
                       merge_traces, write_chrome_trace)


class _ProfileWindow:
    """``jax.profiler`` capture over a round window ``[start, stop)``.

    Drive it with ``maybe_start(next_round)`` before rounds begin and
    ``after_round(completed_round)`` at round boundaries; ``close()``
    guarantees a started capture is stopped. On the socket transport the
    window is exact (the loop reports every round); on the in-process
    engine rounds live inside scanned blocks, so the window snaps to
    eval-block boundaries."""

    def __init__(self, out_dir: str, start: int, stop: int):
        self.dir, self.a, self.b = out_dir, start, stop
        self.on = False
        self.done = False

    def maybe_start(self, next_round: int) -> None:
        if self.done or self.on or not (self.a <= next_round < self.b):
            return
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.on = True

    def after_round(self, completed_round: int) -> None:
        nxt = completed_round + 1
        if self.on and nxt >= self.b:
            jax.profiler.stop_trace()
            self.on, self.done = False, True
        self.maybe_start(nxt)

    def close(self) -> None:
        if self.on:
            jax.profiler.stop_trace()
            self.on, self.done = False, True


def _make_profiler(args, r0: int):
    if not args.profile:
        return None
    if args.profile_window:
        a, b = (int(x) for x in args.profile_window.split(":", 1))
    else:
        a, b = r0, args.rounds
    return _ProfileWindow(args.profile, a, b)


def _dump_obs(out_dir: str, server=None) -> None:
    """End-of-run observability artifacts: ``meters.json`` always; when
    tracing is on, the merged span trace as ``trace.jsonl`` plus a
    Chrome/Perfetto ``trace.chrome.json`` (workers' piggybacked spans are
    shifted onto the server clock by the heartbeat offset estimates)."""
    tracer = get_tracer()
    if tracer.enabled:
        records = tracer.drain()
        if server is not None:
            records = merge_traces(records, server.pop_worker_spans(),
                                   server.clock_offsets())
        with open(os.path.join(out_dir, "trace.jsonl"), "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        write_chrome_trace(records, os.path.join(out_dir, "trace.chrome.json"))
        print(f"trace -> {out_dir}/trace.jsonl ({len(records)} records, "
              f"{tracer.dropped} dropped)")
    with open(os.path.join(out_dir, "meters.json"), "w") as f:
        json.dump(get_registry().snapshot(), f, indent=1)


def make_fanout(args):
    """(client_parallel, mesh, shardings) from --client-parallel.

    'auto' picks the sharded fan-out when the host has multiple devices and
    the client count divides evenly over them, else the single-device vmap.
    Explicit 'shard_map' fails loudly (divisibility / single device) rather
    than silently degrading.
    """
    mode = args.client_parallel
    n = len(jax.devices())
    if mode == "auto":
        mode = "shard_map" if n > 1 and args.clients % n == 0 else "vmap"
    if mode == "vmap":
        return "vmap", None, None
    if n < 2:
        raise ValueError(
            "--client-parallel shard_map needs >1 device (a 1-shard "
            "shard_map would be vmap with extra steps); this host has "
            f"{n} — use 'vmap'/'auto' or force devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    mesh = make_host_mesh()
    shardings = make_fl_shardings(mesh)
    shardings.check_divisible(args.clients)
    return "shard_map", mesh, shardings


def _write_run_config(out_dir: str, run: RunConfig) -> None:
    """Log the run's exact configuration next to its metrics."""
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "run_config.json"), "w") as f:
        json.dump(run.to_json(), f, indent=1)


def _ckpt_manager(args) -> CheckpointManager:
    """The run's checkpoint root: ``--resume PATH`` names an existing root
    to continue (new recovery points land in the same index); otherwise
    ``<out>/ckpt``."""
    return CheckpointManager(args.resume or os.path.join(args.out, "ckpt"))


def _check_resume_config(meta, run: RunConfig) -> None:
    """A resumed run must replay the checkpointed configuration — bitwise
    resume is only defined for the same (seed, fault_seed, knobs)."""
    want, got = run.to_json(), meta.get("run")
    if got is not None and got != want:
        diff = sorted(k for k in set(want) | set(got)
                      if want.get(k) != got.get(k))
        raise ValueError(
            f"--resume configuration mismatch on {diff}: the checkpoint was "
            f"written under a different RunConfig; rounds replayed from it "
            f"would not be the same run")


def _history_to_json(history):
    """Live-loop round records -> JSON-serializable checkpoint form."""
    return [{"round": int(rec["round"]),
             "wall_s": float(rec["wall_s"]),
             "participate": [bool(b) for b in rec["participate"]],
             "delivered": [bool(b) for b in rec["delivered"]],
             "retries": int(rec["retries"]),
             "bytes_up": int(rec["bytes_up"]),
             "bytes_down": int(rec["bytes_down"]),
             "overhead_up": int(rec.get("overhead_up", 0)),
             "overhead_down": int(rec.get("overhead_down", 0)),
             "dead": [int(c) for c in rec["dead"]],
             "losses": {str(k): float(v) for k, v in rec["losses"].items()}}
            for rec in history]


def _history_from_json(recs):
    return [{**rec,
             "participate": np.asarray(rec["participate"], bool),
             "delivered": np.asarray(rec["delivered"], bool),
             "losses": {int(k): float(v) for k, v in rec["losses"].items()}}
            for rec in recs]


def train_vision_socket(args, *, spec, model, params, strategy, run, codec):
    """The live multi-process path: a ``SocketServer`` + N spawned workers
    driven by ``repro.fl.engine.LiveRoundLoop`` — framed rounds over real
    sockets with the run's deadline/backoff/liveness knobs. Same metrics
    JSONL + checkpoint contract as the in-process path."""
    from repro.comm.transport import SocketServer, spawn_local_workers
    from repro.fl.engine import LiveRoundLoop
    from repro.launch.worker import vision_setup

    test = make_class_image_dataset(
        jax.random.fold_in(jax.random.PRNGKey(args.seed), 1), 1000,
        spec.input_shape, spec.num_classes)

    @jax.jit
    def eval_acc(p):
        return accuracy(model.apply(p, jnp.asarray(test.x)),
                        jnp.asarray(test.y))

    mgr = _ckpt_manager(args)
    r0, bank, history = 0, {}, []
    if args.resume:
        # full recovery point: params + per-client EF bank + ledger +
        # history; every worker is a (re)joiner the server re-syncs
        params, bank, meta = load_fl_checkpoint(mgr, params)
        _check_resume_config(meta, run)
        r0 = int(meta["round"])
        history = _history_from_json(meta.get("history", []))
        print(f"resuming from {mgr.path(r0)} at round {r0}")

    _write_run_config(args.out, run)
    t0 = time.time()
    server = SocketServer(args.clients,
                          heartbeat_s=run.heartbeat_s,
                          liveness_timeout_s=run.liveness_timeout_s)
    if args.resume:
        server.restore_ledger(meta["ledger"])  # round numbering continues
        server.seed_ef_bank(bank)
    procs = spawn_local_workers(server.address, range(args.clients))
    profiler = _make_profiler(args, r0)
    try:
        server.wait_ready()
        server.send_setup(vision_setup(run, model=args.model, spec=spec,
                                       train_size=args.train_size,
                                       trace=args.trace))
        mode = "a" if args.resume else "w"
        with open(os.path.join(args.out, "metrics.jsonl"), mode) as log:
            def on_round(rec, rep):
                if profiler is not None:
                    profiler.after_round(rec["round"])
                r = rec["round"] + 1
                if r % args.eval_every and r != args.rounds:
                    return
                out = {"round": r,
                       "loss": float(np.mean(list(rec["losses"].values())))
                       if rec["losses"] else None,
                       "acc": float(eval_acc(loop.params)),
                       "delivered": int(rec["delivered"].sum()),
                       "retries": rec["retries"],
                       "bytes_up": rec["bytes_up"],
                       "bytes_down": rec["bytes_down"],
                       "overhead_up": rec["overhead_up"],
                       "overhead_down": rec["overhead_down"],
                       "wall_s": round(rec["wall_s"], 4),
                       "elapsed_s": round(time.time() - t0, 1)}
                print(json.dumps(out))
                log.write(json.dumps(out) + "\n")
                log.flush()

            def ckpt_fn(lp, rnd):
                # settle: every participating live worker must have pushed
                # its round-``rnd`` commit before the bank is snapshotted —
                # an unsettled recovery point would not resume bitwise
                rec = lp.history[-1]
                cids = [c for c in range(args.clients)
                        if rec["participate"][c] and c not in rec["dead"]]
                if not server.wait_ef_bank(rnd, cids, timeout=30.0):
                    live = set(server.live_workers())
                    cids = [c for c in cids if c in live]
                    if not server.wait_ef_bank(rnd, cids, timeout=30.0):
                        raise RuntimeError(
                            f"EF bank did not settle for round {rnd}; "
                            f"refusing to write an unsettled recovery point")
                save_fl_checkpoint(
                    mgr, rnd + 1, lp.params, run=run,
                    ledger=server.ledger(),
                    history=_history_to_json(lp.history),
                    ef_bank=server.ef_bank(),
                    extra={"model": args.model, "dataset": args.dataset,
                           "compressor": args.compressor,
                           "transport": "socket"})

            loop = LiveRoundLoop(server, strategy, codec, run, params,
                                 on_round=on_round)
            loop.history.extend(history)
            ck = dict(ckpt_every=args.ckpt_every,
                      ckpt_fn=ckpt_fn if args.ckpt_every else None)
            # the first round jit-compiles the client step inside every
            # worker (round 0, or the first resumed round of freshly
            # restarted workers); a tight configured deadline would mark
            # them all undelivered before they ever ran. Boot patiently,
            # then enforce the configured deadline/backoff after that.
            remaining = args.rounds - r0
            boot = max(run.round_deadline_s, 300.0)
            if profiler is not None:
                profiler.maybe_start(r0)
            if remaining > 0:
                loop.run(1, deadline_s=boot,
                         policy=RetryPolicy(max_retries=0,
                                            recv_timeout_s=boot,
                                            max_timeout_s=boot), **ck)
                loop.run(remaining - 1, **ck)
            final = loop.params
            if args.ckpt_every and mgr.latest() != args.rounds:
                # final recovery point (cadence may not divide --rounds)
                ckpt_fn(loop, args.rounds - 1)
        _dump_obs(args.out, server=server)
    finally:
        if profiler is not None:
            profiler.close()
        server.stop()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
    save_checkpoint(os.path.join(args.out, "final"), final,
                    meta={"model": args.model, "dataset": args.dataset,
                          "compressor": args.compressor,
                          "rounds": args.rounds, "transport": "socket"})
    print(f"checkpoint -> {args.out}/final")


def train_vision(args):
    spec = DATASETS[args.dataset]
    model = make_paper_model(args.model, spec)
    params = model.init(jax.random.PRNGKey(args.seed))
    d = flat.tree_size(params)
    comp = matched_compressors(args.model, spec, d)[args.compressor]
    syn_spec = vision_syn_spec(spec, comp)
    strategy = make_strategy(comp, loss_fn=model.syn_loss, syn_spec=syn_spec,
                             local_lr=args.lr)
    if args.transport == "socket":
        # worker processes ARE the fan-out; the mesh paths stay in-process
        mode, mesh, shardings = "vmap", None, None
    else:
        mode, mesh, shardings = make_fanout(args)
    run = RunConfig.from_flags(args, compressor=comp, client_parallel=mode,
                               mesh=mesh)
    codec = strategy.wire_codec(params, policy=run.wire_policy) \
        if run.wire == "codec" else None
    if run.transport == "socket":
        return train_vision_socket(args, spec=spec, model=model,
                                   params=params, strategy=strategy,
                                   run=run, codec=codec)

    key = jax.random.PRNGKey(args.seed)
    train = make_class_image_dataset(key, args.train_size, spec.input_shape,
                                     spec.num_classes)
    test = make_class_image_dataset(jax.random.fold_in(key, 1), 1000,
                                    spec.input_shape, spec.num_classes)
    parts = dirichlet_partition(train.y, args.clients, alpha=args.alpha,
                                seed=args.seed, min_per_client=args.batch)
    pools = device_pools(parts)
    if shardings is not None:
        pools = shardings.place_pools(pools)
    engine = RoundEngine(
        build_fl_round(model.loss, strategy, run, codec=codec),
        vision_batcher(train.x, train.y, pools, args.local_steps, args.batch),
        seed=args.seed, shardings=shardings)
    state = engine.init_state(params, args.clients, strategy,
                              staleness_max=run.staleness_max)
    mgr = _ckpt_manager(args)
    meta_extra = {"model": args.model, "dataset": args.dataset,
                  "compressor": args.compressor, "transport": "inproc"}
    r0 = 0
    if args.resume:
        # the freshly-built state is the structure template: a checkpoint
        # of a different model/faults/staleness config fails typed here
        state, _, meta = load_fl_checkpoint(mgr, state)
        _check_resume_config(meta, run)
        if shardings is not None:
            state = shardings.place_state(state)
        r0 = int(meta["round"])
        print(f"resuming from {mgr.path(r0)} at round {r0}")

    @jax.jit
    def eval_acc(p):
        return accuracy(model.apply(p, jnp.asarray(test.x)), jnp.asarray(test.y))

    _write_run_config(args.out, run)
    t0 = time.time()
    profiler = _make_profiler(args, r0)
    if profiler is not None:
        profiler.maybe_start(r0)
    with open(os.path.join(args.out, "metrics.jsonl"),
              "a" if args.resume else "w") as log:
        def on_eval(st, m, r):
            if profiler is not None:
                profiler.after_round(r0 + r - 1)
            rec = {"round": r0 + r, "loss": float(m.loss[-1]),
                   "acc": float(eval_acc(st.params)),
                   "cos": float(np.mean(m.cosine[-1])),
                   "payload_floats": float(m.payload_floats[-1]),
                   "elapsed_s": time.time() - t0}
            print(json.dumps(rec))
            log.write(json.dumps(rec) + "\n")
            log.flush()

        def ckpt_fn(st, rnd):
            save_fl_checkpoint(mgr, rnd, st, run=run, extra=meta_extra)

        try:
            state, _ = engine.run(state, args.rounds - r0,
                                  eval_every=args.eval_every, eval_fn=on_eval,
                                  ckpt_every=args.ckpt_every,
                                  ckpt_fn=ckpt_fn if args.ckpt_every else None)
        finally:
            if profiler is not None:
                profiler.close()
    _dump_obs(args.out)
    if args.ckpt_every and mgr.latest() != args.rounds:
        save_fl_checkpoint(mgr, args.rounds, state, run=run, extra=meta_extra)
    save_checkpoint(os.path.join(args.out, "final"), state.params,
                    meta={"model": args.model, "dataset": args.dataset,
                          "compressor": args.compressor, "rounds": args.rounds})
    print(f"checkpoint -> {args.out}/final")


def train_lm_smoke(args):
    if getattr(args, "transport", "inproc") == "socket":
        raise ValueError(
            "--transport socket drives vision runs only: the worker rebuilds "
            "the client computation from the vision SETUP blob "
            "(repro.launch.worker); the LM smoke path is in-process")
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    d = flat.tree_size(params)
    comp = CompressorConfig(kind=args.compressor if args.compressor != "fedavg"
                            else "identity",
                            error_feedback=args.compressor != "fedavg",
                            syn_steps=10, syn_lr=0.1, syn_seq=8)
    strategy = make_strategy(comp, loss_fn=syn_loss_fn(model),
                             syn_spec=syn_spec_for(cfg, comp),
                             local_lr=args.lr)
    mode, mesh, shardings = make_fanout(args)
    run = RunConfig.from_flags(args, compressor=comp, client_parallel=mode,
                               mesh=mesh)
    codec = strategy.wire_codec(params, policy=run.wire_policy) \
        if run.wire == "codec" else None

    S = 64
    data = make_token_dataset(jax.random.PRNGKey(args.seed), 2048, S,
                              cfg.vocab_size)
    extras = {}
    if isinstance(model, EncDec):
        extras["frames"] = (cfg.num_mm_tokens, cfg.d_model)
    elif cfg.num_mm_tokens:
        extras["prefix_embeds"] = (cfg.num_mm_tokens, cfg.d_model)
    engine = RoundEngine(
        build_fl_round(model.loss, strategy, run, codec=codec),
        token_batcher(data, args.clients, args.local_steps, args.batch,
                      extras=extras),
        seed=args.seed, shardings=shardings)
    state = engine.init_state(params, args.clients, strategy,
                              staleness_max=run.staleness_max)
    engine.run(state, args.rounds, eval_every=args.eval_every,
               eval_fn=lambda st, m, r: print(json.dumps(
                   {"round": r, "loss": float(m.loss[-1]),
                    "cos": float(np.mean(m.cosine[-1])), "params": d})))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mlp",
                    choices=["mlp", "mnistnet", "convnet", "resnet", "regnet"])
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced LM-family FL run (requires --arch)")
    ap.add_argument("--compressor", default="threesfc",
                    choices=["fedavg", "dgc", "signsgd", "stc", "threesfc"])
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--local-steps", type=int, default=5, dest="local_steps")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--train-size", type=int, default=4000, dest="train_size")
    ap.add_argument("--eval-every", type=int, default=10, dest="eval_every")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="experiments/train_run")
    ap.add_argument("--client-parallel", default="auto", dest="client_parallel",
                    choices=["auto", "vmap", "shard_map"],
                    help="client fan-out: sharded over the host mesh "
                         "(shard_map) or single-program vmap")
    ap.add_argument("--wire", default="float", choices=["float", "codec"],
                    help="what crosses the client/server boundary: float "
                         "trees (accounted bytes) or the repro.comm codec's "
                         "framed uint8 buffers (measured bytes)")
    # fault model (repro.fl.faults): all default to the zero-fault config,
    # which compiles the exact unfaulted round
    ap.add_argument("--participation-rate", type=float, default=1.0,
                    dest="participation_rate",
                    help="fraction of clients scheduled each round")
    ap.add_argument("--drop-rate", type=float, default=0.0, dest="drop_rate",
                    help="probability a participating client's payload is "
                         "lost mid-round (EF banks the whole update)")
    ap.add_argument("--straggler-rate", type=float, default=0.0,
                    dest="straggler_rate",
                    help="probability a delivered payload arrives 1..k "
                         "rounds late (requires --staleness-max >= 1)")
    ap.add_argument("--staleness-max", type=int, default=0,
                    dest="staleness_max",
                    help="staleness bound k: late payloads are applied at "
                         "t+delay with weight 1/(1+delay); 0 disables the "
                         "ring buffer")
    ap.add_argument("--fault-seed", type=int, default=0, dest="fault_seed",
                    help="seed of the fault stream (schedules are a pure "
                         "function of (fault_seed, round))")
    # transport (repro.comm.transport): socket mode spawns N worker
    # processes and runs framed rounds over real sockets
    ap.add_argument("--transport", default="inproc",
                    choices=["inproc", "socket"],
                    help="how rounds move: one in-process program (the "
                         "engine's scanned loop) or a SocketServer + N "
                         "worker processes (requires --wire codec)")
    ap.add_argument("--round-deadline-s", type=float, default=30.0,
                    dest="round_deadline_s",
                    help="hard bound on one round's collect phase")
    ap.add_argument("--recv-timeout-s", type=float, default=2.0,
                    dest="recv_timeout_s",
                    help="per-client receive window before the first RESEND")
    ap.add_argument("--recv-backoff", type=float, default=2.0,
                    dest="recv_backoff",
                    help="exponential backoff factor per retry attempt")
    ap.add_argument("--transport-retries", type=int, default=2,
                    dest="transport_retries",
                    help="RESENDs before a client counts as dropped")
    ap.add_argument("--heartbeat-s", type=float, default=0.5,
                    dest="heartbeat_s", help="worker liveness tick period")
    ap.add_argument("--liveness-timeout-s", type=float, default=5.0,
                    dest="liveness_timeout_s",
                    help="silence window after which a worker counts as dead")
    # recovery (repro.checkpoint): periodic full-state recovery points +
    # bitwise resume — both transports
    ap.add_argument("--ckpt-every", type=int, default=0, dest="ckpt_every",
                    help="write a durable full-state recovery point every N "
                         "rounds (params + EF + staleness buffer + round "
                         "counter + byte ledger) under <out>/ckpt; 0 writes "
                         "only the final params checkpoint")
    ap.add_argument("--resume", default=None, metavar="CKPT_ROOT",
                    help="resume from the latest recovery point under this "
                         "checkpoint root (e.g. <out>/ckpt); the run must "
                         "use the same configuration, replays the remaining "
                         "rounds bitwise, and appends to the existing "
                         "metrics JSONL")
    # observability (repro.obs): host-side span tracing, metrics endpoints,
    # device-timeline profiling
    ap.add_argument("--trace", action="store_true",
                    help="record host-side spans (server round phases, "
                         "transport framing, checkpoint I/O; socket workers "
                         "piggyback theirs over MSG_METRIC) and write "
                         "<out>/trace.jsonl + trace.chrome.json")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace into DIR "
                         "(view with TensorBoard or Perfetto)")
    ap.add_argument("--profile-window", default=None, metavar="A:B",
                    dest="profile_window",
                    help="restrict --profile to absolute rounds [A, B); "
                         "exact on --transport socket, snaps to eval-block "
                         "boundaries in-process")
    ap.add_argument("--metrics-port", type=int, default=None,
                    dest="metrics_port",
                    help="serve /healthz and /metrics (the obs.meters "
                         "snapshot) on this port for the run's duration "
                         "(0 picks a free port)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.trace:
        configure_tracer(True, proc="server")
    http = None
    if args.metrics_port is not None:
        from repro.obs.http import ObsHTTPServer
        http = ObsHTTPServer(port=args.metrics_port)
        print(f"metrics -> {http.url}/metrics")
    try:
        if args.arch and args.smoke:
            train_lm_smoke(args)
        else:
            train_vision(args)
    finally:
        if http is not None:
            http.stop()


if __name__ == "__main__":
    main()
