"""Client worker process: the other end of the socket transport.

``python -m repro.launch.worker --connect host:port --client-id i`` dials
the ``repro.comm.transport.SocketServer`` at ``host:port``, introduces
itself (HELLO), rebuilds the *entire* client-side computation from the
server's SETUP blob — model, synthetic dataset, Dirichlet partition,
strategy, codec, PRNG streams — and then serves rounds until STOP.

Determinism contract (the socket-vs-oracle bitwise gate rests on this)
----------------------------------------------------------------------
The worker recomputes exactly what the in-process oracle's client ``i``
computes, from nothing but the SETUP blob and its client id:

* model params come from ``model.init(PRNGKey(run.fl.seed))`` — but the
  round's *global* params are always the server's ROUND broadcast
  (identity-codec framed, lossless), so server and workers agree bit for
  bit even after faulted rounds;
* the batch for (round r, client i) follows the engine PRNG contract
  (``repro.fl.engine``): ``pos = randint(fold_in(fold_in(data_key, r), i),
  (K, B), 0, size_i)`` over the device-resident pools — the gather indices
  are integer math, identical at any fan-out width;
* the compressor key is ``split(fold_in(round_key, r), N)[i]`` — the same
  element of the same split the oracle's vmap consumes;
* the client step runs as a width-1 ``jax.vmap`` over the SAME
  ``client_step`` body as ``fl.round``'s fan-out (local_train ->
  ``strategy.wire_step``), with the batch gather inside the same jit.

EF commit protocol
------------------
The worker holds its EF residual locally and *defers* the commit until the
server's ACK for the round arrives: ACK(delivered=1) commits the
strategy's post-compression residual (``e' = u - r``), ACK(delivered=0)
banks the whole accumulated update (``e' = u = g + e``) — byte-for-byte
the fault algebra of ``repro.fl.faults``, which is what makes residual-
mass conservation hold over a real wire. A round that is still un-acked
when the next ROUND arrives is committed as undelivered (conservative: the
server has necessarily moved on without this client's frame). MSG_EF_REQ
dumps the committed residual as a flat f32 leaf stream — the observability
hook the conservation gates read.

Every commit is also *pushed* to the server (MSG_EF_PUSH, tagged with the
committed round): the server's EF bank then always holds this client's
last-committed residual, which is the only state the worker process owns.
That bank is the recovery source for elastic membership — when this
process is killed and a replacement connects, the server re-syncs it with
MSG_EF_SYNC and the residual continues bitwise from where it died
(``VisionClientCompute.install_ef``).

A non-participating round (ROUND flags bit 0 clear) is sat out entirely:
no compute, no frame, EF frozen — the ``participate=False`` branch.

Induced straggle: the SETUP blob may carry ``straggle[cid] = seconds``;
the worker then sleeps that long each round between computing and sending
its frame (the heartbeat thread keeps ticking, so a straggler is *alive*,
just late — the server's deadline, not the straggler's nap, bounds the
round).
"""
from __future__ import annotations

import argparse
import json
import struct
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.transport import (FLAG_PARTICIPATE, MSG_ACK, MSG_EF_DUMP,
                                  MSG_EF_PUSH, MSG_EF_REQ, MSG_EF_SYNC,
                                  MSG_FRAME, MSG_METRIC, MSG_RESEND,
                                  MSG_ROUND, MSG_SETUP, MSG_STOP, ServerLink)
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import configure_tracer, get_logger, get_tracer

PyTree = Any

# pre-SETUP heartbeat period: the worker must look alive from the moment it
# connects (jit compilation of the client step can take seconds), before it
# knows the configured heartbeat_s
_BOOT_HEARTBEAT_S = 0.2


def vision_setup(run, *, model: str, spec, train_size: int,
                 straggle: Optional[Dict[int, float]] = None,
                 trace: bool = False) -> Dict:
    """The SETUP blob for a vision run — everything a worker needs to
    rebuild the client computation, JSON-serializable. One construction
    shared by the training CLI, the transport bench and the tests so the
    blob's schema cannot drift between drivers. ``trace=True`` turns on
    the worker-side span recorder (spans ride back on MSG_METRIC)."""
    return {
        "kind": "vision",
        "model": model,
        "spec": [spec.name, list(spec.input_shape), int(spec.num_classes)],
        "train_size": int(train_size),
        "run": run.to_json(),
        "straggle": {str(k): float(v) for k, v in (straggle or {}).items()},
        "trace": bool(trace),
    }


class VisionClientCompute:
    """Client ``i``'s half of the vision round, rebuilt from a SETUP blob.

    Holds the local EF residual (leading axis 1, mirroring the oracle's
    per-client row) plus the deferred-commit slot the ACK protocol fills.
    """

    def __init__(self, setup: Dict, client_id: int):
        from repro.configs.run import RunConfig
        from repro.configs.base import CompressorConfig
        from repro.comm.codec import make_codec
        from repro.core.strategy import make_strategy
        from repro.data.partition import dirichlet_partition
        from repro.data.synthetic import make_class_image_dataset
        from repro.fl.client import local_train
        from repro.fl.engine import device_pools
        from repro.models.build import vision_syn_spec
        from repro.models.cnn import VisionSpec, make_paper_model

        run = RunConfig.from_json(setup["run"])
        cfg = run.fl
        spec = VisionSpec(setup["spec"][0], tuple(setup["spec"][1]),
                          int(setup["spec"][2]))
        model = make_paper_model(setup["model"], spec)
        params = model.init(jax.random.PRNGKey(cfg.seed))
        comp = cfg.compressor
        strategy = make_strategy(comp, loss_fn=model.syn_loss,
                                 syn_spec=vision_syn_spec(spec, comp),
                                 local_lr=cfg.local_lr)
        codec = strategy.wire_codec(params, policy=run.wire_policy)

        key = jax.random.PRNGKey(cfg.seed)
        train = make_class_image_dataset(key, setup["train_size"],
                                         spec.input_shape, spec.num_classes)
        parts = dirichlet_partition(train.y, cfg.num_clients,
                                    alpha=cfg.dirichlet_alpha, seed=cfg.seed,
                                    min_per_client=cfg.local_batch)
        pools = device_pools(parts)
        x = jnp.asarray(train.x)
        y = jnp.asarray(train.y)

        base = jax.random.PRNGKey(cfg.seed)
        data_key = jax.random.fold_in(base, 0)    # engine _DATA_FOLD
        round_key = jax.random.fold_in(base, 1)   # engine _ROUND_FOLD

        self.run = run
        self.codec = codec
        i = int(client_id)
        N = cfg.num_clients
        K, B = cfg.local_steps, cfg.local_batch
        loss_fn = model.loss

        # width-1 row of the oracle's per-client state
        self.ef = jax.tree_util.tree_map(
            lambda e: e[None], strategy.init_ef_state(params))
        self._pending: Optional[Dict] = None

        # the downlink params frame is identity-coded (lossless f32)
        self._down = make_codec(
            CompressorConfig(kind="identity", error_feedback=False), params)
        self._dec = jax.jit(
            lambda buf: self._down.recon_tree(self._down.decode(buf), params))

        def client_step(global_params, ef_i, batches_i, key_i, cid, rnd):
            # the oracle's client body verbatim (fl.round client phase)
            g, loss = local_train(loss_fn, global_params, batches_i,
                                  cfg.local_lr, num_micro=run.num_micro)
            msg, ef_new, _ = strategy.wire_step(
                key_i, g, ef_i, global_params, codec=codec,
                round_idx=rnd, client_idx=cid)
            ef_drop = strategy._accumulate(g, ef_i) \
                if comp.error_feedback else ef_i
            return msg, ef_new, ef_drop, loss

        def step(p, ef, r):
            # batch gather inside the jit, per the engine PRNG contract
            kr = jax.random.fold_in(data_key, r)
            k = jax.random.fold_in(kr, i)
            pos = jax.random.randint(k, (K, B), 0, pools.size[i])
            idx = pools.index[i, pos]
            batches = {"x": x[idx][None], "y": y[idx][None]}
            keys = jax.random.split(
                jax.random.fold_in(round_key, r), N)[i:i + 1]
            cids = jnp.arange(N, dtype=jnp.uint32)[i:i + 1]
            return jax.vmap(client_step, in_axes=(None, 0, 0, 0, 0, None))(
                p, ef, batches, keys, cids, r)

        self._step = jax.jit(step)

    def decode_params(self, frame_bytes: bytes) -> PyTree:
        return self._dec(jnp.asarray(np.frombuffer(frame_bytes, np.uint8)))

    def compute(self, params: PyTree, round_idx: int):
        """Run client ``i``'s round ``round_idx``; stages the two EF
        branches for the deferred ACK commit. Returns (frame bytes, loss)."""
        msg, ef_new, ef_drop, loss = self._step(
            params, self.ef, jnp.int32(round_idx))
        self._pending = {"round": round_idx, "ef_new": ef_new,
                         "ef_drop": ef_drop}
        return np.asarray(msg[0], np.uint8).tobytes(), float(loss[0])

    def pending_round(self) -> Optional[int]:
        return None if self._pending is None else self._pending["round"]

    def commit(self, delivered: bool) -> None:
        """Resolve the staged round: the strategy residual on delivery, the
        whole banked update on drop (fault algebra of ``repro.fl.faults``),
        cast back to the carried EF dtype exactly like the oracle's
        ``finish``."""
        if self._pending is None:
            return
        src = self._pending["ef_new" if delivered else "ef_drop"]
        self.ef = jax.tree_util.tree_map(
            lambda n, o: n.astype(o.dtype), src, self.ef)
        self._pending = None

    def ef_bytes(self) -> bytes:
        """Committed EF residual as the flat f32 leaf stream MSG_EF_DUMP
        and MSG_EF_PUSH carry (tree_leaves order, matching any host-side
        flattening of the oracle's EF row)."""
        return np.concatenate(
            [np.asarray(l[0], np.float32).ravel()
             for l in jax.tree_util.tree_leaves(self.ef)]).tobytes()

    def install_ef(self, stream: bytes) -> None:
        """Install a server-synced residual (flat f32 leaf stream, the
        MSG_EF_SYNC body) — the rejoin path: a restarted worker process
        lost its residual with its life, and the server's EF bank is the
        recovery source. Clears any staged round (it predates the sync)."""
        flat = np.frombuffer(stream, np.float32)
        leaves, treedef = jax.tree_util.tree_flatten(self.ef)
        total = sum(int(l.size) for l in leaves)
        if flat.size != total:
            raise ValueError(
                f"EF sync stream carries {flat.size} floats, this client's "
                f"residual has {total}")
        out, off = [], 0
        for l in leaves:
            n = int(l.size)
            out.append(jnp.asarray(flat[off:off + n].reshape(l.shape),
                                   dtype=l.dtype))
            off += n
        self.ef = jax.tree_util.tree_unflatten(treedef, out)
        self._pending = None


def build_compute(setup: Dict, client_id: int):
    if setup.get("kind") != "vision":
        raise ValueError(
            f"worker only knows how to rebuild 'vision' runs, got "
            f"{setup.get('kind')!r}")
    return VisionClientCompute(setup, client_id)


def replay_live_run(setup: Dict, params: PyTree, delivered: np.ndarray,
                    participate: Optional[np.ndarray] = None):
    """A live socket run's own computation, in one process: the bitwise
    oracle for ``LiveRoundLoop`` over ``setup``'s workers.

    Per round, each participating client runs its worker step
    (``build_compute``, width 1, as its worker runs it) and commits by its
    frame's fate; the delivered frames then go through the live loop's
    server step (``fl.engine.live_server_step``). ``delivered`` and
    ``participate`` (default: everyone) are (rounds, N) bool masks.
    Returns ``(params, [flat f32 EF per client])``."""
    from repro.configs.run import RunConfig
    from repro.fl.engine import live_server_step

    rounds, n = delivered.shape
    participate = np.ones_like(delivered) if participate is None \
        else participate
    clients = [build_compute(setup, i) for i in range(n)]
    codec = clients[0].codec
    step = live_server_step(codec, n,
                            RunConfig.from_json(setup["run"]).fl.server_lr)
    for r in range(rounds):
        bufs = np.zeros((n, codec.nbytes), np.uint8)
        for i, c in enumerate(clients):
            if participate[r, i]:
                frame = np.frombuffer(c.compute(params, r)[0], np.uint8)
                if delivered[r, i]:
                    bufs[i] = frame
                c.commit(bool(delivered[r, i]))
        params = step(params, jnp.asarray(bufs), jnp.asarray(delivered[r]))
    return jax.device_get(params), [
        np.frombuffer(c.ef_bytes(), np.float32) for c in clients]


def _serve(link: ServerLink, compute, client_id: int,
           straggle_s: float, log=None) -> None:
    """The worker's message loop: ROUND -> compute/frame/metric, RESEND ->
    re-send the cached frame, ACK -> commit the EF branch, EF_REQ -> dump,
    STOP -> exit. Single-threaded on purpose (besides the heartbeat): the
    protocol is strictly ordered per connection, so there is nothing to
    race.

    When the process tracer is enabled (SETUP ``trace``), the round's
    decode/compute/straggle spans are drained and piggybacked on the
    MSG_METRIC body — they reach the server in-band, on this worker's own
    clock, for offset-shifted merge into the server trace."""
    if log is None:
        log = get_logger("worker", client=client_id)
    tracer = get_tracer()
    last_frame: Optional[bytes] = None
    last_round = -1

    def commit_and_push(delivered: bool) -> None:
        # resolve the staged round, then push the committed residual so the
        # server's EF bank tracks this client's last commit (the rejoin /
        # resume recovery source)
        staged = compute.pending_round()
        if staged is None:
            return
        compute.commit(delivered=delivered)
        stream = compute.ef_bytes()
        link.send(MSG_EF_PUSH, struct.pack("<I", staged) + stream)
        tracer.event("ef_push", round=staged, bytes=len(stream),
                     delivered=delivered)

    while True:
        mtype, body = link.recv()
        if mtype == MSG_STOP:
            log.info("stop received, exiting")
            return
        if mtype == MSG_ROUND:
            rnd, flags = struct.unpack_from("<IB", body)
            rlog = log.bind(round=rnd)
            # a still-staged previous round means the server moved on
            # without acking us — it necessarily gave up on our frame
            commit_and_push(delivered=False)
            if not flags & FLAG_PARTICIPATE:
                last_frame, last_round = None, rnd
                rlog.debug("sitting round out")
                continue                     # sit the round out; EF frozen
            with tracer.span("worker.decode", round=rnd, phase="decode",
                             bytes=len(body) - 5):
                params = compute.decode_params(body[5:])
            with tracer.span("worker.compute", round=rnd, phase="compute"):
                frame, loss = compute.compute(params, rnd)
            if straggle_s > 0:
                with tracer.span("worker.straggle", round=rnd,
                                 phase="straggle", sleep_s=straggle_s):
                    time.sleep(straggle_s)   # alive (heartbeats), just late
            payload = struct.pack("<If", rnd, loss)
            spans = tracer.drain()
            if spans:
                payload += json.dumps(spans).encode("utf-8")
            link.send(MSG_METRIC, payload)
            with tracer.span("worker.send", round=rnd, phase="send",
                             bytes=len(frame)):
                link.send(MSG_FRAME, frame)
            last_frame, last_round = frame, rnd
            rlog.debug("served: loss=%.4f frame=%dB", loss, len(frame))
        elif mtype == MSG_RESEND:
            (rnd,) = struct.unpack("<I", body)
            if last_frame is not None and rnd == last_round:
                tracer.event("worker.resend", round=rnd,
                             bytes=len(last_frame))
                link.send(MSG_FRAME, last_frame)
                log.bind(round=rnd).info("re-sent frame (%dB)",
                                         len(last_frame))
        elif mtype == MSG_ACK:
            rnd, delivered = struct.unpack("<IB", body)
            if compute.pending_round() == rnd:
                commit_and_push(delivered=bool(delivered))
        elif mtype == MSG_EF_REQ:
            link.send(MSG_EF_DUMP, compute.ef_bytes())
        elif mtype == MSG_EF_SYNC:
            # server-held residual (rejoin/resume): install and continue
            # from exactly where the previous incarnation committed
            compute.install_ef(body[4:])
            tracer.event("ef_sync", bytes=len(body) - 4)
            log.info("EF residual re-synced from server (%dB)",
                     len(body) - 4)
        # unknown/duplicate control messages are ignored: the server owns
        # the protocol version, the worker just serves what it understands


def run_worker(address, client_id: int) -> None:
    log = get_logger("worker", client=client_id)
    link = ServerLink.connect(tuple(address), client_id)
    log.info("connected to %s:%s", *tuple(address))
    # look alive immediately — SETUP parsing and jit compilation happen
    # before the configured heartbeat is known
    link.start_heartbeat(_BOOT_HEARTBEAT_S)
    try:
        setup = None
        while setup is None:
            mtype, body = link.recv()
            if mtype == MSG_STOP:
                return
            if mtype == MSG_SETUP:
                setup = json.loads(body.decode("utf-8"))
        if setup.get("trace"):
            configure_tracer(True, proc=f"client-{client_id}")
        t0 = time.monotonic()
        compute = build_compute(setup, client_id)
        log.info("computation rebuilt in %.1fs", time.monotonic() - t0)
        hb = compute.run.heartbeat_s
        if hb < _BOOT_HEARTBEAT_S:
            link.start_heartbeat(hb)         # beat faster than configured
        straggle_s = float(setup.get("straggle", {}).get(str(client_id), 0.0))
        if straggle_s > 0:
            log.info("induced straggle: %.2fs per round", straggle_s)
        _serve(link, compute, client_id, straggle_s, log=log)
    except (ConnectionError, OSError):
        log.info("server connection lost, exiting")
    finally:
        link.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--connect", required=True, metavar="HOST:PORT")
    ap.add_argument("--client-id", type=int, required=True, dest="client_id")
    args = ap.parse_args(argv)
    enable_compile_cache()
    host, port = args.connect.rsplit(":", 1)
    run_worker((host, int(port)), args.client_id)


if __name__ == "__main__":
    main()
