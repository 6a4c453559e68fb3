"""Socket transport: framing primitives, deadline/retry/liveness semantics
against fake raw-socket workers, and a seeded end-to-end multi-process round
gated bitwise against the in-process oracle.

Everything that opens real sockets or subprocesses carries
``@pytest.mark.transport``: ``conftest`` arms those tests with a hard
SIGALRM ceiling, so "the server never hangs on a dead peer" is itself
enforced — a hang fails the test, it cannot stall the suite.
"""
import socket
import struct
import threading
import time

import numpy as np
import pytest

from repro.comm.frame import FrameSpec, encode_header
from repro.comm.transport import (MAX_MSG, MSG_FRAME, MSG_HEARTBEAT,
                                  MSG_HELLO, MSG_RESEND, MSG_ROUND,
                                  ProtocolError, SocketServer, recv_msg,
                                  send_msg)
from repro.fl.engine import RetryPolicy

_SPEC = FrameSpec("identity", "fp32", (8,))


def _codec_frame(round_idx=0, client_idx=0) -> np.ndarray:
    head = np.asarray(encode_header(_SPEC, round_idx, client_idx))
    return np.concatenate([head, np.arange(8, dtype=np.uint8)])


# ---------------------------------------------------------------------------
# framing primitives (socketpair: no listener, cannot hang)
# ---------------------------------------------------------------------------


def test_msg_roundtrip_including_zero_length_body():
    a, b = socket.socketpair()
    try:
        # zero-length frame: a heartbeat is 5 bytes of header, 0 of body
        n = send_msg(a, MSG_HEARTBEAT)
        assert n == 5
        assert recv_msg(b) == (MSG_HEARTBEAT, b"")
        # ndarray bodies serialize as their raw bytes
        payload = np.arange(32, dtype=np.uint8)
        n = send_msg(a, MSG_FRAME, payload)
        assert n == 5 + 32
        mtype, body = recv_msg(b)
        assert mtype == MSG_FRAME
        np.testing.assert_array_equal(np.frombuffer(body, np.uint8), payload)
        # explicit zero-length data frame round-trips too
        send_msg(a, MSG_FRAME, b"")
        assert recv_msg(b) == (MSG_FRAME, b"")
    finally:
        a.close()
        b.close()


def test_partial_read_at_length_prefix_boundary_is_connection_error():
    # peer dies mid-prefix: 3 of the 5 header bytes, then EOF
    a, b = socket.socketpair()
    a.sendall(struct.pack("<IB", 100, MSG_FRAME)[:3])
    a.close()
    with pytest.raises(ConnectionError):
        recv_msg(b)
    b.close()
    # peer dies mid-body: full prefix promising 100 B, 10 B delivered
    a, b = socket.socketpair()
    a.sendall(struct.pack("<IB", 100, MSG_FRAME) + b"x" * 10)
    a.close()
    with pytest.raises(ConnectionError):
        recv_msg(b)
    b.close()


def test_insane_length_prefix_is_protocol_error():
    a, b = socket.socketpair()
    a.sendall(struct.pack("<IB", MAX_MSG + 1, MSG_FRAME))
    with pytest.raises(ProtocolError):
        recv_msg(b)
    a.close()
    b.close()


def test_retry_policy_backoff_schedule():
    pol = RetryPolicy(max_retries=3, recv_timeout_s=1.0, recv_backoff=2.0,
                      max_timeout_s=5.0)
    # exponential per attempt, capped at max_timeout_s
    assert [pol.timeout(a) for a in range(4)] == [1.0, 2.0, 4.0, 5.0]
    flat = RetryPolicy(max_retries=2, recv_timeout_s=0.5, recv_backoff=1.0,
                       max_timeout_s=10.0)
    assert [flat.timeout(a) for a in range(3)] == [0.5, 0.5, 0.5]


# ---------------------------------------------------------------------------
# server semantics against fake raw-socket workers
# ---------------------------------------------------------------------------


def _fake_worker(server, cid):
    sock = socket.create_connection(server.address, timeout=10)
    send_msg(sock, MSG_HELLO, struct.pack("<I", cid))
    return sock


@pytest.mark.transport
def test_corrupt_frames_exhaust_retries_then_dropped():
    """A worker that answers every (re)send with garbage burns exactly
    ``max_retries`` RESENDs, is marked undelivered, and every garbage
    frame is still billed — the bytes crossed the wire."""
    server = SocketServer(1, heartbeat_s=0.5, liveness_timeout_s=60.0)
    sock = _fake_worker(server, 0)
    stop = threading.Event()
    resends = []

    def worker():
        while not stop.is_set():
            try:
                mtype, body = recv_msg(sock)
            except (ConnectionError, OSError):
                return
            if mtype == MSG_RESEND:
                resends.append(struct.unpack("<I", body)[0])
            if mtype in (MSG_ROUND, MSG_RESEND):
                send_msg(sock, MSG_FRAME, b"\x00" * 64)   # never parses

    t = threading.Thread(target=worker, daemon=True)
    try:
        server.wait_ready(10)
        t.start()
        r = server.begin_round()
        server.broadcast_round(r, np.zeros((16,), np.uint8))
        pol = RetryPolicy(max_retries=2, recv_timeout_s=0.5,
                          recv_backoff=1.0, max_timeout_s=1.0)
        t0 = time.monotonic()
        rep = server.collect(r, [True], policy=pol, deadline_s=20.0)
        wall = time.monotonic() - t0
        assert not rep.delivered[0] and rep.frames[0] is None
        assert rep.retries == 2 and resends == [r, r]
        assert wall < 10.0                     # gave up, did not sit on the
        assert server.uplink.per_round[-1] >= 64  # deadline; garbage billed
    finally:
        stop.set()
        server.stop()
        sock.close()


@pytest.mark.transport
def test_worker_killed_mid_frame_maps_to_dropped_never_hangs():
    """A peer that dies halfway through a frame (length prefix promised
    4096 B, 100 arrived) becomes delivered=False within the dead-sweep,
    NOT a hang until the deadline."""
    server = SocketServer(1, heartbeat_s=0.5, liveness_timeout_s=60.0)
    sock = _fake_worker(server, 0)

    def worker():
        try:
            mtype, _ = recv_msg(sock)
            assert mtype == MSG_ROUND
            sock.sendall(struct.pack("<IB", 4096, MSG_FRAME) + b"y" * 100)
            sock.close()                       # SIGKILL from the wire's view
        except (ConnectionError, OSError):
            pass

    t = threading.Thread(target=worker, daemon=True)
    try:
        server.wait_ready(10)
        t.start()
        r = server.begin_round()
        server.broadcast_round(r, np.zeros((16,), np.uint8))
        pol = RetryPolicy(max_retries=5, recv_timeout_s=10.0,
                          max_timeout_s=10.0)
        t0 = time.monotonic()
        rep = server.collect(r, [True], policy=pol, deadline_s=60.0)
        wall = time.monotonic() - t0
        assert not rep.delivered[0]
        assert wall < 10.0                     # death sentinel, not deadline
        assert server.live_workers() == []
    finally:
        server.stop()


@pytest.mark.transport
def test_stale_frame_is_billed_then_discarded():
    """A frame carrying last round's header is billed (the bytes moved)
    but never counted delivered; the retry timer then recovers the real
    frame."""
    server = SocketServer(1, heartbeat_s=0.5, liveness_timeout_s=60.0)
    sock = _fake_worker(server, 0)
    stale = _codec_frame(round_idx=0, client_idx=0)
    sent = {"n": 0}

    def worker():
        while True:
            try:
                mtype, _ = recv_msg(sock)
            except (ConnectionError, OSError):
                return
            if mtype == MSG_ROUND:
                sent["n"] += 1
                send_msg(sock, MSG_FRAME, stale)          # wrong round
            elif mtype == MSG_RESEND:
                sent["n"] += 1
                send_msg(sock, MSG_FRAME, _codec_frame(1, 0))  # the real one

    t = threading.Thread(target=worker, daemon=True)
    try:
        server.wait_ready(10)
        t.start()
        assert server.begin_round() == 0      # round 0 exists but is skipped
        r = server.begin_round()
        assert r == 1
        server.broadcast_round(r, np.zeros((16,), np.uint8))
        pol = RetryPolicy(max_retries=2, recv_timeout_s=0.5,
                          recv_backoff=1.0, max_timeout_s=1.0)
        rep = server.collect(r, [True], policy=pol, deadline_s=20.0)
        assert rep.delivered[0] and rep.retries == 1 and sent["n"] == 2
        hdr_bytes = server.uplink.per_round[-1]
        assert hdr_bytes == 2 * stale.nbytes  # stale + good, both billed
    finally:
        server.stop()
        sock.close()


# ---------------------------------------------------------------------------
# seeded end-to-end: real worker subprocesses vs the in-process oracle
# ---------------------------------------------------------------------------


@pytest.mark.transport(timeout=300)
def test_live_socket_round_bitwise_equals_inprocess_oracle():
    """Two real worker subprocesses drive a round over the socket; params,
    per-client EF, and per-round billing must be bitwise what the
    width-matched in-process oracle computes from the same seed: each
    client's step at width 1 (as its worker runs it) and the live loop's
    server step. The vmapped in-process engine, which runs both clients
    at width 2, agrees to two roundings of the largest weight (XLA lowers
    the client step per vmap width; observed 9.3e-10 on 1 of 48,203
    params)."""
    import jax

    from repro.comm.transport import spawn_local_workers
    from repro.configs.base import CompressorConfig, FLConfig
    from repro.configs.run import RunConfig
    from repro.core.strategy import make_strategy
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.engine import (LiveRoundLoop, RoundEngine, device_pools,
                                 vision_batcher)
    from repro.fl.faults import null_schedule
    from repro.fl.round import build_fl_round
    from repro.launch.worker import replay_live_run, vision_setup
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import VisionSpec, make_paper_model

    N, R, train_n = 2, 2, 96
    spec = VisionSpec("tiny", (6, 6, 1), 3)
    comp = CompressorConfig(kind="stc", keep_ratio=0.1)
    fl = FLConfig(num_clients=N, local_steps=2, local_lr=0.05,
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

    train = make_class_image_dataset(jax.random.PRNGKey(fl.seed), train_n,
                                     spec.input_shape, spec.num_classes)
    parts = dirichlet_partition(train.y, N, alpha=fl.dirichlet_alpha,
                                seed=fl.seed, min_per_client=fl.local_batch)
    pools = device_pools(parts)
    engine = RoundEngine(
        build_fl_round(model.loss, strategy, RunConfig(fl=fl, wire="codec"),
                       codec=codec,
                       fault_schedule_fn=lambda r, n: null_schedule(n)),
        vision_batcher(train.x, train.y, pools, fl.local_steps,
                       fl.local_batch),
        seed=fl.seed)
    state = engine.init_state(params, N, strategy)
    state, _ = engine.run_loop(state, R)
    vmap_params, vmap_ef = jax.device_get((state.params, state.ef))

    # width-matched oracle: the workers' own client computation in-process
    setup = vision_setup(run, model="mlp", spec=spec, train_size=train_n)
    oracle_params, oracle_efs = replay_live_run(
        setup, params, np.ones((R, N), bool))

    server = SocketServer(N, heartbeat_s=run.heartbeat_s,
                          liveness_timeout_s=run.liveness_timeout_s)
    procs = spawn_local_workers(server.address, range(N))
    try:
        server.wait_ready(60)
        server.send_setup(setup)
        loop = LiveRoundLoop(server, strategy, codec, run, params)
        # round 0 compiles inside the workers: generous window, no resends
        warm = RetryPolicy(max_retries=0, recv_timeout_s=240.0,
                           max_timeout_s=240.0)
        loop.run(1, deadline_s=240.0, policy=warm)
        live_params = jax.device_get(loop.run(R - 1))
        efs = [server.request_ef(i, timeout=30) for i in range(N)]
    finally:
        server.stop()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:
                p.kill()

    def ravel(t):
        return np.concatenate([np.asarray(l, np.float32).ravel()
                               for l in jax.tree_util.tree_leaves(t)])

    # against the vmapped engine: two roundings of the largest weight
    tol = 2 * float(np.spacing(np.max(np.abs(ravel(vmap_params)))))
    assert all(rec["delivered"].all() for rec in loop.history)
    np.testing.assert_array_equal(ravel(oracle_params), ravel(live_params))
    np.testing.assert_allclose(ravel(vmap_params), ravel(live_params),
                               rtol=0, atol=tol)
    for i in range(N):
        assert efs[i] is not None
        np.testing.assert_array_equal(efs[i], oracle_efs[i])
        ve = np.concatenate([np.asarray(l[i], np.float32).ravel()
                             for l in jax.tree_util.tree_leaves(vmap_ef)])
        np.testing.assert_allclose(efs[i], ve, rtol=0, atol=tol)
    # the settled round billed exactly the codec bytes — headers, ACKs and
    # heartbeats live in the overhead buckets, not the data-plane stats
    assert loop.history[1]["bytes_up"] == N * codec.nbytes
    assert server.overhead_up > 0 and server.overhead_down > 0
