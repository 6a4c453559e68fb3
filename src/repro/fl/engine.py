"""Device-resident multi-round FL engine: scanned rounds, on-device sampling,
donated EF state.

The seed drivers (``benchmarks/fl_harness.run_fl``, both ``launch/train.py``
paths) all ran the same Python loop: sample client batches on the host with
numpy, upload an ``(N, K, B, ...)`` tree every round, dispatch one jitted
round, then block on ≥2 device→host syncs (``float(m.loss)``,
``float(jnp.mean(m.cosine))``). This module replaces that loop with a single
device-resident program:

* the training set and the Dirichlet partition live on device
  (``device_pools`` pads the ragged per-client index lists to an ``(N, P)``
  pool matrix — padding is dead weight, never sampled, see the PRNG
  contract below);
* per-round batches are *gathered* inside the jitted computation
  (``vision_batcher`` / ``token_batcher``) — no host numpy, no per-round
  host→device transfer;
* ``RoundEngine`` wraps the round function in ``lax.scan`` over a whole
  eval block, so an L-round block costs ONE dispatch and ONE host sync
  (the stacked ``RoundMetrics`` fetch) instead of L dispatches + 2L syncs;
* the scan/jit donates the ``FLState`` argument, so the per-client N×d EF
  residual tree — the dominant HBM resident — is updated in place instead
  of being double-buffered across the dispatch boundary.

Sampling-gather PRNG contract
-----------------------------
The batch for (round r, client i) is fully determined by the engine seed::

    data_key = fold_in(PRNGKey(seed), 0)           # batch sampling stream
    round_key = fold_in(PRNGKey(seed), 1)          # compressor-key stream
    pos_i    = randint(fold_in(fold_in(data_key, r), i), (K, B), 0, size_i)
    batch_i  = gather(dataset, pools.index[i, pos_i])

``r`` is the *absolute* round counter carried in ``FLState.round`` — not the
position within a scan block. Folding on the absolute round (instead of
splitting a carried key) is what makes the stream independent of how rounds
are grouped into dispatches. The per-round compressor key is derived the
same way (``fold_in(round_key, r)``).

Why eval cadence = scan length
------------------------------
An eval is the one thing that genuinely needs the host: it reads
``state.params`` (or the caller formats/logs metrics), which forces a
device→host sync. So the scan should extend exactly to the next eval point
— any shorter wastes dispatches, any longer would compute past the params
the eval needs. ``RoundEngine.run`` therefore scans ``eval_every`` rounds
per dispatch (plus a final remainder block). By the PRNG contract above,
changing the eval cadence regroups the dispatches but does NOT change the
training trajectory — blocks [3] and [2, 1] produce bit-identical states
(tested in tests/test_engine.py::test_eval_cadence_invariance).

Donation safety: ``jit(..., donate_argnums=0)`` consumes the input state's
buffers — a donated ``FLState`` must never be touched after the dispatch.
``RoundEngine.init_state`` therefore deep-copies the params it is given
(the caller's model params survive the first donation), and every ``run*``
method returns the fresh state that replaces the consumed one.

Mesh placement contract
-----------------------
Pass ``shardings=make_fl_shardings(mesh)`` (see ``repro.fl.sharding``) to
run the engine on an explicit mesh. The contract, enforced end to end:

* ``init_state`` places the state before the first dispatch: params and the
  round counter replicated, the N×d EF residual tree sharded leading-axis
  over ``client_axes(mesh)`` — each device owns its clients' residuals.
* every scanned block is jitted with ``in_shardings``/``out_shardings`` set
  to that same ``FLState`` prefix tree, so (a) donation reuses the *sharded*
  buffers in place (the EF tree is never re-laid-out across a dispatch) and
  (b) the carried state can never silently gather to one device — the
  output sharding is pinned, not inferred.
* the per-round batch tree gathered by ``batch_fn`` is pinned to the client
  sharding inside the jit (``constrain_client_tree``) so GSPMD feeds each
  device exactly its clients' batches.
* block metrics are pinned replicated — they are O(N) scalars per round and
  the host fetch at the block boundary reads them without a device gather.

The round function must use the matching fan-out
(``make_fl_round(..., client_parallel='shard_map', mesh=mesh)``) for the
per-client region to stay collective-free; the vmap fan-out also runs
under these shardings (GSPMD partitions it) and is the bit-exactness
oracle (tests/test_shard_round.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.round import BATCH_SCOPE, FLState, RoundMetrics, fl_init
from repro.fl.server import client_mean, server_update
from repro.obs import get_registry, get_tracer

PyTree = Any
# batch_fn(data_key, round_idx) -> per-client stacked batch pytree (N, K, B, ...)
BatchFn = Callable[[jax.Array, jax.Array], PyTree]
RoundFn = Callable[[FLState, PyTree, jax.Array], Tuple[FLState, RoundMetrics]]

_DATA_FOLD = 0
_ROUND_FOLD = 1


class ClientPools(NamedTuple):
    """Padded on-device Dirichlet partition: ``index[i, :size[i]]`` are the
    dataset rows client ``i`` may sample; ``index[i, size[i]:]`` is padding
    (zeros) that the sampler never reads (positions are drawn < size[i])."""

    index: jax.Array                 # (N, P) int32
    size: jax.Array                  # (N,) int32


def device_pools(parts: Sequence[np.ndarray]) -> ClientPools:
    """Materialize a host-side partition (list of ragged index arrays, as
    produced by ``data.partition.dirichlet_partition``) as device pools.

    Zero-sample clients (an empty Dirichlet part — alpha small, N large)
    get ``size`` clamped to 1 over their all-zeros index row, i.e. they
    resample dataset row 0 every step: ``randint(maxval=0)`` is undefined
    (it silently returns garbage inside jit), so the clamp turns a
    degenerate part into a documented convention instead of corrupt
    sampling. Callers that want to exclude such clients outright should
    filter the partition before building pools."""
    cap = max(max(len(p) for p in parts), 1)
    index = np.zeros((len(parts), cap), np.int32)
    for i, p in enumerate(parts):
        index[i, : len(p)] = np.asarray(p, np.int32)
    size = np.array([max(len(p), 1) for p in parts], np.int32)
    return ClientPools(jnp.asarray(index), jnp.asarray(size))


def vision_batcher(train_x: np.ndarray, train_y: np.ndarray,
                   pools: ClientPools, local_steps: int,
                   local_batch: int) -> BatchFn:
    """Non-iid ``{"x", "y"}`` batches gathered from device-resident data.

    The batch contract: ``x`` is ``(N, K, B, *sample_shape)`` in the set's
    dtype, ``y`` is ``(N, K, B)``, and client ``i``'s values are
    ``train_x[pools.index[i, pos]]`` for the positions the PRNG contract
    (module docstring) draws.

    How the set sits on the device: once, here and outside any jit, it is
    stored as sample-contiguous rows ``(n, m)``, ``m = prod(sample_shape)``;
    the round gathers whole rows by the same indices and reshapes the
    gathered ``(N, K, B, m)`` to ``(N, K, B, *sample_shape)``, so the batch
    is the set's values bit for bit. Why, on a TPU, whose tiles span an
    array's two minor dimensions by (8, 128): stored as ``(n, 28, 28, 1)``
    the set has no layout that tiles well. Its default layout puts the
    sample index in the lanes (``{0,3,2,1:T(1,128)}``), each block relaid
    the whole set into one (32, 128)-padded bf16 tile per image, and the
    gather then cut the batch out of those tiles one image at a time. As
    rows, the block's one pass over the set writes rows of 784 values
    (``bf16[n,784]{1,0}``) and the gather moves whole rows. Rows padded to
    whole 128-lane tiles gather no faster and hold more memory.
    """
    sample_shape = tuple(train_x.shape[1:])
    # a free view of a host set, reshaped before its one upload
    rows = jnp.asarray(train_x.reshape(train_x.shape[0], -1))
    y = jnp.asarray(train_y)
    num_clients = pools.index.shape[0]

    def batch_fn(data_key: jax.Array, round_idx: jax.Array) -> PyTree:
        kr = jax.random.fold_in(data_key, round_idx)

        def per_client(i):
            k = jax.random.fold_in(kr, i)
            pos = jax.random.randint(k, (local_steps, local_batch), 0,
                                     pools.size[i])
            return pools.index[i, pos]

        idx = jax.vmap(per_client)(jnp.arange(num_clients))
        x = rows[idx].reshape(*idx.shape, *sample_shape)
        return {"x": x, "y": y[idx]}

    return batch_fn


def token_batcher(tokens: np.ndarray, num_clients: int, local_steps: int,
                  local_batch: int,
                  extras: Optional[Dict[str, Tuple[int, ...]]] = None) -> BatchFn:
    """IID ``{"tokens"}`` batches (the LM-smoke protocol) plus optional
    all-zero multimodal stubs: ``extras`` maps batch key -> trailing shape,
    materialized as ``(N, K, B, *shape)`` zeros inside the jit (free on
    device, vs. the seed loop uploading them every round)."""
    toks = jnp.asarray(tokens)
    n = toks.shape[0]
    extras = dict(extras or {})

    def batch_fn(data_key: jax.Array, round_idx: jax.Array) -> PyTree:
        kr = jax.random.fold_in(data_key, round_idx)

        def per_client(i):
            k = jax.random.fold_in(kr, i)
            return jax.random.randint(k, (local_steps, local_batch), 0, n)

        idx = jax.vmap(per_client)(jnp.arange(num_clients))
        batch = {"tokens": toks[idx]}
        for name, shape in extras.items():
            batch[name] = jnp.zeros(
                (num_clients, local_steps, local_batch, *shape), jnp.float32)
        return batch

    return batch_fn


@dataclasses.dataclass
class EngineStats:
    """Dispatch/sync accounting, the structural half of BENCH_round_engine."""

    dispatches: int = 0              # jitted computations launched
    host_syncs: int = 0              # blocking device->host reads
    rounds: int = 0

    def per_round(self) -> Dict[str, float]:
        r = max(self.rounds, 1)
        return {"dispatches_per_round": self.dispatches / r,
                "host_syncs_per_round": self.host_syncs / r}


class RunHistory(NamedTuple):
    metrics: RoundMetrics            # stacked over all rounds (host arrays)
    evals: List[Tuple[int, Any]]     # (round, eval_fn result) per eval point


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Transport give-up policy: how often a rejected/late uplink frame is
    re-requested before the server treats that client as DROPPED this
    round (the fault semantics of ``repro.fl.faults`` — the client's EF
    keeps the whole update, the server renormalizes over what arrived).

    Every retry is a re-send of the SAME frame and is billed by the
    channel like any other send — retransmission is never free, so a lossy
    link shows up in the per-round byte buckets, not just the fault
    counters.

    The timeout schedule generalizes the retry count to a live transport:
    attempt ``a`` waits ``recv_timeout_s * recv_backoff**a`` seconds
    (exponential backoff), capped at ``max_timeout_s`` — which the socket
    driver sets to the round deadline, since no single receive should
    outwait the round itself.
    """

    max_retries: int = 2
    recv_timeout_s: float = 2.0
    recv_backoff: float = 2.0
    max_timeout_s: float = 30.0

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(
                f"max_retries must be >= 0, got {self.max_retries}")
        if self.recv_timeout_s <= 0.0:
            raise ValueError(
                f"recv_timeout_s must be > 0, got {self.recv_timeout_s}")
        if self.recv_backoff < 1.0:
            raise ValueError(
                f"recv_backoff must be >= 1.0 (a shrinking retry window "
                f"races its own resends), got {self.recv_backoff}")
        if self.max_timeout_s < self.recv_timeout_s:
            raise ValueError(
                f"max_timeout_s ({self.max_timeout_s}) must be >= "
                f"recv_timeout_s ({self.recv_timeout_s})")

    def timeout(self, attempt: int) -> float:
        """Receive window for attempt ``attempt`` (0-based)."""
        return min(self.recv_timeout_s * self.recv_backoff ** attempt,
                   self.max_timeout_s)


class DeliveryReport(NamedTuple):
    """What ``RoundEngine.deliver`` got through the wire."""

    frames: List[Any]                # validated host frames; None = given up
    delivered: np.ndarray            # (N,) bool — the round's delivered mask
    retries: int                     # total re-sends across all clients


class RoundEngine:
    """Drives ``make_fl_round``-style round functions in eval-sized scans.

    ``run_block``/``run`` is the production path (one dispatch + one sync
    per block, donated state); ``run_loop`` is the per-round reference loop
    with the seed driver's dispatch/sync pattern but the *same* on-device
    sampling — the bit-exactness oracle for the scanned path.
    """

    def __init__(self, round_fn: RoundFn, batch_fn: BatchFn, *, seed: int = 0,
                 donate: bool = True, shardings=None):
        base = jax.random.PRNGKey(seed)
        self._data_key = jax.random.fold_in(base, _DATA_FOLD)
        self._round_key = jax.random.fold_in(base, _ROUND_FOLD)
        self._round_fn = round_fn
        self._batch_fn = batch_fn
        self.donate = donate
        # repro.fl.sharding.FLShardings | None — the mesh placement contract
        # (see module docstring); imported structurally to keep this module
        # importable without touching jax device state.
        self.shardings = shardings
        self._blocks: Dict[int, Callable] = {}
        self._loop_step = None
        self.stats = EngineStats()

    # -- state ------------------------------------------------------------
    def init_state(self, params: PyTree, num_clients: int,
                   strategy=None, *, staleness_max: int = 0) -> FLState:
        """``fl_init`` on a deep copy of ``params`` so donation of the
        engine state can never consume the caller's model tree. Pass the
        round's ``CompressionStrategy`` so its ``init_ef_state`` shapes the
        EF residual (zeros f32 otherwise — identical for every built-in),
        and ``staleness_max=run.staleness_max`` when the round function was
        built with a staleness buffer (the FLState structures must match).
        With a placement contract installed, the fresh state is placed on
        the mesh (params replicated, EF client-sharded) before the first
        dispatch."""
        owned = jax.tree_util.tree_map(jnp.copy, params)
        state = fl_init(owned, num_clients, strategy,
                        staleness_max=staleness_max)
        if self.shardings is not None:
            state = self.shardings.place_state(state)
        return state

    # -- transport delivery (host-side, the driver half of the fault model)
    @staticmethod
    def deliver(channel, frames, *,
                policy: RetryPolicy = RetryPolicy()) -> DeliveryReport:
        """Push per-client uplink frames through a (possibly faulty)
        channel with retry/give-up semantics.

        Each frame is sent via ``channel.send_up`` and validated with
        ``frame.parse_header``; a ``None`` delivery (the wire dropped it)
        or a typed ``FrameError`` (corrupt on arrival) triggers a re-send,
        up to ``policy.max_retries`` times. A client whose every attempt
        fails is marked undelivered — exactly the ``delivered=False``
        branch of the in-round fault model, so the driver can hand the
        mask to a faulted round (or just renormalize over the survivors).
        Retries are re-sends of the SAME frame and are billed by the
        channel like any other send (retransmission is not free).
        """
        from repro.comm.frame import FrameError, parse_header

        tracer = get_tracer()
        out: List[Any] = []
        delivered = np.zeros((len(frames),), bool)
        retries = 0
        with tracer.span("engine.deliver", clients=len(frames)) as sp:
            for i, buf in enumerate(frames):
                got = None
                for attempt in range(policy.max_retries + 1):
                    if attempt > 0:
                        retries += 1
                        tracer.event("retry.resend", client=i,
                                     attempt=attempt)
                    wire = channel.send_up(buf)
                    if wire is None:
                        continue
                    try:
                        parse_header(wire)
                    except FrameError:
                        continue
                    got = wire
                    break
                out.append(got)
                delivered[i] = got is not None
                if got is None:
                    tracer.event("retry.give_up", client=i,
                                 attempts=policy.max_retries)
            sp.end(delivered=int(delivered.sum()), retries=retries)
        get_registry().counter("engine.deliver.retries").inc(retries)
        return DeliveryReport(out, delivered, retries)

    # -- the round body (shared by scan and reference loop) ----------------
    def _round(self, state: FLState) -> Tuple[FLState, RoundMetrics]:
        with jax.named_scope(BATCH_SCOPE):
            batches = self._batch_fn(self._data_key, state.round)
            if self.shardings is not None:
                batches = self.shardings.constrain_client_tree(batches)
        key = jax.random.fold_in(self._round_key, state.round)
        return self._round_fn(state, batches, key)

    def _block(self, length: int) -> Callable:
        fn = self._blocks.get(length)
        if fn is None:
            def blk(state):
                return jax.lax.scan(lambda s, _: self._round(s), state, None,
                                    length=length)
            donate = (0,) if self.donate else ()
            if self.shardings is None:
                fn = jax.jit(blk, donate_argnums=donate)
            else:
                # pin input AND output state to the contract: donation then
                # reuses the sharded buffers in place, and the scanned EF
                # carry can never silently gather to one device.
                fn = jax.jit(
                    blk, donate_argnums=donate,
                    in_shardings=(self.shardings.state,),
                    out_shardings=(self.shardings.state,
                                   self.shardings.replicated))
            self._blocks[length] = fn
        return fn

    def block_hlo_text(self, state: FLState, length: int) -> str:
        """Optimized HLO text of the ``length``-round block executable, with
        its op_name metadata: the instruction names a profiler trace shows,
        each under the phase scopes (``fl.round.PHASE_SCOPES``) it came
        from. Compiles, or loads from the compile cache, and runs nothing;
        ``state`` is only read for its shapes and is not consumed."""
        return self._block(length).lower(state).compile().as_text()

    # -- scanned path ------------------------------------------------------
    def run_block(self, state: FLState,
                  length: int) -> Tuple[FLState, RoundMetrics]:
        """``length`` rounds in ONE dispatch; the input ``state`` is consumed
        (donated) — use only the returned state. The stacked metrics come
        back via a single ``device_get`` (the block's one host sync).

        Span tags use the engine's host-side round counter, never
        ``state.round`` — reading the device counter here would force an
        extra sync and corrupt the very dispatch/sync accounting this
        path is gated on."""
        tracer = get_tracer()
        r0 = self.stats.rounds
        with tracer.span("engine.dispatch", block=length, rounds_done=r0):
            state, ms = self._block(length)(state)
        self.stats.dispatches += 1
        with tracer.span("engine.sync", block=length, rounds_done=r0):
            ms = jax.device_get(ms)
        self.stats.host_syncs += 1
        self.stats.rounds += length
        return state, ms

    def run(self, state: FLState, num_rounds: int, *, eval_every: int = 0,
            eval_fn: Optional[Callable[[FLState, RoundMetrics, int], Any]] = None,
            ckpt_every: int = 0,
            ckpt_fn: Optional[Callable[[FLState, int], Any]] = None,
            ) -> Tuple[FLState, RunHistory]:
        """Blocks of ``eval_every`` rounds (plus a remainder block), with
        ``eval_fn(state, block_metrics, rounds_done)`` called at each eval
        boundary — the seed drivers' eval cadence ((r+1) % eval_every == 0,
        plus the final round). ``block_metrics`` is the just-fetched stacked
        ``RoundMetrics`` of the block that ended at the boundary, so
        eval-time logging costs no extra sync.

        ``ckpt_fn(state, absolute_round)`` fires whenever the *absolute*
        round counter (``FLState.round`` — a resumed state starts past 0)
        crosses a multiple of ``ckpt_every``; both cadences are anchored on
        the absolute counter, so a resumed run checkpoints and evals at the
        same rounds the uninterrupted run does. Scan blocks extend to the
        nearest upcoming boundary of either cadence — by the fold_in PRNG
        contract the extra block splits regroup dispatches without changing
        the trajectory (the eval-cadence-invariance property), which is
        exactly what makes checkpoint placement bitwise-free."""
        r0 = int(state.round)
        target = r0 + num_rounds

        def boundary(cur: int, every: int) -> int:
            return (cur // every + 1) * every if every > 0 else target

        chunks: List[RoundMetrics] = []
        evals: List[Tuple[int, Any]] = []
        cur = r0
        while cur < target:
            nxt = min(boundary(cur, eval_every), boundary(cur, ckpt_every),
                      target)
            state, ms = self.run_block(state, nxt - cur)
            cur = nxt
            chunks.append(ms)
            if eval_fn is not None and (
                    cur == target or (eval_every > 0 and cur % eval_every == 0)):
                evals.append((cur - r0, eval_fn(state, ms, cur - r0)))
            if ckpt_fn is not None and ckpt_every > 0 and cur % ckpt_every == 0:
                ckpt_fn(state, cur)
        if chunks:
            metrics = RoundMetrics(*[
                np.concatenate([np.atleast_1d(np.asarray(getattr(c, f)))
                                for c in chunks])
                for f in RoundMetrics._fields])
        else:                        # num_rounds == 0: empty, not None
            metrics = RoundMetrics(*[np.zeros((0,), np.float32)
                                     for _ in RoundMetrics._fields])
        return state, RunHistory(metrics, evals)

    # -- per-round reference loop -----------------------------------------
    def run_loop(self, state: FLState,
                 num_rounds: int) -> Tuple[FLState, RoundMetrics]:
        """Seed-driver dispatch pattern: one jit call per round, two blocking
        scalar syncs per round (loss, mean cosine) — but the same on-device
        sampling and round math as the scanned path, so the two are
        bit-exact. Never donates (the seed loop did not)."""
        if self._loop_step is None:
            self._loop_step = jax.jit(self._round)
        out: List[RoundMetrics] = []
        for _ in range(num_rounds):
            state, m = self._loop_step(state)
            self.stats.dispatches += 1
            float(m.loss)
            float(jnp.mean(m.cosine))
            self.stats.host_syncs += 2
            self.stats.rounds += 1
            # oracle record for the bit-exactness tests; by now the round is
            # fully computed, so this copy is instrumentation, not part of
            # the counted seed driver pattern
            out.append(jax.device_get(m))
        metrics = RoundMetrics(*[
            np.stack([np.asarray(getattr(m, f)) for m in out])
            for f in RoundMetrics._fields])
        return state, metrics


def live_server_step(codec, num_clients: int, server_lr: float):
    """The live loop's jitted server step ``(params, (N, nbytes) uint8
    frames, (N,) delivered) -> params``: a bitwise mirror of ``fl.round``'s
    faulted codec path at S=0, weights=None (vmap decode -> recon ->
    mean(where) * N/count -> ``server_update``)."""
    N = num_clients

    def step(p, bufs, delivered):
        canon = jax.vmap(codec.decode)(bufs)
        recons = jax.vmap(lambda c: codec.recon_tree(c, p))(canon)
        cnt = jnp.sum(delivered.astype(jnp.float32))
        ratio = jnp.where(cnt > 0, N / cnt, 0.0)
        agg = jax.tree_util.tree_map(
            lambda m: m * ratio,
            client_mean(jax.tree_util.tree_map(
                lambda x: jnp.where(
                    delivered.reshape((-1,) + (1,) * (x.ndim - 1)), x, 0),
                recons)))
        return server_update(p, agg, server_lr)

    return jax.jit(step)


class LiveRoundLoop:
    """The server half of a live cross-process round over a transport.

    Where ``RoundEngine`` scans rounds inside one device program (clients
    are a vmap axis), ``LiveRoundLoop`` drives real client *processes*
    through a ``repro.comm.transport.SocketServer``: broadcast the params
    frame, ``collect`` the uplink under the round deadline with
    backoff/retries/liveness, ACK each worker its delivered verdict, and
    aggregate on the server.

    The server step mirrors the in-process faulted pipeline EXACTLY
    (``fl.round``'s codec decode -> recon -> masked mean x N/count ->
    ``server_update``), with every transport outcome — timeout, corrupt
    frame, dead worker — mapped onto the ``delivered=False`` mask. That is
    what makes the live loop bitwise-comparable to the in-process oracle
    on identical fault patterns (gated in ``benchmarks/bench_transport.py``):
    undelivered rows are zero placeholders whose decoded garbage the
    masked ``where`` never reads, exactly like the oracle's masked rows.

    ``participate_fn(round) -> (N,) bool`` drives partial participation
    (non-participants are told to sit the round out; their EF freezes —
    the ``participate=False`` branch). ``on_round(record, report)`` fires
    after every round with the history record + raw ``DeliveryReport``.
    """

    def __init__(self, server, strategy, codec, run, params, *,
                 policy: Optional[RetryPolicy] = None,
                 participate_fn=None, on_round=None):
        # lazy comm imports: fl never hard-depends on the wire layer
        from repro.comm.codec import make_codec
        from repro.configs.base import CompressorConfig

        self.server = server
        self.strategy = strategy
        self.codec = codec
        self.cfg = run
        self.policy = policy if policy is not None else run.retry_policy()
        self.participate_fn = participate_fn
        self.on_round = on_round
        self.params = jax.tree_util.tree_map(jnp.copy, params)
        self.history: List[Dict[str, Any]] = []
        N = run.fl.num_clients
        server_lr = run.fl.server_lr
        # the downlink broadcast is the raw params frame (identity codec);
        # compressing it too is the E-3SFC roadmap item, not this loop's
        self._down = make_codec(
            CompressorConfig(kind="identity", error_feedback=False), params)
        self._enc = jax.jit(
            lambda p, r: self._down.encode(p, round_idx=r))

        self._step = live_server_step(codec, N, server_lr)
        self._placeholder = np.zeros((codec.nbytes,), np.uint8)

    def run(self, num_rounds: int, *, deadline_s: Optional[float] = None,
            policy: Optional[RetryPolicy] = None, ckpt_every: int = 0,
            ckpt_fn=None):
        """Drive ``num_rounds`` live rounds; returns the final params.
        Per-round records (wall clock, delivered mask, retries, byte
        buckets, dead set, reported losses) accumulate in ``history``.
        ``deadline_s``/``policy`` override the loop's configuration for
        these rounds only — warm-up rounds (first-dispatch jit compilation
        happens inside the workers' round 0) want generous windows,
        measured straggle rounds tight ones.

        ``ckpt_fn(loop, round)`` fires at round boundaries where
        ``(round + 1) % ckpt_every == 0`` — round indices are absolute
        (``server.begin_round`` resumes numbering from a restored ledger),
        so a resumed loop checkpoints at the same rounds the uninterrupted
        one does. The driver's hook is expected to settle the server's EF
        bank (``wait_ef_bank``) before snapshotting."""
        N = self.cfg.fl.num_clients
        dl = self.cfg.round_deadline_s if deadline_s is None else deadline_s
        pol = self.policy if policy is None else policy
        tracer = get_tracer()
        meters = get_registry()
        for _ in range(num_rounds):
            r = self.server.begin_round()
            oh0 = (self.server.overhead_up, self.server.overhead_down)
            t0 = time.perf_counter()
            with tracer.span("round", round=r, deadline_s=dl) as round_sp:
                with tracer.span("round.encode", round=r,
                                 phase="encode") as enc_sp:
                    down = np.asarray(self._enc(self.params, jnp.uint32(r)))
                    enc_sp.end(bytes=int(down.nbytes))
                part = (np.ones((N,), bool) if self.participate_fn is None
                        else np.asarray(self.participate_fn(r), bool))
                with tracer.span("round.broadcast", round=r,
                                 phase="broadcast"):
                    self.server.broadcast_round(r, down, part)
                live = np.zeros((N,), bool)
                live[self.server.live_workers()] = True
                with tracer.span("round.collect", round=r, phase="collect",
                                 deadline_s=dl) as col_sp:
                    rep = self.server.collect(
                        r, part & live, policy=pol, deadline_s=dl)
                    col_sp.end(delivered=int(rep.delivered.sum()),
                               retries=rep.retries)
                with tracer.span("round.ack", round=r, phase="ack"):
                    self.server.send_acks(r, rep.delivered)
                with tracer.span("round.aggregate", round=r,
                                 phase="aggregate"):
                    bufs = np.stack(
                        [np.asarray(f, np.uint8) if f is not None
                         else self._placeholder for f in rep.frames])
                    self.params = self._step(self.params, jnp.asarray(bufs),
                                             jnp.asarray(rep.delivered))
                    jax.block_until_ready(self.params)
                dead = sorted(set(range(N))
                              - set(self.server.live_workers()))
                # one outcome tag per client per round: what the trace
                # analyzer attributes stragglers / drops / deaths from
                for cid in range(N):
                    if not part[cid]:
                        outcome = "sat_out"
                    elif rep.delivered[cid]:
                        outcome = "delivered"
                    elif cid in dead:
                        outcome = "dead"
                    else:
                        outcome = "undelivered"
                    tracer.event("round.outcome", round=r, client=cid,
                                 outcome=outcome)
                round_sp.end(delivered=int(rep.delivered.sum()),
                             retries=rep.retries)
            wall_s = time.perf_counter() - t0
            meters.counter("loop.rounds").inc()
            meters.gauge("loop.round").set(r)
            meters.histogram("loop.round_wall_s").observe(wall_s)
            rec = {"round": r,
                   "wall_s": wall_s,
                   "participate": part,
                   "delivered": rep.delivered.copy(),
                   "retries": rep.retries,
                   "bytes_up": self.server.uplink.per_round[-1],
                   "bytes_down": self.server.downlink.per_round[-1],
                   "overhead_up": self.server.overhead_up - oh0[0],
                   "overhead_down": self.server.overhead_down - oh0[1],
                   "dead": dead,
                   "losses": self.server.pop_metrics(r)}
            self.history.append(rec)
            if self.on_round is not None:
                self.on_round(rec, rep)
            if ckpt_fn is not None and ckpt_every > 0 \
                    and (r + 1) % ckpt_every == 0:
                ckpt_fn(self, r)
        return self.params
