"""One federated round, end to end, as a single jit/pjit-able pipeline.

``build_fl_round(loss_fn, strategy, run)`` composes THE round function from
three phases, each parameterized by the ``RunConfig`` and the
``CompressionStrategy`` (``repro.core.strategy``) instead of being one of
eight hand-written closure variants:

  1. **client phase** — every client runs K local SGD steps, then the
     strategy EF-compresses its accumulated update into a *message*:
     the reconstruction tree (float mode), the raw wire payload (fused
     mode) or a framed ``uint8`` codec buffer (codec mode). Per-client, no
     cross-client collectives.
  2. **transport boundary** — the client axis is fanned out either as a
     plain ``vmap`` (single-device reference semantics, the bit-exactness
     oracle) or as a ``jax.shard_map`` over ``client_axes(mesh)`` whose
     only communication is ONE tiled ``all_gather`` of the messages (the
     per-client region is HLO-gated collective-free under the
     ``CLIENT_SCOPE`` named scope).
  3. **server phase** — messages are decoded (codec mode) and aggregated:
     the default path averages per-client reconstructions (``fl.server``),
     while strategies declaring ``supports_fused_aggregate`` (3SFC) hand
     the *batched payloads* straight to ``strategy.server_aggregate`` —
     one replicated batched backward, no O(d) collective — so the fused
     decode is a strategy capability, not a special case here.

Fan-out notes (``run.client_parallel``)
---------------------------------------
* ``'vmap'``: single program; with a mesh attached, GSPMD partitions it.
* ``'shard_map'`` (requires ``run.mesh``): each device runs its *local*
  clients' ``local_train`` + encode; only the boundary communicates. The
  default path's gather is deliberately ``all_gather``-then-reduce instead
  of ``psum``: the all-reduce combiner order differs from a single-device
  axis reduction (measured ~1e-5 on 8 hosts), which would break the
  shard_map ≡ vmap oracle contract that keeps this pipeline testable. Per
  the HLO byte accounting both forms move the same O(d) operand bytes per
  device — a collective-order choice, not a bandwidth concession. The
  fused path's gather carries ONLY the tiny payloads (= the paper's
  compressed uplink, as on-mesh wire bytes).

Wire modes (``run.wire``)
-------------------------
* ``'float'``: messages are float trees; wire size is *accounted*
  (``payload_floats``, Eq. 1).
* ``'codec'`` (requires ``codec`` from ``repro.comm.make_codec``): each
  client serializes its payload into ONE framed ``uint8`` buffer inside
  the per-client region; only those buffers cross the boundary and the
  server decodes them before aggregating. ``RoundMetrics.wire_bytes_up``
  then reports the *measured* per-client uplink bytes. EF uses the codec's
  dequantized view, so client and server stay consistent; wherever the
  codec is lossless the round is bit-identical to float mode (gated by
  ``benchmarks/bench_wire.py``).

Metrics returned per round: mean local loss, per-client cosine compression
efficiency (paper Fig. 7), payload floats (paper Eq. 1 accounting), and the
measured uplink bytes (0 in float mode — nothing was serialized).

``make_fl_round`` is kept as a thin deprecated shim over
``build_fl_round`` for existing callers.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import FLConfig
from repro.configs.run import RunConfig
from repro.core import flat
from repro.core.strategy import CompressionStrategy, warn_deprecated_once
from repro.fl import faults as faults_lib
from repro.fl.client import local_train
from repro.fl.server import (aggregate, client_mean, client_sum,
                              server_update)

PyTree = Any

# Named scope wrapping the per-client local-train + encode region; the
# collectives benchmark greps compiled-HLO metadata for this name to prove
# the region stays collective-free (tested in tests/test_hlo_analyzer.py).
CLIENT_SCOPE = "fl_client_local"


class FLState(NamedTuple):
    params: PyTree          # global model w^t
    ef: PyTree              # per-client EF residuals, leading axis N
    round: jax.Array
    # staleness ring buffer (repro.fl.faults): per params leaf a (S, *shape)
    # bank of weighted in-flight reconstructions + the (S,) arrived-weight
    # accumulator. None (an empty pytree node) whenever staleness_max == 0,
    # so zero-fault states keep the exact seed structure.
    buf: PyTree = None
    buf_w: Optional[jax.Array] = None


class RoundMetrics(NamedTuple):
    loss: jax.Array         # mean local training loss (participants only)
    cosine: jax.Array       # per-client compression efficiency (N,)
    payload_floats: jax.Array
    update_norm: jax.Array
    # measured per-client uplink bytes (wire='codec'); 0 in float mode
    wire_bytes_up: jax.Array = 0.0
    # total aggregation weight that arrived this round: N when healthy,
    # the renormalization denominator under faults (fresh + matured stale)
    arrivals: jax.Array = -1.0


def fl_init(params: PyTree, num_clients: int,
            strategy: Optional[CompressionStrategy] = None, *,
            staleness_max: int = 0) -> FLState:
    """Fresh round state; the EF residual comes from the strategy when one
    is given (zeros f32 mirroring params otherwise — the same default).
    ``staleness_max > 0`` attaches the zeroed staleness ring buffer."""
    if strategy is not None:
        ef1 = strategy.init_ef_state(params)
    else:
        ef1 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    ef = jax.tree_util.tree_map(
        lambda e: jnp.broadcast_to(e, (num_clients, *e.shape)), ef1)
    buf, buf_w = faults_lib.init_stale_buffer(params, staleness_max)
    return FLState(params, ef, jnp.zeros((), jnp.int32), buf, buf_w)


def _check_codec(run: RunConfig, strategy: CompressionStrategy,
                 codec) -> None:
    """Validate the (wire, codec) pair for codec mode."""
    if run.wire == "float":
        return
    if codec is None:
        raise ValueError("wire='codec' requires a codec "
                         "(see repro.comm.make_codec)")
    if codec.kind != strategy.cfg.kind:
        raise ValueError(f"codec kind {codec.kind!r} does not match "
                         f"compressor kind {strategy.cfg.kind!r}")
    codec.check_round_wire()


def build_fl_round(
    loss_fn: Callable[[PyTree, Dict], jax.Array],
    strategy: CompressionStrategy,
    run: RunConfig,
    *,
    codec=None,
    fault_schedule_fn=None,
) -> Callable[[FLState, PyTree, jax.Array], Tuple[FLState, RoundMetrics]]:
    """THE round builder: one pipeline over (strategy × fan-out × wire).

    ``run.fused_decode`` requires ``strategy.supports_fused_aggregate``
    (§Perf beyond-paper optimization): the server aggregates straight from
    the gathered wire payloads — for 3SFC, since every ĝ_i is evaluated at
    the same w^t (Eq. 10),

        G(ĝ_1..ĝ_N) = ∇_w (1/N) Σ_i s_i F(D_syn,i, w^t),

    so the all_gather carries ONLY the tiny (D_syn, s) payloads and ONE
    replicated batched backward replaces the O(d) full-gradient collective.
    EF stays exact because each client updates its residual locally.

    ``run.has_faults`` switches in the masked fault pipeline
    (``repro.fl.faults``); ``fault_schedule_fn(round_idx, num_clients) ->
    FaultSchedule`` overrides the config-derived schedule and forces the
    masked pipeline even on a zero-fault config — the injection seam the
    fault harness uses to (a) prove the masked pipeline under a null
    schedule is bitwise the unfaulted round and (b) drive hand-written
    fault patterns in the EF-invariance tests. Injected schedules must
    respect ``run.staleness_max`` (delays > 0 need the ring buffer).
    """
    cfg: FLConfig = run.fl
    mesh: Optional[Mesh] = run.mesh
    axes = run.client_axes()
    fused = run.fused_decode
    faulted = run.has_faults or fault_schedule_fn is not None
    N = cfg.num_clients
    S = run.staleness_max
    if fused and not strategy.supports_fused_aggregate:
        raise ValueError(
            f"fused_decode requires a strategy with "
            f"supports_fused_aggregate; {strategy.cfg.kind!r} has none")
    if faulted and fused:
        if type(strategy).mask_payloads is CompressionStrategy.mask_payloads:
            raise ValueError(
                f"fused_decode under faults requires strategy "
                f"{strategy.cfg.kind!r} to implement mask_payloads "
                f"(weighting the batched wire payloads)")
    _check_codec(run, strategy, codec)
    # the fault stream is its own root key — fault patterns re-seed without
    # perturbing the data/compressor draws (fl.faults determinism contract)
    fault_key = jax.random.PRNGKey(run.fault_seed) if faulted else None

    # ---- client phase: local train + strategy encode ----------------------
    if run.wire == "codec":
        def encode(key_i, g, ef_i, params, cid, rnd):
            return strategy.wire_step(key_i, g, ef_i, params, codec=codec,
                                      round_idx=rnd, client_idx=cid)
    elif fused:
        def encode(key_i, g, ef_i, params, cid, rnd):
            return strategy.payload_step(key_i, g, ef_i, params)
    else:
        def encode(key_i, g, ef_i, params, cid, rnd):
            return strategy.step(key_i, g, ef_i, params)

    def client_core(global_params, ef_i, batches_i, key_i, cid, rnd):
        g, loss = local_train(loss_fn, global_params, batches_i,
                              cfg.local_lr, num_micro=run.num_micro)
        msg, ef_new, metrics = encode(key_i, g, ef_i, global_params,
                                      cid, rnd)
        return g, msg, ef_new, loss, metrics

    if not faulted:
        def client_step(global_params, ef_i, batches_i, key_i, cid, rnd):
            _, msg, ef_new, loss, metrics = client_core(
                global_params, ef_i, batches_i, key_i, cid, rnd)
            return msg, ef_new, loss, metrics

        in_axes = (None, 0, 0, 0, 0, None)
    else:
        def client_step(global_params, ef_i, batches_i, key_i, cid, rnd,
                        part_i, deliv_i):
            g, msg, ef_new, loss, metrics = client_core(
                global_params, ef_i, batches_i, key_i, cid, rnd)
            # EF fault algebra (repro.fl.faults): a skipped client's
            # residual FREEZES; a dropped payload banks the whole
            # accumulated update u = g + e in the residual (nothing lost)
            # — with EF off there is no residual, the update is lost and
            # e stays whatever the strategy keeps it as. Pure per-client
            # `where` selects: no new collectives, bitwise inert when
            # part_i and deliv_i are both true.
            if strategy.cfg.error_feedback:
                ef_drop = strategy._accumulate(g, ef_i)
            else:
                ef_drop = ef_i
            ef_out = jax.tree_util.tree_map(
                lambda new, drop, old: jnp.where(
                    part_i, jnp.where(deliv_i, new, drop), old),
                ef_new, ef_drop, ef_i)
            return msg, ef_out, loss, metrics

        in_axes = (None, 0, 0, 0, 0, None, 0, 0)
    n_extra = 2 if faulted else 0

    # ---- transport boundary: the client fan-out ---------------------------
    if axes is None:
        def fanout(*args):
            return jax.vmap(client_step, in_axes=in_axes)(*args)
    else:
        def body(*args):
            with jax.named_scope(CLIENT_SCOPE):
                outs = jax.vmap(client_step, in_axes=in_axes)(*args)
            # ONE tiled all_gather of every output EXCEPT the
            # client-resident EF tree — the gathered operands are the wire
            # (recon trees, wire payloads or framed uint8 buffers). The
            # fault masks ride IN as client-sharded scalars (per-client
            # where-selects in the scope above), never adding a collective.
            gather = lambda x: jax.lax.all_gather(x, axes, tiled=True)
            return tuple(
                o if i == 1 else jax.tree_util.tree_map(gather, o)
                for i, o in enumerate(outs))

        fanout = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axes), P(axes), P(axes), P(axes), P())
            + (P(axes),) * n_extra,
            out_specs=tuple(P(axes) if i == 1 else P() for i in range(4)),
            check_vma=False,
        )

    def _replicate(x):
        # Explicit mesh plumbing for the vmap fused path: with no mesh the
        # constraint is a no-op by construction (single-process tests);
        # with one, the payloads are pinned replicated so the batched
        # backward runs on every device.
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))

    # ---- server phase: decode + aggregate + update + metrics --------------
    wire_bytes = codec.nbytes if run.wire == "codec" else 0.0

    def finish(state: FLState, agg, ef_new, loss, metrics, payload_floats,
               arrivals, buf, buf_w) -> Tuple[FLState, RoundMetrics]:
        new_params = server_update(state.params, agg, cfg.server_lr)
        ef_new = jax.tree_util.tree_map(
            lambda n, o: n.astype(o.dtype), ef_new, state.ef)
        rm = RoundMetrics(
            loss=loss,
            cosine=metrics.cosine,
            payload_floats=payload_floats,
            update_norm=flat.tree_norm(agg),
            wire_bytes_up=jnp.float32(wire_bytes),
            arrivals=arrivals,
        )
        return FLState(new_params, ef_new, state.round + 1, buf, buf_w), rm

    def _mask_bcast(m, x):
        return m.reshape((-1,) + (1,) * (x.ndim - 1))

    def _faulted_aggregate(state: FLState, recons, sched, weights):
        """Masked/weighted aggregation + staleness-buffer turnover.

        Returns ``(agg, arrivals, buf, buf_w)``. The unweighted no-staleness
        branch is ``mean(where(mask, x, 0)) * (N/count)`` — count-correct
        renormalization that multiplies by *exactly* 1.0 under an
        all-healthy schedule, keeping the zero-fault round bitwise equal to
        the unfaulted pipeline (gated in benchmarks/bench_faults.py).
        """
        now = sched.arrives_now
        if S == 0 and weights is None:
            cnt = jnp.sum(now.astype(jnp.float32))
            ratio = jnp.where(cnt > 0, N / cnt, 0.0)
            agg = jax.tree_util.tree_map(
                lambda m: m * ratio,
                client_mean(jax.tree_util.tree_map(
                    lambda x: jnp.where(_mask_bcast(now, x), x, 0), recons)))
            return agg, cnt, state.buf, state.buf_w
        # generic path: staleness-weighted sum of fresh + matured payloads,
        # renormalized by the total arrived weight
        base_w = jnp.ones((N,), jnp.float32) if weights is None else weights
        w_now = jnp.where(now, sched.weight * base_w, 0.0)
        if S == 0:
            mature_w = jnp.float32(0.0)
            num = client_sum(jax.tree_util.tree_map(
                lambda x: _mask_bcast(w_now, x) * x, recons))
            buf, buf_w = state.buf, state.buf_w
        else:
            if state.buf_w is None:
                raise ValueError(
                    "staleness_max > 0 requires an FLState carrying the "
                    "staleness buffer — init with fl_init(..., "
                    "staleness_max=run.staleness_max)")
            w_late = jnp.where(sched.arrives_late, sched.weight * base_w, 0.0)
            mature, mature_w, buf, buf_w = faults_lib.consume_and_bank(
                state.buf, state.buf_w, state.round, sched.delay, w_late,
                recons)
            num = jax.tree_util.tree_map(
                jnp.add, client_sum(jax.tree_util.tree_map(
                    lambda x: _mask_bcast(w_now, x) * x, recons)), mature)
        den = jnp.sum(w_now) + mature_w
        inv = jnp.where(den > 0, 1.0 / den, 0.0)
        agg = jax.tree_util.tree_map(lambda x: x * inv, num)
        return agg, den, buf, buf_w

    def fl_round(state: FLState, client_batches: PyTree, key: jax.Array,
                 weights: jax.Array = None):
        keys = jax.random.split(key, cfg.num_clients)
        cids = jnp.arange(cfg.num_clients, dtype=jnp.uint32)
        if faulted:
            if fault_schedule_fn is not None:
                sched = fault_schedule_fn(state.round, N)
            else:
                sched = faults_lib.fault_schedule(
                    fault_key, state.round, N,
                    participation_rate=run.participation_rate,
                    drop_rate=run.drop_rate,
                    straggler_rate=run.straggler_rate,
                    staleness_max=S)
            extra = (sched.participate, sched.delivered)
        else:
            sched = None
            extra = ()
        msgs, ef_new, losses, metrics = fanout(
            state.params, state.ef, client_batches, keys, cids, state.round,
            *extra)
        if faulted:
            # loss over participants only (mean × N/count: exact 1.0 when
            # everyone participates, same identity as the aggregate)
            cnt_p = jnp.sum(sched.participate.astype(jnp.float32))
            loss = client_mean(jnp.where(sched.participate, losses, 0.0)) * \
                jnp.where(cnt_p > 0, N / cnt_p, 0.0)
        else:
            loss = client_mean(losses)
        if fused:
            if axes is None:
                # vmap fan-out: the payloads are tiny -> pin replicated
                msgs = jax.tree_util.tree_map(_replicate, msgs)
            payloads = jax.vmap(codec.decode)(msgs) \
                if run.wire == "codec" else msgs
            # scalar, matching the default path's jnp.mean reduction
            pf = jnp.float32(strategy.payload_floats(state.params))
            if faulted:
                # fused faults: zero out undelivered payloads inside the
                # batched aggregate (S == 0 here by RunConfig validation),
                # then renormalize the mean over N to a mean over arrivals
                w = jnp.where(sched.arrives_now, jnp.float32(1.0),
                              jnp.float32(0.0))
                agg = strategy.server_aggregate(
                    state.params, strategy.mask_payloads(payloads, w))
                cnt = jnp.sum(w)
                agg = flat.tree_scale(
                    agg, jnp.where(cnt > 0, N / cnt, 0.0))
                return finish(state, agg, ef_new, loss, metrics, pf, cnt,
                              state.buf, state.buf_w)
            agg = strategy.server_aggregate(state.params, payloads)
            return finish(state, agg, ef_new, loss, metrics, pf,
                          jnp.float32(N), state.buf, state.buf_w)
        if run.wire == "codec":
            # (N, nbytes) uint8 -> per-client reconstruction trees
            canon = jax.vmap(codec.decode)(msgs)
            recons = jax.vmap(
                lambda c: codec.recon_tree(c, state.params))(canon)
        else:
            recons = msgs
        if faulted:
            agg, arrivals, buf, buf_w = _faulted_aggregate(
                state, recons, sched, weights)
            return finish(state, agg, ef_new, loss, metrics,
                          jnp.mean(metrics.payload_floats), arrivals,
                          buf, buf_w)
        # inputs are full (N, ...) arrays in client order on both fan-out
        # paths, and aggregate adds them in that order (server.client_sum),
        # so the result is identical
        agg = aggregate(recons, weights)
        return finish(state, agg, ef_new, loss, metrics,
                      jnp.mean(metrics.payload_floats),
                      jnp.float32(N), state.buf, state.buf_w)

    return fl_round


# ---------------------------------------------------------------------------
# deprecated shim (PR 5): the old 10-knob factory over the new pipeline
# ---------------------------------------------------------------------------


def make_fl_round(
    loss_fn: Callable[[PyTree, Dict], jax.Array],
    compressor,
    cfg: FLConfig,
    *,
    num_micro: int = 1,
    fused_decode: bool = False,
    syn_loss_fn: Callable = None,
    syn_spec=None,
    client_parallel: str = "vmap",
    mesh: Optional[Mesh] = None,
    wire: str = "float",
    codec=None,
) -> Callable[[FLState, PyTree, jax.Array], Tuple[FLState, RoundMetrics]]:
    """Deprecated: build a ``RunConfig`` and call ``build_fl_round``.

    ``compressor`` may be a ``TreeCompressor`` (its strategy is used) or a
    ``CompressionStrategy`` directly. The legacy ``syn_loss_fn``/``syn_spec``
    pair is required with ``fused_decode`` for signature compatibility but
    the strategy's own hooks (identical by construction) do the work.
    """
    warn_deprecated_once(
        "make_fl_round",
        "repro.fl.round.build_fl_round(loss_fn, strategy, RunConfig(...))")
    if fused_decode:
        assert syn_loss_fn is not None and syn_spec is not None, \
            "fused_decode needs the 3SFC syn_loss_fn + syn_spec"
    strategy = getattr(compressor, "strategy", compressor)
    run = RunConfig(fl=cfg, client_parallel=client_parallel, wire=wire,
                    fused_decode=fused_decode, num_micro=num_micro,
                    mesh=mesh)
    return build_fl_round(loss_fn, strategy, run, codec=codec)


# convenience alias used in docs/examples
fl_round = make_fl_round
