"""Layer-1 driver: compile the FULL round matrix at tiny shapes.

``iter_round_configs()`` enumerates every *valid* point of
``strategy_kinds()`` × {vmap, shard_map} × {float, codec} × {fused,
default} × {faulted, null} — capability-filtered exactly the way
``build_fl_round`` itself filters (codec only for kinds with a registered
wire format, fused only for ``supports_fused_aggregate`` strategies,
fused×faulted only with a real ``mask_payloads``), so the checker covers
precisely the space a user can construct, no more and no less.

``build_round_artifact`` compiles one point at deliberately tiny shapes
(4 clients, 1 local step, batch 4, a 4×4×1 3-class vision spec) with the
EF state donated, and packages the optimized HLO plus the config-derived
expectations into a ``contracts.RoundArtifact``. shard_map points need a
≥4-device runtime, so ``python -m repro.analysis.ir`` is run as a child
under ``benchmarks.bench_collectives.multidev_env()`` (the forced-8-device
host-CPU recipe) and prints the ``contracts.run_contracts`` report as JSON
— the driver (``scripts/check_static.py``) never ships HLO text across the
process boundary, only the verdicts.
"""
from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

from repro.analysis import contracts

# tiny-but-real round shape: 4 clients over a (4, 1) data×model mesh,
# one local step, batch 4, 4x4x1 inputs, 3 classes
TINY_N, TINY_K, TINY_B = 4, 1, 4
TINY_MESH_SHAPE = (4, 1)
TINY_INPUT = (4, 4, 1)
TINY_CLASSES = 3


def iter_round_configs() -> List[Dict[str, Any]]:
    """Every constructible (kind, fanout, wire, fused, faulted) point."""
    from repro.comm.codec import CODECS
    from repro.core.strategy import (CompressionStrategy, STRATEGIES,
                                     strategy_kinds)
    cfgs: List[Dict[str, Any]] = []
    for kind in strategy_kinds():
        cls = STRATEGIES[kind]
        wires = ["float"] + (["codec"] if kind in CODECS else [])
        fuseds = [False, True] if cls.supports_fused_aggregate else [False]
        masked = cls.mask_payloads is not CompressionStrategy.mask_payloads
        for fanout in ("vmap", "shard_map"):
            for wire in wires:
                for fused in fuseds:
                    for faulted in (False, True):
                        if fused and faulted and not masked:
                            continue
                        cfgs.append({"kind": kind, "fanout": fanout,
                                     "wire": wire, "fused": fused,
                                     "faulted": faulted})
    return cfgs


def build_context() -> Dict[str, Any]:
    """Shared compile context: tiny model/params, mesh + shardings when the
    runtime has ≥4 devices (else shard_map points must be skipped by the
    caller), abstract batch/key avals."""
    import jax
    import jax.numpy as jnp

    from repro.fl.sharding import make_fl_shardings
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import VisionSpec, make_paper_model

    spec = VisionSpec("tiny", TINY_INPUT, TINY_CLASSES)
    model = make_paper_model("mlp", spec)
    params = model.init(jax.random.PRNGKey(0))
    mesh = sh = None
    client_shards = 1
    if len(jax.devices()) >= TINY_MESH_SHAPE[0]:
        mesh = make_mesh(TINY_MESH_SHAPE, ("data", "model"))
        sh = make_fl_shardings(mesh)
        client_shards = sh.client_shards
    batches = {
        "x": jax.ShapeDtypeStruct(
            (TINY_N, TINY_K, TINY_B, *TINY_INPUT), jnp.float32),
        "y": jax.ShapeDtypeStruct((TINY_N, TINY_K, TINY_B), jnp.int32),
    }
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return {"spec": spec, "model": model, "params": params, "mesh": mesh,
            "sh": sh, "client_shards": client_shards, "batches": batches,
            "key": key}


def build_round_artifact(config: Dict[str, Any],
                         ctx: Optional[Dict[str, Any]] = None,
                         ) -> contracts.RoundArtifact:
    """Compile one matrix point (EF donated) into a contract-checkable
    artifact."""
    import jax

    from repro.comm.codec import make_codec
    from repro.configs.base import CompressorConfig, FLConfig
    from repro.configs.run import RunConfig
    from repro.core.strategy import make_strategy
    from repro.fl import faults as F
    from repro.fl.round import build_fl_round, fl_init
    from repro.models.build import vision_syn_spec

    if ctx is None:
        ctx = build_context()
    kind = config["kind"]
    shard = config["fanout"] == "shard_map"
    if shard and ctx["mesh"] is None:
        raise RuntimeError(
            "shard_map config needs a >=4-device runtime "
            "(run via benchmarks.bench_collectives.multidev_env())")

    ccfg = CompressorConfig(kind=kind, keep_ratio=0.25, syn_steps=2,
                            syn_lr=0.1,
                            error_feedback=(kind != "identity"))
    spec = vision_syn_spec(ctx["spec"], ccfg)
    strat = make_strategy(ccfg, loss_fn=ctx["model"].syn_loss,
                          syn_spec=spec, local_lr=0.05)
    fl = FLConfig(num_clients=TINY_N, local_steps=TINY_K, local_lr=0.05,
                  local_batch=TINY_B, compressor=ccfg)
    run = RunConfig(fl=fl, wire=config["wire"],
                    fused_decode=config["fused"],
                    client_parallel=config["fanout"],
                    mesh=ctx["mesh"] if shard else None)
    codec = None
    if config["wire"] == "codec":
        codec = make_codec(ccfg, ctx["params"], syn_spec=spec,
                           syn_loss_fn=ctx["model"].syn_loss)
    sched = (lambda r, n: F.null_schedule(n)) if config["faulted"] else None
    rf = build_fl_round(ctx["model"].loss, strat, run,
                        codec=codec, fault_schedule_fn=sched)
    state = fl_init(ctx["params"], TINY_N, strat)

    jit_kwargs: Dict[str, Any] = {"donate_argnums": (0,)}
    if shard:
        sh = ctx["sh"]
        jit_kwargs.update(
            in_shardings=(sh.state, sh.client, sh.replicated),
            out_shardings=(sh.state, sh.replicated))
    compiled = jax.jit(rf, **jit_kwargs).lower(
        state, ctx["batches"], ctx["key"]).compile()

    n_p = len(jax.tree_util.tree_leaves(state.params))
    n_e = len(jax.tree_util.tree_leaves(state.ef))
    shards = ctx["client_shards"] if shard else 1
    payload = None
    if config["fused"]:
        payload = (4.0 * float(strat.payload_floats(ctx["params"]))
                   * (TINY_N // shards))
    return contracts.RoundArtifact(
        config=dict(config),
        hlo_text=compiled.as_text(),
        ef_param_indices=tuple(range(n_p, n_p + n_e)),
        payload_bytes_local=payload,
        codec_nbytes=(codec.nbytes if codec is not None else None),
        codec_policy=(codec.policy if codec is not None else None),
        num_clients=TINY_N,
        client_shards=shards)


def run_matrix(configs: Optional[List[Dict[str, Any]]] = None,
               verbose: bool = True) -> Dict[str, Any]:
    """Compile every matrix point and evaluate the contracts in-process."""
    if configs is None:
        configs = iter_round_configs()
    ctx = build_context()
    artifacts: List[contracts.RoundArtifact] = []
    for i, cfg in enumerate(configs):
        a = build_round_artifact(cfg, ctx)
        artifacts.append(a)
        if verbose:
            print(f"  [{i + 1}/{len(configs)}] compiled {a.label}",
                  file=sys.stderr)
    return contracts.run_contracts(artifacts)


def main() -> None:
    report = run_matrix()
    json.dump(report, sys.stdout)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
