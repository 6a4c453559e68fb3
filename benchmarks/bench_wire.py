"""Measured wire bytes: the codec subsystem's end-to-end gate.

Everything the repo previously *accounted* as float counts is serialized
here into real framed ``uint8`` buffers (``repro.comm``) at the paper
MLP/MNIST shapes (d = 199,210) and measured. Gated:

* **round trip**: ``decode(encode(payload))`` is bit-exact for all five
  compressors on a realistic client update (one K=5 local-train), and the
  decoded server reconstruction equals the client's dequantized view
  bitwise (threesfc: the Eq. 10 server recompute, ≤ 1e-5);
* **signSGD budget**: measured uplink ≤ ceil(d/8) + per-leaf scales +
  header — ONE bit per coordinate actually on the wire;
* **3SFC budget**: measured uplink within 2% of the accounted
  4·(795+1) bytes + header;
* **measured vs accounted**: the ratio is recorded per method (DGC's
  ``ceil(log2 d)``-bit indices beat the "2k floats" convention; identity's
  header is the only overhead);
* **round parity**: 3 scanned engine rounds in ``wire='codec'`` mode equal
  float mode bitwise — params, EF, every shared metric — for
  fedavg/dgc/stc/threesfc (default AND fused 3SFC decode). signSGD is the
  documented exception: a 3-valued sign does not fit in the 1-bit wire, so
  coordinates that are *exactly* zero decode to +scale; the bench measures
  the zero fraction and the resulting divergence instead of pretending the
  float convention was serializable (the wire path itself is
  self-consistent: client EF uses the same ±1 view the server decodes).

Also exercises ``comm.channel.InProcessChannel``: one round's frames move
client->server through it and the uplink counters must bill exactly
N · nbytes. Deterministic end to end — ``--quick`` == ``--full``. Writes
``experiments/results/wire.json``.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

N_CLIENTS = 8
LOCAL_STEPS, LOCAL_BATCH = 5, 32       # paper MLP/MNIST round shape
PARITY_ROUNDS = 3
PARITY_K, PARITY_B = 2, 8
THREESFC_RECON_TOL = 1e-5
BITWISE_KINDS = ("fedavg", "dgc", "stc", "threesfc")


def _tree_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _tree_maxdiff(a, b) -> float:
    diffs = [float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                   - y.astype(jnp.float32))))
             for x, y in zip(jax.tree_util.tree_leaves(a),
                             jax.tree_util.tree_leaves(b))]
    return max(diffs) if diffs else 0.0


def _measure(model, params, d, kinds, syn_specs) -> Dict:
    """Serialize one realistic client update per method and measure it."""
    from repro.comm import InProcessChannel, parse_header
    from repro.core import flat
    from repro.core.strategy import make_strategy
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.client import local_train
    from repro.models.cnn import MNIST_SPEC

    ds = make_class_image_dataset(jax.random.PRNGKey(11), 256,
                                  MNIST_SPEC.input_shape, 10)
    idx = jax.random.randint(jax.random.PRNGKey(12), (LOCAL_STEPS, LOCAL_BATCH),
                             0, 256)
    batches = {"x": jnp.asarray(ds.x)[idx], "y": jnp.asarray(ds.y)[idx]}
    u, _ = local_train(model.loss, params, batches, 0.01)

    per_method: Dict[str, Dict] = {}
    for name, ccfg in kinds.items():
        spec = syn_specs[name]
        strat = make_strategy(ccfg, loss_fn=model.syn_loss, syn_spec=spec,
                              local_lr=0.01)
        codec = strat.wire_codec(params)
        out = strat.client_encode(jax.random.PRNGKey(13), u, params)
        buf = jax.jit(lambda w: codec.encode(w, round_idx=3, client_idx=1))(
            out.wire)
        hdr = parse_header(np.asarray(buf))
        assert hdr["kind"] == ccfg.kind and hdr["round"] == 3 \
            and hdr["client"] == 1, hdr

        canon = codec.decode(buf)
        # THE round-trip gate: the decoded payload must equal the byte-free
        # canonical oracle bitwise (a symmetric pack/unpack bug cannot hide)
        roundtrip = _tree_equal(canon, codec.canonical(out.wire))
        # ... in eager as in jit, and encode must be deterministic
        canon2 = codec.decode(codec.encode(out.wire, 3, 1))   # eager
        jit_eager_stable = _tree_equal(canon, canon2)
        buf2 = codec.encode(out.wire, round_idx=3, client_idx=1)
        enc_deterministic = bool(np.array_equal(np.asarray(buf),
                                                np.asarray(buf2)))

        recon_dec = codec.recon_tree(canon, params)
        recon_cli, direction, scale = codec.client_view(out)
        if direction is not None:                 # threesfc: factored client
            recon_cli = flat.tree_scale(direction, scale)
            recon_diff = _tree_maxdiff(recon_cli, recon_dec)
            recon_ok = recon_diff <= THREESFC_RECON_TOL
        else:
            recon_diff = _tree_maxdiff(recon_cli, recon_dec)
            recon_ok = _tree_equal(recon_cli, recon_dec)

        accounted_floats = strat.payload_floats(params)
        # stc shares signsgd's 1-bit sign semantics: a kept value that is
        # exactly zero would decode to +mu where the float path writes 0.
        # Count them so a future parity divergence is attributable (today:
        # 0 — top-k only reaches zeros when a leaf has fewer than k
        # nonzeros, which the paper shapes never do).
        zero_kept = None
        if name == "stc":
            zero_kept = int(sum(int(jnp.sum(sgn == 0.0))
                                for sgn, _, _ in out.wire))
        per_method[name] = {
            "measured_bytes": int(codec.nbytes),
            "header_bytes": int(codec.header_bytes),
            "payload_bytes": int(codec.nbytes - codec.header_bytes),
            "header_overhead": codec.header_bytes / codec.nbytes,
            "accounted_floats": float(accounted_floats),
            "accounted_bytes": 4.0 * float(accounted_floats),
            "measured_over_accounted":
                codec.nbytes / (4.0 * float(accounted_floats)),
            "roundtrip_bitexact": bool(roundtrip and jit_eager_stable
                                       and enc_deterministic),
            "recon_consistent": bool(recon_ok),
            "recon_maxdiff": float(recon_diff),
        }
        if zero_kept is not None:
            per_method[name]["zero_kept_values"] = zero_kept

    # the channel bills exactly one frame per client
    ch = InProcessChannel()
    ch.begin_round()
    strat = make_strategy(kinds["threesfc"], loss_fn=model.syn_loss,
                          syn_spec=syn_specs["threesfc"], local_lr=0.01)
    codec = strat.wire_codec(params)
    out = strat.client_encode(jax.random.PRNGKey(14), u, params)
    for c in range(N_CLIENTS):
        ch.send_up(codec.encode(out.wire, round_idx=0, client_idx=c))
    channel = {
        "uplink_bytes_per_round": ch.uplink.per_round[0],
        "expected": N_CLIENTS * codec.nbytes,
        "messages": ch.uplink.messages,
    }

    # exact zeros in the realistic update: the signsgd 1-bit caveat, measured
    zeros = sum(int(jnp.sum(l == 0.0)) for l in jax.tree_util.tree_leaves(u))
    return {"methods": per_method, "channel": channel,
            "update_zero_coords": zeros, "update_zero_fraction": zeros / d}


def _parity(model, params, kinds, syn_specs) -> Dict:
    """wire='codec' engine rounds vs the float oracle, 3 scanned rounds."""
    from repro.configs.base import FLConfig
    from repro.configs.run import RunConfig
    from repro.core.strategy import make_strategy
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.engine import RoundEngine, device_pools, vision_batcher
    from repro.fl.round import build_fl_round
    from repro.models.cnn import MNIST_SPEC

    train = make_class_image_dataset(jax.random.PRNGKey(1), 400,
                                     MNIST_SPEC.input_shape, 10)
    parts = dirichlet_partition(train.y, N_CLIENTS, alpha=0.5, seed=0,
                                min_per_client=16)

    def run3(ccfg, spec, wire, fused=False):
        strat = make_strategy(ccfg, loss_fn=model.syn_loss, syn_spec=spec,
                              local_lr=0.05)
        cfg = FLConfig(num_clients=N_CLIENTS, local_steps=PARITY_K,
                       local_lr=0.05, local_batch=PARITY_B, compressor=ccfg)
        run = RunConfig(fl=cfg, wire=wire, fused_decode=fused)
        codec = strat.wire_codec(params) if wire == "codec" else None
        eng = RoundEngine(
            build_fl_round(model.loss, strat, run, codec=codec),
            vision_batcher(train.x, train.y, device_pools(parts),
                           PARITY_K, PARITY_B), seed=0)
        return eng.run_block(eng.init_state(params, N_CLIENTS), PARITY_ROUNDS)

    shared = ("loss", "cosine", "payload_floats", "update_norm")
    out: Dict[str, Dict] = {}
    for name, ccfg in kinds.items():
        spec = syn_specs[name]
        sf, mf = run3(ccfg, spec, "float")
        sw, mw = run3(ccfg, spec, "codec")
        rec = {
            "params_bitexact": _tree_equal(sf.params, sw.params),
            "ef_bitexact": _tree_equal(sf.ef, sw.ef),
            "metrics_bitexact": all(
                np.array_equal(np.asarray(getattr(mf, f)),
                               np.asarray(getattr(mw, f))) for f in shared),
            "max_abs_param_diff": _tree_maxdiff(sf.params, sw.params),
            "wire_bytes_up": float(np.asarray(mw.wire_bytes_up)[0]),
        }
        if name == "threesfc":
            s1, _ = run3(ccfg, spec, "float", fused=True)
            s2, m2 = run3(ccfg, spec, "codec", fused=True)
            rec["fused_params_bitexact"] = _tree_equal(s1.params, s2.params)
            rec["fused_ef_bitexact"] = _tree_equal(s1.ef, s2.ef)
            rec["fused_wire_bytes_up"] = float(np.asarray(m2.wire_bytes_up)[0])
        out[name] = rec
    return out


def _gate(results: Dict, d: int, n_leaves: int) -> Dict:
    m = results["measure"]["methods"]
    results["pass_roundtrip"] = bool(
        all(m[k]["roundtrip_bitexact"] for k in m))
    results["pass_recon_consistency"] = bool(
        all(m[k]["recon_consistent"] for k in m))
    sign_budget = -(-d // 8) + 4 * n_leaves + m["signsgd"]["header_bytes"]
    results["signsgd_byte_budget"] = sign_budget
    results["pass_signsgd_bytes"] = bool(
        m["signsgd"]["measured_bytes"] <= sign_budget)
    target = 4.0 * (795 + 1)                       # paper MLP/MNIST budget
    results["threesfc_byte_target"] = target + m["threesfc"]["header_bytes"]
    results["pass_threesfc_bytes"] = bool(
        abs(m["threesfc"]["measured_bytes"]
            - (target + m["threesfc"]["header_bytes"])) <= 0.02 * target)
    p = results["parity"]
    results["pass_round_parity"] = bool(
        all(p[k]["params_bitexact"] and p[k]["ef_bitexact"]
            and p[k]["metrics_bitexact"] for k in BITWISE_KINDS)
        and p["threesfc"]["fused_params_bitexact"]
        and p["threesfc"]["fused_ef_bitexact"])
    ch = results["measure"]["channel"]
    results["pass_channel_accounting"] = bool(
        ch["uplink_bytes_per_round"] == ch["expected"])
    results["pass"] = all(results[k] for k in (
        "pass_roundtrip", "pass_recon_consistency", "pass_signsgd_bytes",
        "pass_threesfc_bytes", "pass_round_parity",
        "pass_channel_accounting"))
    return results


def run(quick: bool = True, out_dir: str = "experiments/results") -> Dict:
    # deterministic end to end: quick == full (orchestrator symmetry only)
    del quick
    from repro.core import flat
    from repro.fl.budget import matched_compressors
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import MNIST_SPEC, make_paper_model

    model = make_paper_model("mlp", MNIST_SPEC)
    params = model.init(jax.random.PRNGKey(0))
    d = flat.tree_size(params)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    kinds = matched_compressors("mlp", MNIST_SPEC, d)
    syn_specs = {k: vision_syn_spec(MNIST_SPEC, c) for k, c in kinds.items()}

    print("serializing one client-round per method (mlp/mnist, "
          f"d={d})...")
    measure = _measure(model, params, d, kinds, syn_specs)
    print("wire == float parity, 3 scanned rounds per method...")
    parity = _parity(model, params, kinds, syn_specs)

    results = _gate({
        "config": {
            "model": "mlp", "dataset": "mnist", "model_params": d,
            "num_leaves": n_leaves, "num_clients": N_CLIENTS,
            "local_steps": LOCAL_STEPS, "local_batch": LOCAL_BATCH,
            "parity_rounds": PARITY_ROUNDS,
        },
        "measure": measure,
        "parity": parity,
    }, d, n_leaves)

    m = measure["methods"]
    print(f"\n== Measured wire bytes per client-round (mlp/mnist, d={d}) ==")
    print(f"  {'method':9s} {'measured':>9s} {'accounted':>10s} "
          f"{'ratio':>6s} {'header':>7s}")
    for k, r in m.items():
        print(f"  {k:9s} {r['measured_bytes']:9d} "
              f"{r['accounted_bytes']:10.0f} "
              f"{r['measured_over_accounted']:6.3f} "
              f"{r['header_bytes']:5d} B")
    print(f"  [{'PASS' if results['pass_roundtrip'] else 'FAIL'}] "
          f"decode(encode(payload)) bit-exact for all five compressors")
    print(f"  [{'PASS' if results['pass_recon_consistency'] else 'FAIL'}] "
          f"decoded server recon == client dequantized view (threesfc "
          f"<= {THREESFC_RECON_TOL:.0e}, measured "
          f"{m['threesfc']['recon_maxdiff']:.1e})")
    print(f"  [{'PASS' if results['pass_signsgd_bytes'] else 'FAIL'}] "
          f"signsgd uplink {m['signsgd']['measured_bytes']} B <= "
          f"ceil(d/8) + scales + header = {results['signsgd_byte_budget']} B "
          f"(1 bit/coord, measured)")
    print(f"  [{'PASS' if results['pass_threesfc_bytes'] else 'FAIL'}] "
          f"threesfc uplink {m['threesfc']['measured_bytes']} B within 2% "
          f"of 4*(795+1) + header = {results['threesfc_byte_target']:.0f} B")
    pr = parity
    print(f"  [{'PASS' if results['pass_round_parity'] else 'FAIL'}] "
          f"wire-mode rounds == float-mode rounds over {PARITY_ROUNDS} "
          f"scanned rounds (bitwise: {', '.join(BITWISE_KINDS)} + fused "
          f"threesfc)")
    print(f"         signsgd (1-bit wire, documented): "
          f"max |dparams| = {pr['signsgd']['max_abs_param_diff']:.1e}, "
          f"update zero-coord fraction = "
          f"{measure['update_zero_fraction']:.2e}")
    print(f"  [{'PASS' if results['pass_channel_accounting'] else 'FAIL'}] "
          f"channel bills exactly N*nbytes "
          f"({measure['channel']['uplink_bytes_per_round']} B/round)")

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "wire.json"), "w") as f:
        json.dump(results, f, indent=2)
    return results


def main():
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--quick", dest="quick", action="store_true", default=True,
                   help="accepted for orchestrator symmetry; the measurement "
                        "is deterministic, quick == full")
    g.add_argument("--full", dest="quick", action="store_false")
    args = ap.parse_args()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
