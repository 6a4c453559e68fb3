"""Fault-injection harness: convergence under dropout/staleness + the
zero-fault bitwise gate.

Two claims are measured and gated at reduced MLP/MNIST shapes:

* **zero-fault bitwise**: the masked fault pipeline under a null schedule
  (forced via ``build_fl_round``'s ``fault_schedule_fn`` seam) produces
  bit-for-bit the params AND EF state of the unfaulted round for
  fedavg/threesfc/signsgd — turning the fault machinery on costs nothing
  when there are no faults, by IEEE identity rather than by luck;
* **graceful degradation**: with the server renormalizing over arrivals and
  client EF banking dropped payloads, fedavg and threesfc still reach the
  zero-fault target loss under 30% dropout within 2x the zero-fault
  round count (rounds-to-target, measured on the smoothed loss curve).

The full grid — {fedavg, threesfc, signsgd} x dropout {0, 30, 50%} x
staleness k in {0, 2} (k=2 adds 40% stragglers, late payloads weighted
1/(1+delay)) — is recorded for the table; only the 30%-dropout column is
gated (50% dropout and staleness are reported, not promised). Fault
schedules are a pure function of (fault_seed, round), so every cell is
deterministic — ``--quick`` differs from ``--full`` only in rounds. Writes
``experiments/results/faults.json``.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, Optional

import jax
import numpy as np

N_CLIENTS = 8
LOCAL_STEPS, LOCAL_BATCH = 2, 16
DROPOUTS = (0.0, 0.3, 0.5)
STALENESS = (0, 2)
STRAGGLER_RATE = 0.4          # only in the k=2 cells
FAULT_SEED = 17
SMOOTH = 3                    # rounds-to-target on a 3-round moving average


def _methods():
    from repro.configs.base import CompressorConfig

    return {
        "fedavg": CompressorConfig(kind="identity", error_feedback=False),
        "threesfc": CompressorConfig(kind="threesfc", syn_steps=3,
                                     syn_lr=0.1),
        "signsgd": CompressorConfig(kind="signsgd"),
    }


def _world(train_size: int):
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_class_image_dataset
    from repro.models.cnn import MNIST_SPEC, make_paper_model

    model = make_paper_model("mlp", MNIST_SPEC)
    params = model.init(jax.random.PRNGKey(0))
    train = make_class_image_dataset(jax.random.PRNGKey(1), train_size,
                                     MNIST_SPEC.input_shape, 10)
    parts = dirichlet_partition(train.y, N_CLIENTS, alpha=0.5, seed=0,
                                min_per_client=LOCAL_BATCH)
    return model, params, train, parts


def _run_cell(model, params, train, parts, ccfg, rounds: int, *,
              drop: float = 0.0, k: int = 0, sched_fn=None) -> Dict:
    """One (method, fault-config) trajectory: the stacked per-round loss
    curve and mean arrivals, from ONE scanned dispatch."""
    from repro.configs.base import FLConfig
    from repro.configs.run import RunConfig
    from repro.core.strategy import make_strategy
    from repro.fl.engine import RoundEngine, device_pools, vision_batcher
    from repro.fl.round import build_fl_round
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import MNIST_SPEC

    spec = vision_syn_spec(MNIST_SPEC, ccfg)
    strat = make_strategy(ccfg, loss_fn=model.syn_loss, syn_spec=spec,
                          local_lr=0.05)
    cfg = FLConfig(num_clients=N_CLIENTS, local_steps=LOCAL_STEPS,
                   local_lr=0.05, local_batch=LOCAL_BATCH, compressor=ccfg)
    run = RunConfig(fl=cfg, drop_rate=drop, fault_seed=FAULT_SEED,
                    straggler_rate=STRAGGLER_RATE if k > 0 else 0.0,
                    staleness_max=k)
    eng = RoundEngine(
        build_fl_round(model.loss, strat, run, fault_schedule_fn=sched_fn),
        vision_batcher(train.x, train.y, device_pools(parts),
                       LOCAL_STEPS, LOCAL_BATCH), seed=0)
    state = eng.init_state(params, N_CLIENTS, strat,
                           staleness_max=run.staleness_max)
    state, ms = eng.run_block(state, rounds)
    return {
        "state": state,
        "loss": np.asarray(ms.loss, np.float64),
        "arrivals_mean": float(np.mean(np.asarray(ms.arrivals))),
    }


def _rounds_to_target(loss: np.ndarray, target: float) -> Optional[int]:
    """First round (1-based) where the SMOOTH-round trailing mean of the
    participant loss crosses the target; None = never within the run."""
    smooth = np.convolve(loss, np.ones(SMOOTH) / SMOOTH, mode="valid")
    hits = np.nonzero(smooth <= target)[0]
    return int(hits[0]) + SMOOTH if hits.size else None


def _bitwise_gate(model, params, train, parts, kinds) -> Dict:
    """Masked pipeline + null schedule vs the unfaulted round, 2 rounds."""
    from repro.fl import faults as F

    out = {}
    for name, ccfg in kinds.items():
        plain = _run_cell(model, params, train, parts, ccfg, 2)
        null = _run_cell(model, params, train, parts, ccfg, 2,
                         sched_fn=lambda r, n: F.null_schedule(n))
        eq = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for tree in (("params",), ("ef",))
            for a, b in zip(
                jax.tree_util.tree_leaves(getattr(plain["state"], tree[0])),
                jax.tree_util.tree_leaves(getattr(null["state"], tree[0]))))
        out[name] = bool(
            eq and np.array_equal(plain["loss"], null["loss"]))
    return out


def run(quick: bool = True, out_dir: str = "experiments/results") -> Dict:
    rounds = 24 if quick else 60
    target_round = max(rounds * 2 // 5, SMOOTH)   # 2x headroom fits the run
    kinds = _methods()
    model, params, train, parts = _world(800 if quick else 2000)

    print(f"zero-fault bitwise gate (2 rounds x {len(kinds)} methods)...")
    bitwise = _bitwise_gate(model, params, train, parts, kinds)

    grid: Dict[str, Dict] = {}
    targets: Dict[str, float] = {}
    for name, ccfg in kinds.items():
        grid[name] = {}
        for k in STALENESS:
            for drop in DROPOUTS:
                cell = f"drop{int(drop * 100)}_k{k}"
                print(f"{name}: {cell} ({rounds} rounds)...")
                r = _run_cell(model, params, train, parts, ccfg, rounds,
                              drop=drop, k=k)
                rec = {"final_loss": float(r["loss"][-1]),
                       "arrivals_mean": r["arrivals_mean"],
                       "loss_curve": [round(float(x), 4) for x in r["loss"]]}
                if drop == 0.0 and k == 0:
                    # the method's own healthy run sets its target
                    smooth = np.convolve(r["loss"],
                                         np.ones(SMOOTH) / SMOOTH, "valid")
                    targets[name] = float(smooth[target_round - SMOOTH])
                rec["rounds_to_target"] = _rounds_to_target(
                    r["loss"], targets[name])
                grid[name][cell] = rec

    results: Dict = {
        "config": {
            "model": "mlp", "dataset": "mnist", "num_clients": N_CLIENTS,
            "local_steps": LOCAL_STEPS, "local_batch": LOCAL_BATCH,
            "rounds": rounds, "dropouts": list(DROPOUTS),
            "staleness": list(STALENESS), "straggler_rate": STRAGGLER_RATE,
            "fault_seed": FAULT_SEED, "smooth": SMOOTH,
        },
        "targets": targets,
        "zero_fault_bitwise": bitwise,
        "grid": grid,
    }

    results["pass_zero_fault_bitwise"] = bool(all(bitwise.values()))
    gate_30 = {}
    for name in ("fedavg", "threesfc"):
        r0 = grid[name]["drop0_k0"]["rounds_to_target"]
        r30 = grid[name]["drop30_k0"]["rounds_to_target"]
        gate_30[name] = bool(r0 is not None and r30 is not None
                             and r30 <= 2 * r0)
    results["gate_30_dropout"] = gate_30
    results["pass_dropout_convergence"] = bool(all(gate_30.values()))
    results["pass"] = bool(results["pass_zero_fault_bitwise"]
                           and results["pass_dropout_convergence"])

    print(f"\n== Rounds to zero-fault target loss (mlp/mnist, "
          f"{rounds} rounds, target @ round {target_round}) ==")
    print(f"  {'method':9s} {'target':>7s} "
          + " ".join(f"{f'd{int(d*100)}/k{k}':>8s}"
                     for k in STALENESS for d in DROPOUTS))
    for name in kinds:
        cells = " ".join(
            f"{str(grid[name][f'drop{int(d*100)}_k{k}']['rounds_to_target'] or '-'):>8s}"
            for k in STALENESS for d in DROPOUTS)
        print(f"  {name:9s} {targets[name]:7.4f} {cells}")
    print(f"  [{'PASS' if results['pass_zero_fault_bitwise'] else 'FAIL'}] "
          f"null fault schedule == unfaulted round, bitwise params+EF+loss "
          f"({', '.join(k for k, v in bitwise.items() if v) or 'none'})")
    print(f"  [{'PASS' if results['pass_dropout_convergence'] else 'FAIL'}] "
          f"fedavg+threesfc reach the zero-fault target under 30% dropout "
          f"within 2x the zero-fault rounds")

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "faults.json"), "w") as f:
        json.dump(results, f, indent=2)
    return results


def main():
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--quick", dest="quick", action="store_true", default=True)
    g.add_argument("--full", dest="quick", action="store_false")
    args = ap.parse_args()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
