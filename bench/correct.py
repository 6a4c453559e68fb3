"""The comparison that decides ``correct``.

The program's first ``STEPS`` eval blocks (a step is one call of the
window, ``eval_every`` rounds) are driven in set-up through the window's
own call; the reference follows the same blocks from the seed. Each side is
reduced to a summary: the per-round mean local loss and aggregate norm (the
program's ``RoundMetrics.loss`` and ``update_norm``, as the window's call
returns them) and, per parameter leaf, the norm of the change
``w_0 - w_b`` at each block boundary ``b``. The numbers a cell compares
are those its ``bench/workloads/<cell>.json`` gives a limit:

* ``loss0_gap``: the relative gap of round 0's loss, before any compressed
  update has moved the parameters (batch gather, local training);
* ``loss1_gap``: the same for round 1, the first loss after the server
  applied round 0's aggregate (server update, the carried state);
* ``agg0_gap``: the relative gap of round 0's aggregate norm, the mean of
  the clients' reconstructed messages (the strategy's encode with its
  kernels, the codec, the fan-out and its exchange, the server aggregate);
* ``update_gap``: the first block's change ``w_0 - w_1`` (what the server
  applied, as its state shows after one step), by the worst leaf:
  ``| |d_prog| - |d_ref| |`` over the larger of that leaf's ``|d_ref|`` and
  the median leaf's. A step that returns its state unchanged reads 1.

3SFC amplifies a single rounding over rounds: the synthesis takes another
path and the trajectory drifts, so numbers of later rounds and blocks read
the same for the program as for the reference with one summation reordered
(PERF.md). The per-round numbers therefore come from rounds 0 and 1 only,
and one step is compared.

Leaves whose reference update is under a thousandth of the median leaf's
(nought to rounding) are left out of the worst leaf. A number that is not
finite fails.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

STEPS = 1
NUMBERS = ("loss0_gap", "loss1_gap", "agg0_gap", "update_gap")


def summarize(reading: Dict) -> Dict:
    """``reading``: ``{"loss": (STEPS * every,), "agg": (STEPS * every,),
    "params": [w_0, ..., w_STEPS]}`` with parameters as
    ``{"layer/leaf": array}``."""
    p = reading["params"]
    norms = [{k: float(np.linalg.norm((p[0][k].astype(np.float64)
                                        - p[b][k].astype(np.float64)).ravel()))
              for k in sorted(p[0])} for b in range(1, len(p))]
    return {"loss": [float(x) for x in reading["loss"]],
            "agg": [float(x) for x in reading["agg"]], "norms": norms}


def _rel(a: float, b: float) -> float:
    g = float(abs(a - b) / abs(b))
    return g if math.isfinite(g) else math.inf


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float], keep) -> float:
    med = float(np.median(list(ref.values())))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep]
    return max(gaps) if all(math.isfinite(g) for g in gaps) else math.inf


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Every number of ``NUMBERS``, from two summaries."""
    if set(prog["norms"][0]) != set(ref["norms"][0]):
        raise ValueError(f"program leaves {sorted(prog['norms'][0])} are not "
                         f"the reference's {sorted(ref['norms'][0])}")
    lp, lr = prog["loss"], ref["loss"]
    first = ref["norms"][0]
    med = float(np.median(list(first.values())))
    keep = [k for k, v in first.items() if v >= 1e-3 * med]
    return {
        "loss0_gap": _rel(lp[0], lr[0]),
        "loss1_gap": _rel(lp[1], lr[1]),
        "agg0_gap": _rel(prog["agg"][0], ref["agg"][0]),
        "update_gap": _worst_leaf(prog["norms"][0], first, keep),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number the cell has a limit for is at or under it."""
    return all(values[k] <= limit for k, limit in limits.items())


def report(values: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    """One line per number compared, its value beside its limit."""
    return [f"{k} {values[k]!r} limit {limit!r}" for k, limit in limits.items()]
