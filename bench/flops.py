"""Operations a round requires, from the shapes in the configuration and
the algorithm in the traffic file; nothing here looks at the program.

A forward pass counts 2 operations per multiply-add of its dense and
convolution layers (biases, activations and pooling are left out). Per
client and round:

* local training: ``3 * F * K * B`` (forward and backward, ``F`` the
  forward operations per sample);
* 3SFC encode (Algorithm 1 at synthetic batch ``n``): ``S`` steps of
  gradient descent on the synthetic batch, each ``3 * E`` where
  ``E = 3 * F * n + 6 * d`` is one objective evaluation (a forward and
  backward at the synthetic batch and three dot products over the ``d``
  parameters), since the step differentiates through that evaluation;
  then one more evaluation ``E``; then ``2 * d`` for ``s * grad`` and
  ``2 * d`` for the error-feedback residual ``u - s * grad``;
* signSGD encode: ``d`` for ``|u|`` and its mean, ``d`` for the sign and
  scale, ``d`` for the residual;
* ``d`` for ``u = g + e``.

Per round, the server adds ``N * d`` for the mean of the messages and
``d`` for the update.

This module counts the ``vision`` family. Any other family brings its own
count as ``bench/families/<family>_flops.py`` with the same two functions,
``param_count(config)`` and ``round_flops(config, traffic)``;
``for_family`` finds it by the configuration's ``family``.
"""
from __future__ import annotations

import importlib
import math
import os
import sys
from types import ModuleType
from typing import Dict, List, Optional

from bench import spec


def for_family(config: Dict) -> Optional[ModuleType]:
    """The module that counts ``config``'s family: this one for ``vision``,
    else ``bench/families/<family>_flops.py``, or None where there is no
    such file."""
    family = config["family"]
    if family == "vision":
        return sys.modules[__name__]
    if not os.path.exists(spec.family_path(f"{family}_flops")):
        return None
    return importlib.import_module(f"bench.families.{family}_flops")


def _out_hw(h: int, stride: int) -> int:
    return math.ceil(h / stride)          # SAME padding


def layer_flops(config: Dict) -> List[int]:
    """Forward operations per sample of each dense/conv layer, in order."""
    h, w, _ = config["input_shape"]
    out = []
    for L in config["layers"]:
        if L["kind"] == "dense":
            out.append(2 * L["in"] * L["out"])
        elif L["kind"] == "conv":
            h, w = _out_hw(h, L["stride"]), _out_hw(w, L["stride"])
            out.append(2 * h * w * L["k"] * L["k"] * L["cin"] * L["cout"])
    return out


def forward_flops(config: Dict) -> int:
    return sum(layer_flops(config))


def param_count(config: Dict) -> int:
    d = 0
    for L in config["layers"]:
        if L["kind"] == "dense":
            d += L["in"] * L["out"] + L["out"]
        elif L["kind"] == "conv":
            d += L["k"] * L["k"] * L["cin"] * L["cout"] + L["cout"]
    return d


def encode_flops(config: Dict, traffic: Dict) -> int:
    """One client's compression of its update, error feedback included."""
    d = param_count(config)
    if traffic["strategy"] == "threesfc":
        ev = 3 * forward_flops(config) * traffic["syn_batch"] + 6 * d
        return traffic["syn_steps"] * 3 * ev + ev + 4 * d + d
    if traffic["strategy"] == "signsgd":
        return 3 * d + d
    raise ValueError(f"no operation count for strategy "
                     f"{traffic['strategy']!r}")


def round_flops(config: Dict, traffic: Dict) -> int:
    n = traffic["clients"]
    d = param_count(config)
    local = 3 * forward_flops(config) * traffic["local_steps"] * traffic["batch"]
    return n * (local + encode_flops(config, traffic)) + n * d + d
