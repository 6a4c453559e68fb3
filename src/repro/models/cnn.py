"""The paper's five small vision models, reproduced in JAX.

* MLP        — 784-200-200-10 (199,210 params, matching the paper's count)
* MnistNet   — 2 conv + 2 linear (classic MNIST net)
* ConvNet    — 4 conv + 1 linear
* ResNet     — BN/dropout-free residual net (paper §5 deletes BN/dropout)
* RegNet     — BN-free simplified RegNet stem+stages

All share the facade: ``init(key)``, ``apply(params, x) -> logits``,
``loss(params, batch)`` (softmax CE on int labels), ``syn_loss(params, syn)``
(soft-label CE on synthetic pixels — the 3SFC payload for classifiers).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.threesfc import SynData, soft_xent
from repro.models import layers
from repro.models import params as P_

PyTree = Any


class VisionSpec(NamedTuple):
    name: str
    input_shape: Tuple[int, int, int]     # (H, W, C)
    num_classes: int


def xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))


class VisionModel:
    """Facade wrapping an (init_fn, apply_fn) pair."""

    def __init__(self, spec: VisionSpec, init_fn, apply_fn):
        self.spec = spec
        self._init = init_fn
        self._apply = apply_fn

    def init(self, key) -> PyTree:
        return self._init(key)

    def apply(self, params: PyTree, x: jax.Array) -> jax.Array:
        return self._apply(params, x)

    def loss(self, params: PyTree, batch: Dict[str, jax.Array]) -> jax.Array:
        return xent(self._apply(params, batch["x"]), batch["y"])

    def syn_loss(self, params: PyTree, syn: SynData) -> jax.Array:
        return soft_xent(self._apply(params, syn.x), syn.labels())


# ---------------------------------------------------------------------------
# MLP — 784-200-200-10 = 199,210 params (paper Fig. 1)
# ---------------------------------------------------------------------------


def make_mlp(spec: VisionSpec, hidden: int = 200) -> VisionModel:
    d_in = int(np.prod(spec.input_shape))

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "l1": {"w": P_.dense_init(k1, d_in, (d_in, hidden)), "b": jnp.zeros((hidden,))},
            "l2": {"w": P_.dense_init(k2, hidden, (hidden, hidden)), "b": jnp.zeros((hidden,))},
            "l3": {"w": P_.dense_init(k3, hidden, (hidden, spec.num_classes)),
                   "b": jnp.zeros((spec.num_classes,))},
        }

    def apply(p, x):
        h = x.reshape(x.shape[0], -1)
        h = jax.nn.relu(h @ p["l1"]["w"] + p["l1"]["b"])
        h = jax.nn.relu(h @ p["l2"]["w"] + p["l2"]["b"])
        return h @ p["l3"]["w"] + p["l3"]["b"]

    return VisionModel(spec, init, apply)


# ---------------------------------------------------------------------------
# MnistNet — conv(10,5x5) conv(20,5x5) fc(50) fc(C)
# ---------------------------------------------------------------------------


def make_mnistnet(spec: VisionSpec) -> VisionModel:
    H, W, C = spec.input_shape

    def init(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        flat = (H // 4) * (W // 4) * 20
        return {
            "c1": layers.conv2d_init(k1, C, 10, 5),
            "c2": layers.conv2d_init(k2, 10, 20, 5),
            "f1": {"w": P_.dense_init(k3, flat, (flat, 50)), "b": jnp.zeros((50,))},
            "f2": {"w": P_.dense_init(k4, 50, (50, spec.num_classes)),
                   "b": jnp.zeros((spec.num_classes,))},
        }

    def apply(p, x):
        h = layers.conv2d(p["c1"], x, act=jax.nn.relu)
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        h = layers.conv2d(p["c2"], h, act=jax.nn.relu)
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        h = h.reshape(h.shape[0], -1)
        h = jax.nn.relu(h @ p["f1"]["w"] + p["f1"]["b"])
        return h @ p["f2"]["w"] + p["f2"]["b"]

    return VisionModel(spec, init, apply)


# ---------------------------------------------------------------------------
# ConvNet — 4 conv + 1 linear
# ---------------------------------------------------------------------------


def make_convnet(spec: VisionSpec, widths=(32, 64, 128, 256)) -> VisionModel:
    H, W, C = spec.input_shape

    def init(key):
        ks = jax.random.split(key, 5)
        p = {}
        cin = C
        for i, w in enumerate(widths):
            p[f"c{i}"] = layers.conv2d_init(ks[i], cin, w, 3)
            cin = w
        p["fc"] = {"w": P_.dense_init(ks[4], cin, (cin, spec.num_classes)),
                   "b": jnp.zeros((spec.num_classes,))}
        return p

    def apply(p, x):
        h = x
        for i in range(len(widths)):
            h = layers.conv2d(p[f"c{i}"], h, stride=2 if i else 1,
                              act=jax.nn.relu)
        h = jnp.mean(h, axis=(1, 2))
        return h @ p["fc"]["w"] + p["fc"]["b"]

    return VisionModel(spec, init, apply)


# ---------------------------------------------------------------------------
# ResNet (BN-free) — basic blocks, widths scalable for CPU runtime
# ---------------------------------------------------------------------------


def make_resnet(spec: VisionSpec, widths=(16, 32, 64), blocks_per_stage: int = 1) -> VisionModel:
    H, W, C = spec.input_shape

    def init(key):
        keys = jax.random.split(key, 2 + 3 * len(widths) * blocks_per_stage + len(widths))
        it = iter(keys)
        p = {"stem": layers.conv2d_init(next(it), C, widths[0], 3)}
        cin = widths[0]
        for s, w in enumerate(widths):
            for b in range(blocks_per_stage):
                blk = {
                    "c1": layers.conv2d_init(next(it), cin if b == 0 else w, w, 3),
                    "c2": layers.conv2d_init(next(it), w, w, 3),
                }
                if b == 0 and cin != w:
                    blk["proj"] = layers.conv2d_init(next(it), cin, w, 1)
                p[f"s{s}b{b}"] = blk
            cin = w
        p["fc"] = {"w": P_.dense_init(next(it), cin, (cin, spec.num_classes)),
                   "b": jnp.zeros((spec.num_classes,))}
        return p

    def apply(p, x):
        h = layers.conv2d(p["stem"], x, act=jax.nn.relu)
        for s, w in enumerate(widths):
            for b in range(blocks_per_stage):
                blk = p[f"s{s}b{b}"]
                stride = 2 if (s > 0 and b == 0) else 1
                r = layers.conv2d(blk["c1"], h, stride=stride, act=jax.nn.relu)
                r = layers.conv2d(blk["c2"], r)
                sc = h
                if "proj" in blk:
                    sc = layers.conv2d(blk["proj"], h, stride=stride)
                elif stride != 1:
                    sc = h[:, ::stride, ::stride, :]
                h = jax.nn.relu(r + sc)
        h = jnp.mean(h, axis=(1, 2))
        return h @ p["fc"]["w"] + p["fc"]["b"]

    return VisionModel(spec, init, apply)


# ---------------------------------------------------------------------------
# RegNet (BN-free, simplified X-block: group conv -> 1x1)
# ---------------------------------------------------------------------------


def make_regnet(spec: VisionSpec, widths=(24, 56, 152), depths=(1, 1, 2)) -> VisionModel:
    H, W, C = spec.input_shape

    def init(key):
        n = 1 + sum(depths) * 3 + 1
        keys = iter(jax.random.split(key, n + 8))
        p = {"stem": layers.conv2d_init(next(keys), C, widths[0], 3)}
        cin = widths[0]
        for s, (w, dep) in enumerate(zip(widths, depths)):
            for b in range(dep):
                blk = {
                    "c1": layers.conv2d_init(next(keys), cin if b == 0 else w, w, 1),
                    "c3": layers.conv2d_init(next(keys), w, w, 3),
                    "c2": layers.conv2d_init(next(keys), w, w, 1),
                }
                if b == 0 and cin != w:
                    blk["proj"] = layers.conv2d_init(next(keys), cin, w, 1)
                p[f"s{s}b{b}"] = blk
            cin = w
        p["fc"] = {"w": P_.dense_init(next(keys), cin, (cin, spec.num_classes)),
                   "b": jnp.zeros((spec.num_classes,))}
        return p

    def apply(p, x):
        h = layers.conv2d(p["stem"], x, stride=1, act=jax.nn.relu)
        for s, (w, dep) in enumerate(zip(widths, depths)):
            for b in range(dep):
                blk = p[f"s{s}b{b}"]
                stride = 2 if (s > 0 and b == 0) else 1
                r = layers.conv2d(blk["c1"], h, act=jax.nn.relu)
                r = layers.conv2d(blk["c3"], r, stride=stride, act=jax.nn.relu)
                r = layers.conv2d(blk["c2"], r)
                sc = h
                if "proj" in blk:
                    sc = layers.conv2d(blk["proj"], h, stride=stride)
                elif stride != 1:
                    sc = h[:, ::stride, ::stride, :]
                h = jax.nn.relu(r + sc)
        h = jnp.mean(h, axis=(1, 2))
        return h @ p["fc"]["w"] + p["fc"]["b"]

    return VisionModel(spec, init, apply)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

MNIST_SPEC = VisionSpec("mnist", (28, 28, 1), 10)
EMNIST_SPEC = VisionSpec("emnist", (28, 28, 1), 47)
FMNIST_SPEC = VisionSpec("fmnist", (28, 28, 1), 10)
CIFAR10_SPEC = VisionSpec("cifar10", (32, 32, 3), 10)
CIFAR100_SPEC = VisionSpec("cifar100", (32, 32, 3), 100)

DATASETS = {
    "mnist": MNIST_SPEC,
    "emnist": EMNIST_SPEC,
    "fmnist": FMNIST_SPEC,
    "cifar10": CIFAR10_SPEC,
    "cifar100": CIFAR100_SPEC,
}


def make_paper_model(name: str, spec: VisionSpec) -> VisionModel:
    return {
        "mlp": make_mlp,
        "mnistnet": make_mnistnet,
        "convnet": make_convnet,
        "resnet": make_resnet,
        "regnet": make_regnet,
    }[name](spec)
