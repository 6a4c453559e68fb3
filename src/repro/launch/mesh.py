"""Production mesh factory. Functions only — importing this module never
touches jax device state (jax locks the device count on first init, and the
dry-run needs to set XLA_FLAGS before that happens)."""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...],
              axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the FL round's sharding
    constraints and ``shard_map`` specs are written for GSPMD-partitioned
    axes, and ``jax.make_mesh`` defaults to ``Explicit`` ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """TPU v5e pod slice: 16x16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if model < 1 or n % model != 0:
        raise ValueError(
            f"make_host_mesh: {n} device(s) cannot be split into a "
            f"(data={n}//{model}, model={model}) mesh — n % model must be 0 "
            f"(a truncated mesh would silently drop devices)")
    return make_mesh((n // model, model), ("data", "model"))


def client_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Mesh axes the FL client dimension is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_clients_for(mesh: jax.sharding.Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes.get("data", 1) * sizes.get("pod", 1)
    return n


def axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get(name, 1)
