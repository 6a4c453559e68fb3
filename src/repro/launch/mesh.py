"""Mesh factories and the forced-device host environment. Functions only —
importing this module never touches jax device state (jax locks the device
count on first init, so a multi-device child must get its XLA_FLAGS from
``multidev_env`` before that happens)."""
from __future__ import annotations

import os
from typing import Dict, Tuple

import jax
from jax.sharding import AxisType

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def multidev_env() -> Dict[str, str]:
    """Child environment for forced-8-device host-CPU runs: the XLA device
    flag (must precede jax init), CPU platform pin, and src+repo on
    PYTHONPATH. The one recipe for the tests' ``multidev`` subprocess
    runner (tests/conftest.py), the IR-contract child of
    ``scripts/check_static.py`` and ``benchmarks/bench_collectives``."""
    env = os.environ.copy()
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), REPO]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


def make_mesh(shape: Tuple[int, ...],
              axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the FL round's sharding
    constraints and ``shard_map`` specs are written for GSPMD-partitioned
    axes, and ``jax.make_mesh`` defaults to ``Explicit`` ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


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
