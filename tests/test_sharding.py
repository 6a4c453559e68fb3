"""Sharding rules: specs are rank-correct, divisibility-safe, and the FL
round + serving entries lower & compile on a small host mesh (the same code
path dryrun.py uses at 16x16 and 2x16x16)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShapeConfig, get_smoke_config
from repro.launch import specs as specs_lib
from repro.launch.mesh import client_axes, make_mesh, num_clients_for
from repro.models import params as params_lib
from repro.models.build import build_model

# per-test (not module-wide): the subprocess-backed tests below run their
# multi-device half in a forced-8-device child and work from any parent
needs_multidev = pytest.mark.skipif(
    len(jax.devices()) < 2 and os.environ.get("FORCE_SHARDING_TESTS") != "1",
    reason="needs >=2 devices (run under dryrun flags for multi-dev)")


def _mesh():
    n = len(jax.devices())
    m = 2 if n % 2 == 0 else 1
    return make_mesh((n // m, m), ("data", "model"))


@needs_multidev
def test_param_specs_rank_and_divisibility():
    mesh = _mesh()
    for arch in ("tinyllama-1.1b", "qwen3-moe-30b-a3b", "mamba2-370m",
                 "recurrentgemma-2b"):
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = params_lib.sharding_specs(shapes, mesh)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

        def check(sd, sp):
            assert len(sp) <= len(sd.shape), (sd.shape, sp)
            for dim, ax in zip(sd.shape, tuple(sp) + (None,) * 8):
                if ax is not None:
                    axs = ax if isinstance(ax, tuple) else (ax,)
                    k = 1
                    for a in axs:
                        k *= sizes[a]
                    assert dim % k == 0, (sd.shape, sp)

        jax.tree.map(check, shapes, specs)


SMALL = {
    "train_4k": ShapeConfig("train_4k", 64, 8, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 64, 4, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 64, 8, "decode"),
}


@needs_multidev
@pytest.mark.parametrize("shape", list(SMALL))
def test_entries_lower_on_host_mesh(shape, monkeypatch):
    monkeypatch.setattr(specs_lib, "INPUT_SHAPES", SMALL)
    monkeypatch.setattr(specs_lib, "get_config", get_smoke_config)
    mesh = _mesh()
    made = specs_lib.make_entry("qwen1.5-0.5b", shape, mesh)
    assert made is not None
    entry, args = made
    compiled = jax.jit(entry).lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    assert cost.get("flops", 0) > 0


@needs_multidev
def test_client_axes():
    mesh = _mesh()
    assert client_axes(mesh) == ("data",)
    assert num_clients_for(mesh) == mesh.devices.shape[0]


def test_make_host_mesh_rejects_nondivisible_model():
    """A truncated (n // model, model) mesh would silently drop devices —
    make_host_mesh must refuse instead."""
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match="n % model"):
        make_host_mesh(model=n + 1)          # n % (n+1) != 0 for any n >= 1
    with pytest.raises(ValueError, match="n % model"):
        make_host_mesh(model=0)


def test_fl_shardings_units_on_eight_devices(multidev_scenario):
    """FLShardings placement contract on a real 8-device host mesh
    (subprocess — the pytest process is pinned to 1 device): replicated
    params, 8-way EF/pool shards, in-jit batch constraint, divisibility
    guards in both FLShardings and make_host_mesh."""
    multidev_scenario("sharding_units")
