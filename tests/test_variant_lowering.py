"""§Perf variant paths must lower on a host mesh: fused decode,
reduced-precision EF, the no-qk-hd sharding rule, and activation-sharding
pins."""
import os

import jax
import pytest

from repro.configs.base import ShapeConfig, get_smoke_config
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (run under repro.launch.mesh.multidev_env)")

SMALL = {"train_4k": ShapeConfig("train_4k", 64, 8, "train"),
         "prefill_32k": ShapeConfig("prefill_32k", 64, 4, "prefill")}


@pytest.fixture(autouse=True)
def _small(monkeypatch):
    monkeypatch.setattr(specs_lib, "INPUT_SHAPES", SMALL)
    monkeypatch.setattr(specs_lib, "get_config", get_smoke_config)
    yield
    from repro.models import params as P_, shard
    P_.set_qk_hd_fallback(True)
    shard.enable(False)


def _mesh():
    n = len(jax.devices())
    return make_mesh((n // 2, 2), ("data", "model"))


@pytest.mark.parametrize("variant", [
    {"fused_decode": True},
    {"ef_dtype": "bfloat16", "param_dtype": "bfloat16"},
])
def test_train_variants_lower(variant):
    entry, args = specs_lib.make_entry("qwen1.5-0.5b", "train_4k", _mesh(),
                                       variant=variant)
    compiled = jax.jit(entry).lower(*args).compile()
    assert compiled is not None


@pytest.mark.parametrize("variant", [
    {"no_qk_hd_shard": True},
    {"act_shard": True},
])
def test_prefill_variants_lower(variant):
    entry, args = specs_lib.make_entry("internvl2-1b", "prefill_32k", _mesh(),
                                       variant=variant)
    compiled = jax.jit(entry).lower(*args).compile()
    assert compiled is not None
