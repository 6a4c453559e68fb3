"""The main path compiles for a TPU v5e chip that is described, not attached.

The chip's compiler (Mosaic for the Pallas kernels, XLA:TPU for the rest)
refuses programs that interpret mode runs happily: scalar stores to VMEM,
unsigned reductions and casts, lane reshapes. Each test here AOT-compiles
for one chip of a described ``v5e:2x2`` topology and asserts the kernel is
in the executable as a ``tpu_custom_call`` — no interpreter. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. The persistent compile cache is off around the compiles (an
entry written for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitpack, ops
from repro.kernels.ef_update import ef_update_2d
from repro.kernels.fused_cosine import LANES, fused_cosine_2d

MLP_D = 199_210          # the paper MLP's parameter count (784-200-200-10)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compiled_text(f, *args) -> str:
    return jax.jit(f).lower(*args).compile().as_text()


def _kernel_case(name, chip):
    spec = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    if name == "fused_cosine":
        br, rows = ops._plan_rows(MLP_D, 128)
        return (lambda x, y: fused_cosine_2d(x, y, block_rows=br,
                                             interpret=False),
                spec((rows, LANES)), spec((rows, LANES)))
    if name == "ef_update":
        br, rows = ops._plan_rows(MLP_D, 256)
        return (lambda u, d, s: ef_update_2d(u, d, s, block_rows=br,
                                             interpret=False),
                spec((rows, LANES)), spec((rows, LANES)), spec(()))
    tile = bitpack.BLOCK_ROWS * bitpack.PACK_LANES
    rows = -(-MLP_D // tile) * bitpack.BLOCK_ROWS
    if name == "pack_signs":
        return (lambda x: bitpack.pack_signs_2d(x, interpret=False),
                spec((rows, bitpack.PACK_LANES)))
    return (lambda w: bitpack.unpack_signs_2d(w, interpret=False),
            spec((rows, bitpack.WORD_LANES), jnp.uint32))


@pytest.mark.parametrize("kernel", ["fused_cosine", "ef_update",
                                    "pack_signs", "unpack_signs"])
def test_main_path_kernel_compiles_for_v5e(kernel, one_chip,
                                           no_compile_cache):
    f, *args = _kernel_case(kernel, one_chip)
    assert "tpu_custom_call" in _compiled_text(f, *args)


@pytest.mark.parametrize("kind,wire", [("threesfc", "float"),
                                       ("signsgd", "codec")])
def test_vmap_round_compiles_for_v5e(kind, wire, one_chip, no_compile_cache,
                                     monkeypatch):
    """The whole vmapped round at the paper's MLP/MNIST widths (10 clients,
    5 local steps, batch 32) — the kernels under the client vmap, as the
    round batches them."""
    from repro.configs.base import FLConfig
    from repro.configs.run import RunConfig
    from repro.core import flat
    from repro.core.strategy import make_strategy
    from repro.fl.budget import matched_compressors
    from repro.fl.round import build_fl_round, fl_init
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import MNIST_SPEC, make_paper_model

    # the backend seen here is the CPU's; steer the one interpret switch
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, k, b = 10, 5, 32
    model = make_paper_model("mlp", MNIST_SPEC)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert flat.tree_size(params) == MLP_D
    comp = matched_compressors("mlp", MNIST_SPEC, MLP_D)[kind]
    strategy = make_strategy(comp, loss_fn=model.syn_loss,
                             syn_spec=vision_syn_spec(MNIST_SPEC, comp),
                             local_lr=0.01)
    run = RunConfig(fl=FLConfig(num_clients=n, local_steps=k, local_lr=0.01,
                                local_batch=b, compressor=comp), wire=wire)
    codec = strategy.wire_codec(params, policy=run.wire_policy) \
        if wire == "codec" else None
    round_fn = build_fl_round(model.loss, strategy, run, codec=codec)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    state = on_chip(jax.eval_shape(lambda p: fl_init(p, n, strategy), params))
    batches = on_chip({
        "x": jax.ShapeDtypeStruct((n, k, b, *MNIST_SPEC.input_shape),
                                  jnp.float32),
        "y": jax.ShapeDtypeStruct((n, k, b), jnp.int32)})
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert "tpu_custom_call" in _compiled_text(round_fn, state, batches, key)
