"""The main path compiles for a TPU v5e chip that is described, not attached.

The chip's compiler (Mosaic for the Pallas kernels, XLA:TPU for the rest)
refuses programs that interpret mode runs happily: scalar stores to VMEM,
unsigned reductions and casts, lane reshapes. Each test here AOT-compiles
for one chip of a described ``v5e:2x2`` topology and asserts each kernel
it expects is in the executable as a ``tpu_custom_call`` — no interpreter —
or, for the 3SFC float round, that no kernel and no packed copy of the
tree is. Nothing runs.

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

MLP_D = 199_210          # the paper MLP's parameter count (784-200-200-10)
CONVNET_D = 390_986      # the ConvNet's parameter count (widths 32-64-128-256)


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
    tile = bitpack.BLOCK_ROWS * bitpack.PACK_LANES
    rows = -(-MLP_D // tile) * bitpack.BLOCK_ROWS
    if name == "pack_signs":
        return (lambda x: bitpack.pack_signs_2d(x, interpret=False),
                spec((rows, bitpack.PACK_LANES)))
    return (lambda w: bitpack.unpack_signs_2d(w, interpret=False),
            spec((rows, bitpack.WORD_LANES), jnp.uint32))


@pytest.mark.parametrize("kernel", ["pack_signs", "unpack_signs"])
def test_main_path_kernel_compiles_for_v5e(kernel, one_chip,
                                           no_compile_cache):
    f, *args = _kernel_case(kernel, one_chip)
    assert "tpu_custom_call" in _compiled_text(f, *args)


@pytest.mark.parametrize("kind,wire,model", [
    pytest.param("threesfc", "float", "mlp", id="threesfc-float"),
    pytest.param("signsgd", "codec", "mlp", id="signsgd-codec"),
    pytest.param("threesfc", "float", "convnet", id="threesfc-float-convnet"),
])
def test_vmap_round_compiles_for_v5e(kind, wire, model, one_chip,
                                     no_compile_cache, monkeypatch):
    """The whole vmapped round (10 clients, 5 local steps, batch 32) of the
    paper's MLP on MNIST, or of the ConvNet on CIFAR-10. The codec round
    holds its bitpack kernels under the client vmap; the 3SFC float round
    reduces each leaf in its own layout, so it holds no kernel, no
    per-client d-vector and no copy padded to 1024 lanes."""
    from repro.configs.base import FLConfig
    from repro.configs.run import RunConfig
    from repro.core import flat
    from repro.core.strategy import make_strategy
    from repro.fl.budget import matched_compressors
    from repro.fl.round import build_fl_round, fl_init
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import CIFAR10_SPEC, MNIST_SPEC, make_paper_model

    # the backend seen here is the CPU's; steer the one interpret switch
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, k, b = 10, 5, 32
    spec, d = {"mlp": (MNIST_SPEC, MLP_D),
               "convnet": (CIFAR10_SPEC, CONVNET_D)}[model]
    net = make_paper_model(model, spec)
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    assert flat.tree_size(params) == d
    comp = matched_compressors(model, spec, d)[kind]
    strategy = make_strategy(comp, loss_fn=net.syn_loss,
                             syn_spec=vision_syn_spec(spec, comp),
                             local_lr=0.01)
    run = RunConfig(fl=FLConfig(num_clients=n, local_steps=k, local_lr=0.01,
                                local_batch=b, compressor=comp), wire=wire)
    codec = strategy.wire_codec(params, policy=run.wire_policy) \
        if wire == "codec" else None
    round_fn = build_fl_round(net.loss, strategy, run, codec=codec)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    state = on_chip(jax.eval_shape(lambda p: fl_init(p, n, strategy), params))
    batches = on_chip({
        "x": jax.ShapeDtypeStruct((n, k, b, *spec.input_shape),
                                  jnp.float32),
        "y": jax.ShapeDtypeStruct((n, k, b), jnp.int32)})
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    text = _compiled_text(round_fn, state, batches, key)
    if wire == "codec":
        assert "tpu_custom_call" in text
    else:
        assert "tpu_custom_call" not in text
        assert f"f32[{n},{d}]" not in text
        assert ",1024]" not in text
