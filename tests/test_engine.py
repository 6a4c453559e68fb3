"""Device-resident round engine: the scanned multi-round path is bit-exact
against the per-round reference loop for every compressor kind; donation
consumes the state safely (with and without a mesh); the sampling PRNG
contract makes the trajectory independent of the eval cadence."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CompressorConfig, FLConfig
from repro.core.compressor import make_compressor
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_class_image_dataset
from repro.fl.engine import (RoundEngine, device_pools, token_batcher,
                             vision_batcher)
from repro.fl.round import make_fl_round
from repro.models.build import vision_syn_spec
from repro.models.cnn import MNIST_SPEC, make_paper_model

N, K, BATCH, ROUNDS = 4, 2, 8, 3

KINDS = {
    "fedavg": CompressorConfig(kind="identity", error_feedback=False),
    "dgc": CompressorConfig(kind="topk", keep_ratio=0.05),
    "signsgd": CompressorConfig(kind="signsgd"),
    "stc": CompressorConfig(kind="stc", keep_ratio=0.05),
    "threesfc": CompressorConfig(kind="threesfc", syn_steps=2, syn_lr=0.1),
}


@pytest.fixture(scope="module")
def world():
    model = make_paper_model("mlp", MNIST_SPEC)
    params = model.init(jax.random.PRNGKey(0))
    train = make_class_image_dataset(jax.random.PRNGKey(1), 400, (28, 28, 1), 10)
    parts = dirichlet_partition(train.y, N, alpha=0.5, seed=0,
                                min_per_client=16)
    batch_fn = vision_batcher(train.x, train.y, device_pools(parts), K, BATCH)
    return model, params, batch_fn


def _engine(world, comp_cfg, **kw):
    model, params, batch_fn = world
    spec = vision_syn_spec(MNIST_SPEC, comp_cfg)
    comp = make_compressor(comp_cfg, loss_fn=model.syn_loss, syn_spec=spec,
                           local_lr=0.05)
    cfg = FLConfig(num_clients=N, local_steps=K, local_lr=0.05,
                   local_batch=BATCH, compressor=comp_cfg)
    rf = make_fl_round(model.loss, comp, cfg)
    eng = RoundEngine(rf, batch_fn, seed=0, **kw)
    return eng, eng.init_state(params, N)


def _assert_tree_equal(a, b, what):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=f"{what} not bit-exact")


@pytest.mark.parametrize("kind", list(KINDS))
def test_scan_bit_exact_vs_python_loop(world, kind):
    """ONE scanned dispatch over 3 rounds == 3 per-round dispatches, bitwise:
    params, EF residuals, and every per-round metric."""
    eng, state = _engine(world, KINDS[kind])
    s_scan, ms = eng.run_block(state, ROUNDS)

    eng2, state2 = _engine(world, KINDS[kind], donate=False)
    s_loop, ml = eng2.run_loop(state2, ROUNDS)

    _assert_tree_equal(s_scan.params, s_loop.params, f"{kind} params")
    _assert_tree_equal(s_scan.ef, s_loop.ef, f"{kind} ef")
    assert int(s_scan.round) == int(s_loop.round) == ROUNDS
    for f in ("loss", "cosine", "payload_floats", "update_norm"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ms, f)), np.asarray(getattr(ml, f)),
            err_msg=f"{kind} metric {f} not bit-exact")


def test_eval_cadence_invariance(world):
    """fold_in on the absolute round => regrouping rounds into different
    scan lengths (blocks [3] vs [2, 1]) does not change the trajectory."""
    eng, state = _engine(world, KINDS["dgc"])
    s_a, _ = eng.run_block(state, 3)

    eng_b, state_b = _engine(world, KINDS["dgc"])
    state_b, _ = eng_b.run_block(state_b, 2)
    s_b, _ = eng_b.run_block(state_b, 1)

    _assert_tree_equal(s_a.params, s_b.params, "cadence params")
    _assert_tree_equal(s_a.ef, s_b.ef, "cadence ef")


def test_donation_consumes_state_and_caller_params_survive(world):
    """donate_argnums consumes the FLState buffers: the old state must not be
    reused, the engine's returned state keeps working, and the caller's
    params tree (deep-copied by init_state) stays alive."""
    model, params, _ = world
    eng, state = _engine(world, KINDS["fedavg"])
    old_leaves = jax.tree_util.tree_leaves((state.params, state.ef))
    state2, _ = eng.run_block(state, 2)
    donated = [l.is_deleted() for l in old_leaves]
    if any(donated):                     # backend actually honored donation
        assert all(donated), "donation must consume the whole FLState tree"
    # caller's params were copied at init_state: still alive and usable
    for l in jax.tree_util.tree_leaves(params):
        assert not l.is_deleted()
    _ = float(jax.tree_util.tree_leaves(params)[0].sum())
    # the returned state is the live one: another block runs fine
    state3, ms = eng.run_block(state2, 2)
    assert np.isfinite(np.asarray(ms.loss)).all()
    assert int(state3.round) == 4


def test_donation_safe_under_mesh(world):
    """Same dispatch with an explicit device mesh installed (the production
    context): donation + scan + sampling all trace and run."""
    from jax.sharding import Mesh
    devices = np.array(jax.devices()).reshape(-1)
    eng, state = _engine(world, KINDS["fedavg"])
    with Mesh(devices, ("d",)):
        state, ms = eng.run_block(state, 2)
    assert np.isfinite(np.asarray(ms.loss)).all()
    assert int(state.round) == 2


def test_engine_stats_accounting(world):
    """One dispatch and one host sync per eval block; the reference loop
    pays one dispatch + two syncs per round."""
    eng, state = _engine(world, KINDS["fedavg"])
    state, _ = eng.run_block(state, 3)
    assert eng.stats.dispatches == 1 and eng.stats.host_syncs == 1
    assert eng.stats.rounds == 3

    eng2, state2 = _engine(world, KINDS["fedavg"], donate=False)
    eng2.run_loop(state2, 3)
    assert eng2.stats.dispatches == 3 and eng2.stats.host_syncs == 6


def test_run_blocks_match_eval_cadence(world):
    """engine.run: metrics cover every round, evals land on the block ends
    (the seed cadence: every eval_every rounds plus the final round)."""
    eng, state = _engine(world, KINDS["fedavg"])
    state, hist = eng.run(state, 5, eval_every=2,
                          eval_fn=lambda st, ms, r: (int(st.round),
                                                     len(ms.loss)))
    assert hist.metrics.loss.shape == (5,)
    assert hist.metrics.cosine.shape == (5, N)
    assert [r for r, _ in hist.evals] == [2, 4, 5]
    assert [v for _, v in hist.evals] == [(2, 2), (4, 2), (5, 1)]


def test_run_handles_nonpositive_eval_every(world):
    """eval_every <= 0 means 'no eval cadence': one block for everything."""
    eng, state = _engine(world, KINDS["fedavg"])
    state, hist = eng.run(state, 3, eval_every=0)
    assert hist.metrics.loss.shape == (3,)
    assert eng.stats.dispatches == 1
    assert hist.evals == []


def test_run_zero_rounds_returns_empty_metrics(world):
    eng, state = _engine(world, KINDS["fedavg"])
    state, hist = eng.run(state, 0, eval_every=2)
    assert hist.metrics.loss.shape == (0,)
    assert hist.evals == [] and eng.stats.dispatches == 0
    assert int(state.round) == 0


def test_token_batcher_shapes_and_determinism():
    toks = np.arange(50 * 7, dtype=np.int32).reshape(50, 7) % 13
    bf = token_batcher(toks, num_clients=3, local_steps=2, local_batch=4,
                       extras={"frames": (5, 8)})
    key = jax.random.PRNGKey(0)
    b1 = bf(key, jnp.int32(4))
    b2 = bf(key, jnp.int32(4))
    b3 = bf(key, jnp.int32(5))
    assert b1["tokens"].shape == (3, 2, 4, 7)
    assert b1["frames"].shape == (3, 2, 4, 5, 8)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))


def test_device_pools_padding_never_sampled():
    """Padded pool entries (index 0) must be unreachable THROUGH the real
    batcher: every gathered row belongs to the client's own partition.
    Each dataset row encodes its own index in x, so the gathered batch
    reveals exactly which rows the batcher touched."""
    n = 200
    x = np.broadcast_to(np.arange(n, dtype=np.float32).reshape(n, 1, 1, 1),
                        (n, 2, 2, 1)).copy()
    y = np.random.default_rng(0).integers(0, 10, n).astype(np.int32)
    parts = dirichlet_partition(y, 5, alpha=0.3, seed=2, min_per_client=4)
    bf = vision_batcher(x, y, device_pools(parts), 3, 6)
    key = jax.random.PRNGKey(9)

    for rnd in range(4):
        batch = bf(key, jnp.int32(rnd))
        rows = np.asarray(batch["x"])[..., 0, 0, 0].astype(np.int64)  # (5,3,6)
        for i, pool in enumerate(parts):
            assert np.isin(rows[i], pool).all(), \
                f"client {i} sampled rows outside its pool at round {rnd}"
        np.testing.assert_array_equal(np.asarray(batch["y"]), y[rows])


def test_device_pools_zero_sample_client_clamped():
    """An empty Dirichlet part must not reach randint(maxval=0) (undefined
    inside jit): device_pools clamps its size to 1 over the zero index row,
    i.e. the degenerate client deterministically resamples dataset row 0."""
    n = 60
    x = np.broadcast_to(np.arange(n, dtype=np.float32).reshape(n, 1, 1, 1),
                        (n, 2, 2, 1)).copy()
    y = (np.arange(n) % 10).astype(np.int32)
    parts = [np.arange(20), np.array([], dtype=np.int64), np.arange(20, 60)]
    pools = device_pools(parts)
    assert pools.size.tolist() == [20, 1, 40]
    assert int(pools.index[1].sum()) == 0

    bf = vision_batcher(x, y, pools, local_steps=2, local_batch=4)
    batch = bf(jax.random.PRNGKey(0), jnp.int32(0))
    rows = np.asarray(batch["x"])[..., 0, 0, 0].astype(np.int64)
    np.testing.assert_array_equal(rows[1], np.zeros((2, 4)))   # all row 0
    assert np.isin(rows[0], parts[0]).all()
    assert np.isin(rows[2], parts[2]).all()

    # all-empty partition: still a valid (clamped) pool, no zero-width array
    pools2 = device_pools([np.array([], dtype=np.int64)] * 2)
    assert pools2.index.shape == (2, 1) and pools2.size.tolist() == [1, 1]


def _contract_rows(pools, key, rnd, k, b):
    """``pools.index[i, pos_i]`` by the PRNG contract, client by client."""
    kr = jax.random.fold_in(key, rnd)
    return np.stack([np.asarray(pools.index[i, jax.random.randint(
        jax.random.fold_in(kr, i), (k, b), 0, pools.size[i])])
        for i in range(pools.index.shape[0])])


@pytest.mark.parametrize("sample_shape", [(28, 28, 1), (32, 32, 3)])
def test_vision_batcher_batch_is_the_set_gathered_by_the_contract(
        sample_shape):
    """The sample-contiguous storage changes where the set's bytes sit, not
    the batch: for a host set and a device set, every round's ``x`` is
    bitwise ``train_x[pools.index[i, pos]]``, in the set's shape and dtype,
    and ``y`` is ``train_y`` at the same rows."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((120, *sample_shape)).astype(np.float32)
    y = rng.integers(0, 10, 120).astype(np.int32)
    pools = device_pools(dirichlet_partition(y, 3, alpha=0.5, seed=1,
                                             min_per_client=4))
    key = jax.random.PRNGKey(11)
    for train_x in (x, jnp.asarray(x)):
        bf = jax.jit(vision_batcher(train_x, y, pools, 2, 5))
        for rnd in range(3):
            batch = bf(key, jnp.int32(rnd))
            rows = _contract_rows(pools, key, rnd, 2, 5)
            assert batch["x"].shape == (3, 2, 5, *sample_shape)
            assert batch["x"].dtype == x.dtype
            np.testing.assert_array_equal(np.asarray(batch["x"]), x[rows])
            np.testing.assert_array_equal(np.asarray(batch["y"]), y[rows])


def _gathers(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _gathers(sub)


@pytest.mark.parametrize("sample_shape", [(28, 28, 1), (32, 32, 3)])
def test_vision_batcher_gathers_whole_rows(sample_shape):
    """The image gather reads the set stored once as sample-contiguous rows
    ``(n, prod(sample_shape))``, one row per sample, and never the
    ``(n, *sample_shape)`` set, whose tiling pads each image on a TPU."""
    n = 40
    x = np.zeros((n, *sample_shape), np.float32)
    y = np.zeros((n,), np.int32)
    pools = device_pools([np.arange(20), np.arange(20, 40)])
    bf = vision_batcher(x, y, pools, 2, 3)
    jaxpr = jax.make_jaxpr(bf)(jax.random.PRNGKey(0), jnp.int32(0)).jaxpr
    operands = [tuple(e.invars[0].aval.shape) for e in _gathers(jaxpr)]
    assert (n, int(np.prod(sample_shape))) in operands, operands
    assert (n, *sample_shape) not in operands, operands


def test_benchmarks_run_only_badname_exits_2(capsys):
    from benchmarks import run as bench_run
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--only", "definitely_not_a_bench"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "definitely_not_a_bench" in err
    for name in bench_run.BENCHES:
        assert name in err
