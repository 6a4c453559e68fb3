"""Sharded client fan-out: shard_map rounds must match the vmap oracle and
the EF placement contract must survive donation.

The scenarios need 8 devices, so each test runs its scenario in a child
process via the ``multidev_scenario`` conftest fixture (the pytest process
itself is pinned to 1 CPU device). Child scenarios live in this same file
under ``__main__`` — run one by hand with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python tests/test_shard_round.py bitexact

Exactness contract (measured, see bench_collectives): XLA CPU lowers
batched dots differently per vmap width (~1e-8 param drift), so compressors
whose per-client math differentiates the model (3SFC) are bitwise only on a
width-matched mesh (client axis 1); fedavg/dgc/signsgd/stc are bitwise on
the real 8-way client axis.
"""
from repro.launch.mesh import make_mesh


def test_shard_map_bitexact_vs_vmap_all_compressors(multidev_scenario):
    """3 scanned rounds on the 8-way client mesh: bitwise params/EF/metrics
    for the width-stable compressors; 3SFC bitwise width-matched + tight
    allclose on the 8-way mesh."""
    multidev_scenario("bitexact")


def test_ef_sharding_roundtrip_through_donation(multidev_scenario):
    """Donated scan blocks must consume and reproduce the *sharded* EF
    buffers: spec pinned across blocks, old state consumed, caller's params
    alive."""
    multidev_scenario("ef_donation")


def test_shard_map_wire_mode_equals_vmap_float(multidev_scenario):
    """wire='codec' on the sharded fan-out (only framed uint8 buffers cross
    the shard_map boundary) over 3 scanned rounds: bitwise the vmap float
    oracle for topk; signsgd bitwise its own vmap wire mode (the 1-bit wire
    is fan-out-transparent); threesfc ≤1e-5 vs the vmap float oracle (the
    server-side decode recompute is vmap-width-sensitive, like the fused
    path)."""
    multidev_scenario("wire")


def test_shard_map_fault_pipeline(multidev_scenario):
    """The fault model on the 8-way sharded fan-out (the shard_map half of
    the 28-combo matrix; the vmap half runs in tests/test_faults.py):
    null-schedule masked rounds bitwise the unfaulted shard_map rounds for
    every (kind × wire) combo (fused threesfc at the established 1e-5
    width-lowering tolerance); a 50%-dropout schedule produces the same
    state as the vmap fan-out and drops the identical client set (mask
    transparency — state at 1e-6, since the renormalized masked mean is no
    longer the exact all-true identity under the 8-way psum); and the compiled
    faulted round keeps ZERO collectives inside the per-client
    ``CLIENT_SCOPE`` encode region (the masks ride the client axis, they
    never synchronize it)."""
    multidev_scenario("faults")


# ---------------------------------------------------------------------------
# child scenarios (8 devices)
# ---------------------------------------------------------------------------


def _world():
    import jax

    from repro.configs.base import CompressorConfig, FLConfig
    from repro.core.compressor import make_compressor
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.engine import RoundEngine, device_pools, vision_batcher
    from repro.fl.round import make_fl_round
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import MNIST_SPEC, make_paper_model

    N, K, B = 8, 2, 8
    model = make_paper_model("mlp", MNIST_SPEC)
    params = model.init(jax.random.PRNGKey(0))
    train = make_class_image_dataset(jax.random.PRNGKey(1), 400,
                                     MNIST_SPEC.input_shape, 10)
    parts = dirichlet_partition(train.y, N, alpha=0.5, seed=0,
                                min_per_client=16)

    def engine(ccfg, shardings=None, mode="vmap", mesh=None, donate=True,
               wire="float"):
        spec = vision_syn_spec(MNIST_SPEC, ccfg)
        comp = make_compressor(ccfg, loss_fn=model.syn_loss, syn_spec=spec,
                               local_lr=0.05)
        cfg = FLConfig(num_clients=N, local_steps=K, local_lr=0.05,
                       local_batch=B, compressor=ccfg)
        pools = device_pools(parts)
        if shardings is not None:
            pools = shardings.place_pools(pools)
        wire_kw = {}
        if wire == "codec":
            from repro.comm import make_codec
            wire_kw = dict(wire="codec",
                           codec=make_codec(ccfg, params, syn_spec=spec,
                                            syn_loss_fn=model.syn_loss))
        eng = RoundEngine(
            make_fl_round(model.loss, comp, cfg, client_parallel=mode,
                          mesh=mesh, **wire_kw),
            vision_batcher(train.x, train.y, pools, K, B),
            seed=0, donate=donate, shardings=shardings)
        return eng, eng.init_state(params, N)

    return params, engine, CompressorConfig


def _tree_equal(a, b, what):
    import jax
    import numpy as np
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=f"{what} not bit-exact")


def scenario_bitexact():
    import jax
    import numpy as np

    from repro.fl.sharding import make_fl_shardings

    mesh = make_mesh((8, 1), ("data", "model"))
    sh = make_fl_shardings(mesh)
    mesh_w = make_mesh((1, 8), ("data", "model"))   # width-matched
    sh_w = make_fl_shardings(mesh_w)
    _, engine, CompressorConfig = _world()

    kinds = {
        "fedavg": CompressorConfig(kind="identity", error_feedback=False),
        "dgc": CompressorConfig(kind="topk", keep_ratio=0.05),
        "signsgd": CompressorConfig(kind="signsgd"),
        "stc": CompressorConfig(kind="stc", keep_ratio=0.05),
        "threesfc": CompressorConfig(kind="threesfc", syn_steps=2, syn_lr=0.1),
    }
    for name, ccfg in kinds.items():
        ev, stv = engine(ccfg)
        sv, mv = ev.run_block(stv, 3)
        es, sts = engine(ccfg, sh, "shard_map", mesh)
        ss, ms = es.run_block(sts, 3)
        if name == "threesfc":
            # width-matched mesh: bitwise, proving the shard_map plumbing
            # (specs, gathers, key contract) is exactly transparent
            ew, stw = engine(ccfg, sh_w, "shard_map", mesh_w)
            sw, _ = ew.run_block(stw, 3)
            _tree_equal(sv.params, sw.params, "threesfc width-matched params")
            _tree_equal(sv.ef, sw.ef, "threesfc width-matched ef")
            # 8-way mesh: pinned to tight tolerance (width-dependent XLA
            # batched-dot lowering, ~1e-8 observed)
            for a, b in zip(jax.tree_util.tree_leaves(sv.params),
                            jax.tree_util.tree_leaves(ss.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=0, atol=1e-5)
        else:
            _tree_equal(sv.params, ss.params, f"{name} params")
            _tree_equal(sv.ef, ss.ef, f"{name} ef")
            for f in mv._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(mv, f)), np.asarray(getattr(ms, f)),
                    err_msg=f"{name} metric {f} not bit-exact")
        print(f"ok {name}")

    # fused 3SFC fan-out: gathered (D_syn, s) + replicated backward must
    # match the vmap fused path to the same width tolerance
    from repro.configs.base import FLConfig
    from repro.core.compressor import make_compressor
    from repro.fl.round import make_fl_round
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import MNIST_SPEC, make_paper_model
    ccfg = kinds["threesfc"]
    model = make_paper_model("mlp", MNIST_SPEC)
    spec = vision_syn_spec(MNIST_SPEC, ccfg)
    comp = make_compressor(ccfg, loss_fn=model.syn_loss, syn_spec=spec,
                           local_lr=0.05)
    cfg = FLConfig(num_clients=8, local_steps=2, local_lr=0.05,
                   local_batch=8, compressor=ccfg)
    kw = dict(fused_decode=True, syn_loss_fn=model.syn_loss, syn_spec=spec)
    from repro.data.synthetic import make_class_image_dataset
    from repro.fl.round import fl_init
    import jax.numpy as jnp
    ds = make_class_image_dataset(jax.random.PRNGKey(5), 200,
                                  MNIST_SPEC.input_shape, 10)
    rng = np.random.default_rng(0)
    bx = np.stack([np.asarray(ds.x)[rng.choice(200, (2, 8))] for _ in range(8)])
    by = np.stack([np.asarray(ds.y)[rng.choice(200, (2, 8))] for _ in range(8)])
    batches = {"x": jnp.asarray(bx), "y": jnp.asarray(by)}
    params = model.init(jax.random.PRNGKey(0))
    s0 = fl_init(params, 8)
    key = jax.random.PRNGKey(7)
    rf_v = make_fl_round(model.loss, comp, cfg, mesh=mesh, **kw)
    rf_s = make_fl_round(model.loss, comp, cfg, client_parallel="shard_map",
                         mesh=mesh, **kw)
    s1, _ = jax.jit(rf_v)(s0, batches, key)
    s2, _ = jax.jit(rf_s)(s0, batches, key)
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)
    print("ok fused")


def scenario_ef_donation():
    import jax
    import numpy as np

    from repro.fl.sharding import make_fl_shardings

    mesh = make_mesh((8, 1), ("data", "model"))
    sh = make_fl_shardings(mesh)
    params, engine, CompressorConfig = _world()
    eng, state = engine(CompressorConfig(kind="identity",
                                         error_feedback=False),
                        sh, "shard_map", mesh)

    def ef_spec(st):
        leaf = jax.tree_util.tree_leaves(st.ef)[0]
        return leaf.sharding.spec, leaf.sharding

    spec0, sharding0 = ef_spec(state)
    assert sharding0 == sh.client, (spec0, sh.client.spec)
    # each device owns exactly its N/8 clients' residual slice
    shards = jax.tree_util.tree_leaves(state.ef)[0].addressable_shards
    assert len(shards) == 8
    assert all(s.data.shape[0] == 1 for s in shards), \
        [s.data.shape for s in shards]

    old_leaves = jax.tree_util.tree_leaves((state.params, state.ef))
    state2, _ = eng.run_block(state, 2)
    donated = [l.is_deleted() for l in old_leaves]
    assert any(donated) and all(donated), \
        "donation must consume the whole sharded FLState"
    spec2, sharding2 = ef_spec(state2)
    assert sharding2 == sh.client, \
        f"EF gathered off the client axis after donation: {spec2}"
    # caller's params (deep-copied at init) survive
    for l in jax.tree_util.tree_leaves(params):
        assert not l.is_deleted()
    # second block: the donated round-trip keeps working, spec still pinned
    state3, ms = eng.run_block(state2, 2)
    assert np.isfinite(np.asarray(ms.loss)).all()
    _, sharding3 = ef_spec(state3)
    assert sharding3 == sh.client
    assert int(state3.round) == 4
    print("ok ef_donation")


def scenario_sharding_units():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest as _pytest

    from repro.fl.engine import ClientPools
    from repro.fl.round import FLState, fl_init
    from repro.fl.sharding import make_fl_shardings
    from repro.launch.mesh import client_axes, make_host_mesh

    mesh = make_host_mesh()
    assert mesh.devices.shape == (8, 1)
    sh = make_fl_shardings(mesh)
    assert sh.axes == client_axes(mesh) == ("data",)
    assert sh.client_shards == 8
    assert sh.replicated.spec == jax.sharding.PartitionSpec()

    with _pytest.raises(ValueError, match="not divisible"):
        sh.check_divisible(10)

    # placement: params replicated, EF leading-axis split 8 ways
    params = {"w": jnp.ones((16, 4)), "b": jnp.ones((4,))}
    state = sh.place_state(fl_init(params, 16))
    assert state.params["w"].sharding.is_fully_replicated
    efs = state.ef["w"].addressable_shards
    assert len(efs) == 8 and all(s.data.shape == (2, 16, 4) for s in efs)

    pools = sh.place_pools(ClientPools(jnp.zeros((16, 5), jnp.int32),
                                       jnp.ones((16,), jnp.int32)))
    assert all(s.data.shape == (2, 5)
               for s in pools.index.addressable_shards)

    # in-jit constraint pins a traced client tree to the same sharding
    @jax.jit
    def f(x):
        return sh.constrain_client_tree({"x": x})["x"] * 2

    out = f(jnp.ones((16, 3)))
    assert out.sharding == sh.client

    # make_host_mesh divisibility guard
    with _pytest.raises(ValueError, match="n % model"):
        make_host_mesh(model=3)
    print("ok sharding_units")


def scenario_wire():
    import jax
    import numpy as np

    from repro.fl.sharding import make_fl_shardings

    mesh = make_mesh((8, 1), ("data", "model"))
    sh = make_fl_shardings(mesh)
    _, engine, CompressorConfig = _world()

    shared = ("loss", "cosine", "payload_floats", "update_norm")

    # topk: the codec is lossless, so shard_map wire mode must be bitwise
    # the vmap float oracle — transport AND serialization fully transparent
    ccfg = CompressorConfig(kind="topk", keep_ratio=0.05)
    ev, stv = engine(ccfg)
    sv, mv = ev.run_block(stv, 3)
    es, sts = engine(ccfg, sh, "shard_map", mesh, wire="codec")
    ss, ms = es.run_block(sts, 3)
    _tree_equal(sv.params, ss.params, "topk wire params")
    _tree_equal(sv.ef, ss.ef, "topk wire ef")
    for f in shared:
        np.testing.assert_array_equal(
            np.asarray(getattr(mv, f)), np.asarray(getattr(ms, f)),
            err_msg=f"topk wire metric {f} not bit-exact")
    assert float(np.asarray(ms.wire_bytes_up)[0]) > 0
    print("ok topk")

    # signsgd: the 1-bit wire diverges from the 3-valued float sign on exact
    # zeros (documented), but must be fan-out-transparent: shard_map wire
    # mode bitwise equals vmap wire mode
    ccfg = CompressorConfig(kind="signsgd")
    ev, stv = engine(ccfg, wire="codec")
    sv, mv = ev.run_block(stv, 3)
    es, sts = engine(ccfg, sh, "shard_map", mesh, wire="codec")
    ss, ms = es.run_block(sts, 3)
    _tree_equal(sv.params, ss.params, "signsgd wire params")
    _tree_equal(sv.ef, ss.ef, "signsgd wire ef")
    for f in shared:
        np.testing.assert_array_equal(
            np.asarray(getattr(mv, f)), np.asarray(getattr(ms, f)),
            err_msg=f"signsgd wire metric {f} not bit-exact")
    print("ok signsgd")

    # threesfc: serialized (D_syn, s) frames cross the boundary; the server
    # decode recompute is vmap-width-sensitive (like the fused path), so the
    # 8-way mesh is pinned to the established 1e-5 tolerance
    ccfg = CompressorConfig(kind="threesfc", syn_steps=2, syn_lr=0.1)
    ev, stv = engine(ccfg)
    sv, _ = ev.run_block(stv, 3)
    es, sts = engine(ccfg, sh, "shard_map", mesh, wire="codec")
    ss, _ = es.run_block(sts, 3)
    for a, b in zip(jax.tree_util.tree_leaves(sv.params),
                    jax.tree_util.tree_leaves(ss.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)
    print("ok threesfc")


def scenario_faults():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import encode_region_collectives
    from repro.comm import make_codec
    from repro.configs.base import CompressorConfig, FLConfig
    from repro.configs.run import RunConfig
    from repro.core.strategy import make_strategy
    from repro.fl import faults as F
    from repro.fl.round import build_fl_round, fl_init
    from repro.fl.sharding import make_fl_shardings
    from repro.models.build import vision_syn_spec
    from repro.models.cnn import VisionSpec, make_paper_model

    mesh = make_mesh((8, 1), ("data", "model"))
    sh = make_fl_shardings(mesh)
    N, K, B = 8, 1, 8
    SPEC = VisionSpec("tiny", (4, 4, 1), 3)
    model = make_paper_model("mlp", SPEC)
    params = model.init(jax.random.PRNGKey(0))
    batches = {
        "x": jax.random.normal(jax.random.PRNGKey(1), (N, K, B, 4, 4, 1)),
        "y": jax.random.randint(jax.random.PRNGKey(2), (N, K, B), 0, 3),
    }
    key = jax.random.PRNGKey(5)

    def build(kind, wire, fused, parallel="shard_map", sched_fn=None, **rkw):
        ccfg = CompressorConfig(kind=kind, keep_ratio=0.2, syn_steps=2,
                                syn_lr=0.1,
                                error_feedback=kind != "identity")
        spec = vision_syn_spec(SPEC, ccfg)
        strat = make_strategy(ccfg, loss_fn=model.syn_loss, syn_spec=spec,
                              local_lr=0.05)
        cfg = FLConfig(num_clients=N, local_steps=K, local_lr=0.05,
                       local_batch=B, compressor=ccfg)
        run = RunConfig(fl=cfg, wire=wire, fused_decode=fused,
                        client_parallel=parallel,
                        mesh=mesh if parallel == "shard_map" else None, **rkw)
        codec = make_codec(ccfg, params, syn_spec=spec,
                           syn_loss_fn=model.syn_loss) \
            if wire == "codec" else None
        rf = build_fl_round(model.loss, strat, run, codec=codec,
                            fault_schedule_fn=sched_fn)
        return jax.jit(rf), strat

    def run2(rf):
        st = fl_init(params, N)
        for r in range(2):
            st, m = rf(st, batches, jax.random.fold_in(key, r))
        return st, m

    # 1) zero-fault bitwise on the sharded fan-out: every combo of the
    #    shard_map half of the matrix, masked-with-null vs plain
    ALL = ("identity", "topk", "randk", "signsgd", "stc", "threesfc",
           "fedsynth")
    CODEC = ("identity", "topk", "signsgd", "stc", "threesfc")
    combos = ([(k, "float", False) for k in ALL]
              + [(k, "codec", False) for k in CODEC]
              + [("threesfc", "float", True), ("threesfc", "codec", True)])
    for kind, wire, fused in combos:
        rf, _ = build(kind, wire, fused)
        rfn, _ = build(kind, wire, fused,
                       sched_fn=lambda r, n: F.null_schedule(n))
        sa, ma = run2(rf)
        sb, mb = run2(rfn)
        tag = f"{kind}/{wire}{'/fused' if fused else ''}"
        if fused:
            # the all-ones payload weight shifts XLA's fusion of the
            # gathered batched backward — the same width-sensitive
            # batched-dot lowering already pinned at 1e-5 for fused/8-way
            # threesfc above (observed ~5e-10 absolute); vmap fused is
            # bitwise (tests/test_faults.py)
            for a, b in zip(jax.tree_util.tree_leaves((sa.params, sa.ef)),
                            jax.tree_util.tree_leaves((sb.params, sb.ef))):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=0, atol=1e-5,
                                           err_msg=f"{tag} state")
        else:
            _tree_equal(sa.params, sb.params, f"{tag} shard_map params")
            _tree_equal(sa.ef, sb.ef, f"{tag} shard_map ef")
        # the scalar loss metric is reduced across devices and XLA may
        # reassociate the 8-way reduction differently between the two
        # programs (observed 1 ulp) — the vmap half of the matrix pins
        # the metrics bitwise
        np.testing.assert_allclose(np.asarray(ma.loss), np.asarray(mb.loss),
                                   rtol=0, atol=1e-6,
                                   err_msg=f"{tag} loss")
        assert float(mb.arrivals) == float(N)
        print(f"ok null {tag}")

    # 2) mask fan-out transparency: a real dropout pattern produces the
    #    same state on vmap and shard_map and drops the same clients.
    #    With a non-trivial mask the N/cnt renormalized aggregation is no
    #    longer the exact all-true mean identity, so the 8-way psum may
    #    reassociate it differently from vmap's single-program reduction
    #    (observed 1 ulp, ~4e-11 absolute) — pin at 1e-6 like the loss
    fkw = dict(participation_rate=0.75, drop_rate=0.5, fault_seed=7)
    rf_v, _ = build("topk", "float", False, parallel="vmap", **fkw)
    rf_s, _ = build("topk", "float", False, **fkw)
    sv, mv = run2(rf_v)
    ss, ms = run2(rf_s)
    for a, b in zip(jax.tree_util.tree_leaves((sv.params, sv.ef)),
                    jax.tree_util.tree_leaves((ss.params, ss.ef))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6,
                                   err_msg="faulted vmap-vs-shard_map state")
    np.testing.assert_array_equal(np.asarray(mv.arrivals),
                                  np.asarray(ms.arrivals))
    assert float(ms.arrivals) < float(N)   # the pattern actually dropped
    print("ok fault transparency")

    # 3) HLO gate: the participation/delivery masks ride the client axis —
    #    ZERO collectives inside the per-client encode region
    ccfg = CompressorConfig(kind="topk", keep_ratio=0.2)
    spec = vision_syn_spec(SPEC, ccfg)
    strat = make_strategy(ccfg, loss_fn=model.syn_loss, syn_spec=spec,
                          local_lr=0.05)
    cfg = FLConfig(num_clients=N, local_steps=K, local_lr=0.05,
                   local_batch=B, compressor=ccfg)
    run = RunConfig(fl=cfg, client_parallel="shard_map", mesh=mesh, **fkw)
    rf = build_fl_round(model.loss, strat, run)
    abstract = {
        "x": jax.ShapeDtypeStruct((N, K, B, 4, 4, 1), jnp.float32),
        "y": jax.ShapeDtypeStruct((N, K, B), jnp.int32),
    }
    compiled = jax.jit(
        rf,
        in_shardings=(sh.state, sh.client, sh.replicated),
        out_shardings=(sh.state, sh.replicated),
    ).lower(fl_init(params, N), abstract,
            jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    # the scope filter is the analysis contract's, defined once
    scoped = encode_region_collectives(compiled.as_text())
    assert not scoped, \
        f"faulted client encode region grew collectives: {scoped}"
    print("ok hlo gate")


SCENARIOS = {
    "bitexact": scenario_bitexact,
    "ef_donation": scenario_ef_donation,
    "sharding_units": scenario_sharding_units,
    "wire": scenario_wire,
    "faults": scenario_faults,
}


if __name__ == "__main__":
    import sys

    SCENARIOS[sys.argv[1]]()
