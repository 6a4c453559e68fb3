"""The strategy registry as a third-party extension point.

Registers a toy compression method (per-leaf mean-magnitude x sign) plus a
trivial lossless codec ENTIRELY in this test file — no repro/ source is
edited — and drives it through complete FL rounds: the vmap+float path
in-process, and the shard_map+codec path on the 8-device child (run this
file's scenario by hand with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python tests/test_strategy_api.py shard_codec

). Registry edge cases — duplicate kinds rejected, unknown kinds listing
the valid names — are pinned here too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.codec import Codec, array_to_bytes, bytes_to_array, register_codec
from repro.configs.base import CompressorConfig, FLConfig
from repro.configs.run import RunConfig
from repro.core import strategy as S
from repro.fl.round import build_fl_round, fl_init
from repro.launch.mesh import make_mesh

TOY_KIND = "toy_meansign"


@S.register_strategy(TOY_KIND)
class ToyMeanSign(S.CompressionStrategy):
    """Per-leaf mean-|x| scale times sign — a 10-line custom method."""

    def payload_floats(self, params):
        leaves = jax.tree_util.tree_leaves(params)
        return sum(l.size for l in leaves) / 32.0 + len(leaves)

    def client_encode(self, key, u, params):
        leaves, treedef = jax.tree_util.tree_flatten(u)
        scales = [jnp.mean(jnp.abs(l)) for l in leaves]
        recon = jax.tree_util.tree_unflatten(
            treedef, [s * jnp.sign(l) for s, l in zip(scales, leaves)])
        return S.TreeCompressed(
            recon, jnp.float32(self.payload_floats(params)), jnp.float32(0),
            wire=recon)

    def server_decode(self, payload, params):
        return payload


@register_codec
class ToyCodec(Codec):
    """Trivial lossless codec: the recon tree as one raw f32 stream."""

    kind = TOY_KIND

    def _section_bytes(self):
        return (4 * self.d,)

    def _pack(self, wire):
        leaves = jax.tree_util.tree_leaves(wire)
        return [jnp.concatenate([array_to_bytes(l) for l in leaves])]

    def _unpack(self, sections):
        vec = bytes_to_array(sections[0], (self.d,))
        leaves, off = [], 0
        for shape, n in zip(self.shapes, self.sizes):
            leaves.append(vec[off:off + n].reshape(shape))
            off += n
        return self._leaf_tree(leaves)

    def canonical(self, wire):
        return jax.tree_util.tree_map(
            lambda l: jnp.asarray(l, jnp.float32), wire)


# ---------------------------------------------------------------------------
# registry edges
# ---------------------------------------------------------------------------


def test_duplicate_strategy_kind_rejected():
    with pytest.raises(ValueError, match="already registered"):
        @S.register_strategy(TOY_KIND)
        class Dupe(S.CompressionStrategy):
            pass
    # the original registration is untouched
    assert S.STRATEGIES[TOY_KIND] is ToyMeanSign


def test_duplicate_codec_kind_rejected():
    with pytest.raises(ValueError, match="already registered"):
        @register_codec
        class DupeCodec(Codec):
            kind = TOY_KIND


def test_unknown_kind_lists_valid_names():
    with pytest.raises(ValueError) as ei:
        S.make_strategy(CompressorConfig(kind="definitely_not_a_kind"))
    msg = str(ei.value)
    for known in ("threesfc", "topk", TOY_KIND):
        assert known in msg, msg


def test_strategy_kinds_introspection():
    kinds = S.strategy_kinds()
    assert kinds == sorted(kinds)
    assert TOY_KIND in kinds and "threesfc" in kinds


# ---------------------------------------------------------------------------
# the toy method through a full round, vmap + float (in-process)
# ---------------------------------------------------------------------------


def _world(N=4):
    from repro.models.cnn import VisionSpec, make_paper_model

    model = make_paper_model("mlp", VisionSpec("tiny", (4, 4, 1), 3))
    params = model.init(jax.random.PRNGKey(0))
    K, B = 2, 8
    batches = {
        "x": jax.random.normal(jax.random.PRNGKey(1), (N, K, B, 4, 4, 1)),
        "y": jax.random.randint(jax.random.PRNGKey(2), (N, K, B), 0, 3),
    }
    cfg = FLConfig(num_clients=N, local_steps=K, local_lr=0.05,
                   compressor=CompressorConfig(kind=TOY_KIND))
    return model, params, batches, cfg


def test_toy_strategy_full_round_vmap_float():
    model, params, batches, cfg = _world()
    strat = S.make_strategy(cfg.compressor)
    rf = jax.jit(build_fl_round(model.loss, strat, RunConfig(fl=cfg)))
    state = fl_init(params, cfg.num_clients, strat)
    s1, m = rf(state, batches, jax.random.PRNGKey(3))
    assert np.isfinite(float(m.loss))
    assert float(m.payload_floats) == strat.payload_floats(params)
    assert float(m.wire_bytes_up) == 0.0
    # params actually moved and EF carries the residual u - recon
    moved = any(not np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree_util.tree_leaves(state.params),
                                jax.tree_util.tree_leaves(s1.params)))
    assert moved
    assert any(float(jnp.max(jnp.abs(l))) > 0
               for l in jax.tree_util.tree_leaves(s1.ef))


def test_toy_strategy_wire_codec_matches_float_vmap():
    model, params, batches, cfg = _world()
    strat = S.make_strategy(cfg.compressor)
    codec = strat.wire_codec(params)
    run_f = RunConfig(fl=cfg)
    run_w = RunConfig(fl=cfg, wire="codec")
    state = fl_init(params, cfg.num_clients, strat)
    sf, mf = jax.jit(build_fl_round(model.loss, strat, run_f))(
        state, batches, jax.random.PRNGKey(3))
    sw, mw = jax.jit(build_fl_round(model.loss, strat, run_w, codec=codec))(
        state, batches, jax.random.PRNGKey(3))
    for a, b in zip(jax.tree_util.tree_leaves((sf.params, sf.ef)),
                    jax.tree_util.tree_leaves((sw.params, sw.ef))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg="toy codec not transparent")
    assert float(mw.wire_bytes_up) == codec.nbytes
    assert float(mf.wire_bytes_up) == 0.0


# ---------------------------------------------------------------------------
# shard_map + codec on the 8-device child
# ---------------------------------------------------------------------------


def test_toy_strategy_shard_map_codec(multidev_scenario):
    """The toy method over the sharded fan-out in wire mode must be bitwise
    the vmap float oracle on a width-matched mesh and bitwise the float
    wire on the 8-way client axis (its codec is lossless); the 8-way run
    agrees with the vmap oracle to two roundings of the largest weight, and
    its efficiency cosines to two ulps."""
    multidev_scenario("shard_codec", file="tests/test_strategy_api.py")


METRICS = ("loss", "cosine", "payload_floats", "update_norm")


def scenario_shard_codec():
    model, params, batches, cfg = _world(N=8)
    mesh = make_mesh((8, 1), ("data", "model"))
    strat = S.make_strategy(cfg.compressor)
    codec = strat.wire_codec(params)
    state = fl_init(params, cfg.num_clients, strat)
    key = jax.random.PRNGKey(3)
    s_f, m_f = jax.jit(build_fl_round(model.loss, strat, RunConfig(fl=cfg)))(
        state, batches, key)

    def shard(m, wire="codec"):
        run_w = RunConfig(fl=cfg, wire=wire, client_parallel="shard_map",
                          mesh=m)
        return jax.jit(build_fl_round(
            model.loss, strat, run_w,
            codec=codec if wire == "codec" else None))(state, batches, key)

    def leaves(s):
        return [np.asarray(l)
                for l in jax.tree_util.tree_leaves((s.params, s.ef))]

    # width-matched mesh (client axis 1: each device runs all 8 clients,
    # as vmap does): the shard_map + codec plumbing is bitwise transparent
    s_m, m_m = shard(make_mesh((1, 8), ("data", "model")))
    for a, b in zip(leaves(s_f), leaves(s_m)):
        np.testing.assert_array_equal(a, b)
    for f in METRICS:
        np.testing.assert_array_equal(np.asarray(getattr(m_f, f)),
                                      np.asarray(getattr(m_m, f)))
    # 8-way client axis (1 client per device): the codec is bitwise the
    # float wire at that lowering too
    s_w, m_w = shard(mesh)
    s_wf, m_wf = shard(mesh, wire="float")
    for a, b in zip(leaves(s_wf), leaves(s_w)):
        np.testing.assert_array_equal(a, b)
    for f in METRICS:
        np.testing.assert_array_equal(np.asarray(getattr(m_wf, f)),
                                      np.asarray(getattr(m_w, f)))
    # against the vmap oracle, XLA:CPU lowers the clients' local training
    # per width: batched dots f32[8,8,200] under vmap, plain f32[8,200]
    # dots at one client per device. The local weights w_K then differ by
    # one rounding, and u = w - w_K (hence EF, a difference of ~1e-3
    # values) inherits it, so an elementwise ulp bound cannot hold. The
    # bound is two ulps of the largest weight; observed: one ulp, 3.0e-8
    # on EF, 3.7e-9 on params. The metrics stay bitwise.
    w_max = max(float(np.max(np.abs(l)))
                for l in jax.tree_util.tree_leaves(s_f.params))
    tol = 2 * float(np.spacing(np.float32(w_max)))
    for a, b in zip(leaves(s_f), leaves(s_w)):
        np.testing.assert_allclose(a, b, rtol=0, atol=tol)
    # The metrics that do not read u stay bitwise against the vmap oracle.
    # The efficiency cosine is a function of u, which differs by one
    # rounding here, so it may round to a neighbouring f32 (observed: one
    # ulp on one client); the bound is two ulps of the largest cosine. It
    # is bitwise at the width-matched mesh and against the float wire above.
    for f in ("loss", "payload_floats", "update_norm"):
        np.testing.assert_array_equal(np.asarray(getattr(m_f, f)),
                                      np.asarray(getattr(m_w, f)))
    cos_f, cos_w = np.asarray(m_f.cosine), np.asarray(m_w.cosine)
    cos_tol = 2 * float(np.spacing(np.float32(np.max(np.abs(cos_f)))))
    np.testing.assert_allclose(cos_f, cos_w, rtol=0, atol=cos_tol)
    assert float(np.asarray(m_w.wire_bytes_up)) == codec.nbytes
    print("ok toy shard_codec")


SCENARIOS = {"shard_codec": scenario_shard_codec}


if __name__ == "__main__":
    import sys

    SCENARIOS[sys.argv[1]]()
