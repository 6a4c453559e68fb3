"""flat.tree_stats / flat.tree_ef_update: parity vs ref.py / naive tree_dot
across ragged leaf shapes, mixed dtypes, eager and jit-compiled modes, the
benchmark cells' trees under vmap, no padded or concatenated copy of a
tree, and the AD/vmap contracts the 3SFC encoder relies on
(grad-of-grad)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, flat
from repro.kernels import ref

# ragged on purpose: scalar leaf, sub-lane leaf, exact tile, tile+1, odd big
RAGGED_SHAPES = [(), (7,), (1024,), (1025,), (3, 341), (128, 1024), (13, 77, 5)]
CLIENTS = 10     # the benchmark cells' round: 10 clients under vmap


def _pair(key, shapes, dtypes=None):
    ks = jax.random.split(key, 2 * max(1, len(shapes)))
    dtypes = dtypes or [jnp.float32] * len(shapes)
    a = {f"p{i}": jax.random.normal(ks[2 * i], s, dt)
         for i, (s, dt) in enumerate(zip(shapes, dtypes))}
    b = {f"p{i}": jax.random.normal(ks[2 * i + 1], s, dt)
         for i, (s, dt) in enumerate(zip(shapes, dtypes))}
    return a, b


def _ravel(tree):
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                            for l in jax.tree.leaves(tree)])


def _oracle(a, b):
    """Whole-tree stats via ref.py on the monolithic concat (the contract)."""
    return ref.fused_cosine(_ravel(a), _ravel(b))


def test_ragged_tree_matches_oracle():
    a, b = _pair(jax.random.PRNGKey(0), RAGGED_SHAPES)
    got = flat.tree_stats(a, b)
    np.testing.assert_allclose(got, _oracle(a, b), rtol=2e-4)


def test_matches_naive_tree_dot():
    a, b = _pair(jax.random.PRNGKey(1), RAGGED_SHAPES)
    st = flat.tree_stats(a, b)
    np.testing.assert_allclose(st[0], flat.tree_dot(a, b), rtol=1e-5)
    np.testing.assert_allclose(st[1], flat.tree_sqnorm(a), rtol=2e-4)
    np.testing.assert_allclose(st[2], flat.tree_sqnorm(b), rtol=2e-4)


def test_single_scalar_leaf():
    st = flat.tree_stats({"w": jnp.float32(3.0)}, {"w": jnp.float32(-2.0)})
    np.testing.assert_allclose(st, [-6.0, 9.0, 4.0], rtol=1e-6)


def test_empty_tree_and_empty_leaf():
    np.testing.assert_array_equal(flat.tree_stats({}, {}), jnp.zeros(3))
    a = {"e": jnp.zeros((0,)), "x": jnp.ones((5,))}
    b = {"e": jnp.zeros((0,)), "x": 2.0 * jnp.ones((5,))}
    np.testing.assert_allclose(flat.tree_stats(a, b), [10.0, 5.0, 20.0],
                               rtol=1e-6)


@pytest.mark.parametrize("dtypes", [
    [jnp.bfloat16] * len(RAGGED_SHAPES),
    [jnp.bfloat16 if i % 2 else jnp.float32 for i in range(len(RAGGED_SHAPES))],
])
def test_mixed_dtype_trees(dtypes):
    a, b = _pair(jax.random.PRNGKey(2), RAGGED_SHAPES, dtypes)
    got = flat.tree_stats(a, b)
    np.testing.assert_allclose(got, _oracle(a, b), rtol=5e-3)
    assert got.dtype == jnp.float32


def _cell_tree_shapes(cell):
    """Leaf shapes of the benchmark cells' models: the paper's MLP on MNIST
    (d = 199,210) and the repo's ConvNet on CIFAR-10 (d = 390,986)."""
    from repro.models.cnn import (CIFAR10_SPEC, MNIST_SPEC, make_convnet,
                                  make_paper_model)
    model = (make_paper_model("mlp", MNIST_SPEC) if cell == "mlp"
             else make_convnet(CIFAR10_SPEC))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return [l.shape for l in jax.tree.leaves(params)]


def _client_pairs(key, shapes, n=CLIENTS):
    """n per-client tree pairs, and the same trees stacked on a client axis."""
    pairs = [_pair(k, shapes) for k in jax.random.split(key, n)]
    a = jax.tree.map(lambda *xs: jnp.stack(xs), *[p[0] for p in pairs])
    b = jax.tree.map(lambda *xs: jnp.stack(xs), *[p[1] for p in pairs])
    return pairs, a, b


@pytest.mark.parametrize("cell", ["mlp", "convnet"])
def test_cell_trees_match_oracle(cell):
    """Leafwise stats on the cells' trees under vmap over 10 clients, as
    the round runs the encode, against the concatenated-vector oracle."""
    pairs, a, b = _client_pairs(jax.random.PRNGKey(3), _cell_tree_shapes(cell))
    got = jax.jit(jax.vmap(flat.tree_stats))(a, b)
    want = jnp.stack([_oracle(x, y) for x, y in pairs])
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_jit_compiled_mode():
    a, b = _pair(jax.random.PRNGKey(4), RAGGED_SHAPES)
    got = jax.jit(flat.tree_stats)(a, b)
    np.testing.assert_allclose(got, _oracle(a, b), rtol=2e-4)


def test_vmap_batched_clients():
    """fl/round vmaps the compressor over clients; stats must batch."""
    def one(key):
        a, b = _pair(key, [(300,), (1025,)])
        return a, b
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    ab = [one(k) for k in keys]
    a = jax.tree.map(lambda *xs: jnp.stack(xs), *[x[0] for x in ab])
    b = jax.tree.map(lambda *xs: jnp.stack(xs), *[x[1] for x in ab])
    got = jax.vmap(flat.tree_stats)(a, b)
    want = jnp.stack([_oracle(x, y) for x, y in ab])
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_grad_and_grad_of_grad():
    """The encoder differentiates cosine-of-stats twice (grad-of-grad)."""
    a, b = _pair(jax.random.PRNGKey(6), [(129,), (1025,)])

    def cos(a):
        d, aa, bb = flat.tree_stats(a, b)
        return d / (jnp.sqrt(aa) * jnp.sqrt(bb) + 1e-12)

    def cos_ref(a):
        fa = jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(a)])
        fb = jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(b)])
        return jnp.vdot(fa, fb) / (jnp.linalg.norm(fa) * jnp.linalg.norm(fb)
                                   + 1e-12)

    g = jax.grad(cos)(a)
    gr = jax.grad(cos_ref)(a)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-4,
                                                         atol=1e-6), g, gr)

    def gnorm(f):
        return lambda a: flat.tree_sqnorm(jax.grad(f)(a))

    gg = jax.grad(gnorm(cos))(a)
    ggr = jax.grad(gnorm(cos_ref))(a)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-3,
                                                         atol=1e-6), gg, ggr)


def test_mismatched_trees_raise():
    """Lockstep streaming must reject shape mismatches loudly (zero padding
    would otherwise silently swallow them)."""
    with pytest.raises(ValueError, match="lockstep"):
        flat.tree_stats({"w": jnp.ones((4,))}, {"w": jnp.ones((6,))})
    with pytest.raises(ValueError, match="lockstep"):
        flat.tree_ef_update({"w": jnp.ones((2, 3))}, {"w": jnp.ones((3, 2))},
                           jnp.float32(1.0))


@pytest.mark.parametrize("cell", ["mlp", "convnet"])
def test_tree_ef_update_cell_trees(cell):
    """The EF residual on the cells' trees under vmap over 10 clients, each
    client with its own scale, against ``ref.ef_update`` on the
    concatenated vectors."""
    pairs, u, d = _client_pairs(jax.random.PRNGKey(10), _cell_tree_shapes(cell))
    s = jnp.linspace(-1.25, 0.75, CLIENTS, dtype=jnp.float32)
    got = jax.jit(jax.vmap(flat.tree_ef_update))(u, d, s)
    for i, (ui, di) in enumerate(pairs):
        got_i = _ravel(jax.tree.map(lambda l: l[i], got))
        np.testing.assert_allclose(
            got_i, ref.ef_update(_ravel(ui), _ravel(di), s[i]),
            rtol=1e-5, atol=1e-6)
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(got))


def _lowered_shapes(text, op):
    """Element counts of each ``stablehlo.<op>`` result in a lowering."""
    sizes = []
    for line in text.splitlines():
        if f"stablehlo.{op} " in line:
            dims = re.findall(r"tensor<([0-9x]*)x?f32>", line.split("->")[-1])[-1]
            sizes.append(int(np.prod([int(v) for v in dims.split("x") if v])))
    return sizes


@pytest.mark.parametrize("fn", ["stats", "ef"])
def test_no_pad_or_tree_concat_in_lowering(fn):
    """Each leaf is reduced (or updated) in its own layout: the vmapped
    lowering on the MLP tree pads nothing, and concatenates or reshapes
    nothing larger than the per-client (3,) triple (no leaf is raveled)."""
    spec = {f"p{i}": jax.ShapeDtypeStruct((CLIENTS, *shape), jnp.float32)
            for i, shape in enumerate(_cell_tree_shapes("mlp"))}
    if fn == "stats":
        lowered = jax.jit(jax.vmap(flat.tree_stats)).lower(spec, spec)
    else:
        s = jax.ShapeDtypeStruct((CLIENTS,), jnp.float32)
        lowered = jax.jit(jax.vmap(flat.tree_ef_update)).lower(spec, spec, s)
    text = lowered.as_text()
    assert "stablehlo.pad" not in text
    assert all(n <= CLIENTS * 3 for n in _lowered_shapes(text, "concatenate"))
    assert all(n <= CLIENTS * 3 for n in _lowered_shapes(text, "reshape"))


@pytest.mark.parametrize("fn", ["tree_stats", "tree_dot", "tree_cosine"])
def test_no_dot_in_lowering(fn):
    """The pair reductions are elementwise products summed in f32: a
    ``dot_general`` at default precision would run in bf16 on a TPU."""
    spec = {f"p{i}": jax.ShapeDtypeStruct((CLIENTS, *shape), jnp.float32)
            for i, shape in enumerate(_cell_tree_shapes("mlp"))}
    text = jax.jit(jax.vmap(getattr(flat, fn))).lower(spec, spec).as_text()
    assert "dot_general" not in text
    assert "stablehlo.reduce" in text


def test_tree_ef_update_matches_axpy():
    u, d = _pair(jax.random.PRNGKey(7), RAGGED_SHAPES)
    s = jnp.float32(0.37)
    got = flat.tree_ef_update(u, d, s)
    want = jax.tree.map(lambda ui, di: ui - s * di, u, d)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-5,
                                                         atol=1e-6), got, want)


def test_reconstruction_stats_fused():
    v = jax.random.normal(jax.random.PRNGKey(8), (4097,))
    r = 0.8 * v + 0.1 * jax.random.normal(jax.random.PRNGKey(9), (4097,))
    cos, rel = baselines.reconstruction_stats(v, r)
    want_cos = jnp.vdot(r, v) / (jnp.linalg.norm(r) * jnp.linalg.norm(v))
    want_rel = jnp.linalg.norm(r - v) / jnp.linalg.norm(v)
    np.testing.assert_allclose(cos, want_cos, rtol=1e-4)
    np.testing.assert_allclose(rel, want_rel, rtol=1e-3)


def test_reconstruction_stats_small_error_regime():
    """The error term must resolve errors far below f32 cancellation of the
    ||r||² − 2⟨r,v⟩ + ||v||² identity (~3e-4 relative)."""
    v = jax.random.normal(jax.random.PRNGKey(11), (1 << 20,))
    r = v + 1e-4 * jax.random.normal(jax.random.PRNGKey(12), (1 << 20,))
    _, rel = baselines.reconstruction_stats(v, r)
    want = jnp.linalg.norm(r - v) / jnp.linalg.norm(v)
    assert float(want) > 0
    np.testing.assert_allclose(rel, want, rtol=1e-3)
