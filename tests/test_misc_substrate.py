"""Optimizers, checkpointing, data pipeline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.data.synthetic import make_class_image_dataset, make_token_dataset
from repro.optim import make_optimizer


@pytest.mark.parametrize("name", ["sgd", "momentum", "adam"])
def test_optimizers_minimize_quadratic(name):
    init, update = make_optimizer(name, lr=0.1)
    params = {"x": jnp.asarray([3.0, -2.0])}
    state = init(params)
    loss = lambda p: jnp.sum(p["x"] ** 2)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = update(params, g, state)
    assert float(loss(params)) < 1e-3


def test_optimizer_preserves_dtype():
    init, update = make_optimizer("adam", lr=0.01)
    params = {"x": jnp.ones((4,), jnp.bfloat16)}
    state = init(params)
    g = {"x": jnp.ones((4,), jnp.bfloat16)}
    params, state = update(params, g, state)
    assert params["x"].dtype == jnp.bfloat16


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
            "t": (jnp.zeros((2,)), jnp.asarray(3))}
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, tree, meta={"round": 7})
    like = jax.tree.map(jnp.zeros_like, tree)
    out = load_checkpoint(path, like)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), tree, out)
    assert out["nested"]["b"].dtype == jnp.bfloat16


def test_class_image_dataset_learnable_structure():
    tr = make_class_image_dataset(jax.random.PRNGKey(0), 500, (8, 8, 1), 5)
    te = make_class_image_dataset(jax.random.PRNGKey(9), 200, (8, 8, 1), 5)
    # same templates across splits: per-class means correlate
    for c in range(5):
        m_tr = tr.x[tr.y == c].mean(0).ravel()
        m_te = te.x[te.y == c].mean(0).ravel()
        r = np.corrcoef(m_tr, m_te)[0, 1]
        assert r > 0.8, f"class {c}: templates differ across splits (r={r})"


def test_token_dataset_bigram_structure():
    seqs = make_token_dataset(jax.random.PRNGKey(0), 64, 32, 50, noise=0.0)
    assert seqs.shape == (64, 32)
    # zero-noise: transition deterministic -> each token maps to one successor
    nxt = {}
    for s in seqs:
        for a, b in zip(s[:-1], s[1:]):
            assert nxt.setdefault(int(a), int(b)) == int(b)
