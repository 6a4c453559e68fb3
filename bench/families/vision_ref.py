"""Plain reference of the FL rounds of the vision cells.

Written from the paper (arXiv:2302.13562, Algorithm 1 and Eqs. 6-10) and
the round's stated semantics, with nothing imported from the program:
the data, the Dirichlet partition, the initial weights and every round are
made here from the seed, in straightforward ``jax.numpy`` with no kernels.
In float32 every matmul and convolution runs at ``HIGHEST`` precision.

``run_reference`` follows the program's first ``blocks`` eval blocks from
the seed and returns the per-round losses and aggregate norms and the
parameters at each block boundary. The same function, with
``dtype=bfloat16``, is the control; with ``fault=...`` it stands in for a
program broken in one of the ways a training cell can break:
``half_batch`` leaves half of each local batch out and takes the mean over
the rest; ``altered`` negates client 0's message where it is produced;
``no_exchange`` leaves out the exchange between chips, so the server
averages only the clients of the first chip.

Semantics followed (the program's documented contracts):

* data: per class a template ``normal(PRNGKey(7))``; a sample is
  ``clip(0.5 * T_y + 0.5 + 0.35 * noise, 0, 1)``; the test split uses
  ``fold_in(key, 1)``;
* partition: per class, shuffle its indices and cut them by
  ``Dirichlet(alpha)`` proportions over the clients (numpy, seeded); a
  client short of ``batch`` samples borrows from the largest;
* sampling: round ``r``, client ``i`` draws ``(K, B)`` positions from
  ``fold_in(fold_in(fold_in(seed, 0), r), i)`` into its own index pool;
* local training: ``K`` SGD steps, the update is ``g = w_global - w_local``;
* error feedback (Eq. 6): ``u = g + e``, the message is ``C(u)``,
  ``e' = u - C(u)``;
* 3SFC (Eqs. 7-9): a one-sample synthetic batch from
  ``split(split(fold_in(seed, 1) -> round r, N)[i], 3)``, ``S`` steps of
  gradient descent on ``1 - |cos(grad_w F(D_syn), u)|``, each step scaled by
  the RMS of its gradient, then ``s = <u, grad> / |grad|^2`` and
  ``C(u) = s * grad_w F(D_syn)``;
* signSGD through the 1-bit codec: ``C(u) = mean|u_l| * (+1 if u >= 0
  else -1)`` per leaf;
* server: ``w' = w - mean_i C(u_i)``; the round's loss is the clients' mean
  local loss, its aggregate norm ``|mean_i C(u_i)|`` over all leaves.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FAULTS = ("half_batch", "altered", "no_exchange")


# ---------------------------------------------------------------------------
# inputs made from the seed
# ---------------------------------------------------------------------------


def make_images(key, n: int, shape, num_classes: int, sigma: float = 0.35,
                template_seed: int = 7):
    """(x, y) of the class-template image task, on the device."""
    ky, kn = jax.random.split(key, 2)
    templates = jax.random.normal(jax.random.PRNGKey(template_seed),
                                  (num_classes, *shape))
    y = jax.random.randint(ky, (n,), 0, num_classes)
    noise = sigma * jax.random.normal(kn, (n, *shape))
    return jnp.clip(templates[y] * 0.5 + 0.5 + noise, 0.0, 1.0), y


def dirichlet_pools(labels: np.ndarray, num_clients: int, alpha: float,
                    seed: int, min_per_client: int):
    """(index (N, P) int32, size (N,) int32): each client's sample pool."""
    rng = np.random.default_rng(seed)
    shards: List[List[int]] = [[] for _ in range(num_clients)]
    for c in np.unique(labels):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet(alpha * np.ones(num_clients))
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for i, part in enumerate(np.split(idx, cuts)):
            shards[i].extend(part.tolist())
    pools = []
    for s in shards:
        if len(s) < min_per_client:
            donor = int(np.argmax([len(t) for t in shards]))
            s = s + shards[donor][:min_per_client - len(s)]
        pools.append(np.array(sorted(s), np.int64))
    # every row as wide as the training set: one shape for every seed
    index = np.zeros((num_clients, len(labels)), np.int32)
    for i, p in enumerate(pools):
        index[i, :len(p)] = p
    size = np.array([max(len(p), 1) for p in pools], np.int32)
    return index, size


def init_params(key, layers) -> Dict[str, Dict[str, jax.Array]]:
    """Fan-in truncated-normal weights, zero biases; one key per layer
    with weights, and a conv layer's weight key is the first of a split."""
    weighted = [L for L in layers if "param" in L]
    keys = jax.random.split(key, len(weighted))
    params = {}
    for L, k in zip(weighted, keys):
        if L["kind"] == "dense":
            fan_in, shape, out = L["in"], (L["in"], L["out"]), L["out"]
        else:
            k = jax.random.split(k)[0]
            fan_in = L["cin"] * L["k"] * L["k"]
            shape, out = (L["k"], L["k"], L["cin"], L["cout"]), L["cout"]
        w = (1.0 / np.sqrt(fan_in)) * jax.random.truncated_normal(
            k, -2.0, 2.0, shape)
        params[L["param"]] = {"b": jnp.zeros((out,), jnp.float32), "w": w}
    return params


# ---------------------------------------------------------------------------
# the model and its losses
# ---------------------------------------------------------------------------


def forward(params, x, layers, precision):
    h = x
    for L in layers:
        kind = L["kind"]
        if kind == "flatten":
            h = h.reshape(h.shape[0], -1)
        elif kind == "mean_pool":
            h = jnp.mean(h, axis=(1, 2))
        elif kind == "dense":
            p = params[L["param"]]
            h = jnp.dot(h, p["w"], precision=precision) + p["b"]
        elif kind == "conv":
            p = params[L["param"]]
            s = L["stride"]
            h = lax.conv_general_dilated(
                h, p["w"], (s, s), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=precision) + p["b"]
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        if L.get("act") == "relu":
            h = jax.nn.relu(h)
    return h


def xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def soft_xent(logits, label_logits):
    target = jax.nn.softmax(label_logits, axis=-1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.sum(target * logp, axis=-1))


def _vdot(a, b):
    return sum(jnp.sum(x * y) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


# ---------------------------------------------------------------------------
# one round
# ---------------------------------------------------------------------------


def make_round(config, traffic, *, dtype=jnp.float32,
               fault: Optional[str] = None, chips: int = 1):
    """``round_fn(params, ef, x, y, index, size, data_key, round_key, r)
    -> (params, ef, loss, aggregate norm)``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    layers = config["layers"]
    n = traffic["clients"]
    k_steps, batch, lr = traffic["local_steps"], traffic["batch"], traffic["lr"]
    strategy = traffic["strategy"]
    prec = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    shape = tuple(config["input_shape"])
    num_classes = config["num_classes"]
    syn_lr = traffic.get("syn_lr")

    def model(w, x):
        return forward(w, x, layers, prec)

    def local_train(w0, bx, by):
        if fault == "half_batch":
            bx, by = bx[:, : batch // 2], by[:, : batch // 2]

        def step(w, xy):
            v, gr = jax.value_and_grad(
                lambda w_: xent(model(w_, xy[0]), xy[1]))(w)
            return _tmap(lambda p, g_: (p - lr * g_).astype(dtype), w, gr), v

        w, losses = lax.scan(step, w0, (bx, by))
        return _tmap(lambda a, b: a - b, w0, w), jnp.mean(losses)

    def encode_3sfc(w, u, key):
        kx, ky, _ = jax.random.split(key, 3)
        sb = traffic["syn_batch"]
        syn = (0.1 * jax.random.normal(kx, (sb, *shape)),
               0.1 * jax.random.normal(ky, (sb, num_classes)))
        syn = _tmap(lambda a: a.astype(dtype), syn)

        def objective(syn_):
            gw = jax.grad(lambda w_: soft_xent(model(w_, syn_[0]),
                                               syn_[1]))(w)
            dot, gg, tt = _vdot(gw, u), _vdot(gw, gw), _vdot(u, u)
            cos = dot / (jnp.sqrt(gg) * jnp.sqrt(tt) + 1e-12)
            return 1.0 - jnp.abs(cos), (gw, dot, gg)

        def gd_step(syn_, _):
            g = jax.grad(lambda s_: objective(s_)[0])(syn_)
            return _tmap(lambda p, g_: (p - syn_lr * g_ / jnp.sqrt(
                jnp.mean(g_ * g_) + 1e-12)).astype(dtype), syn_, g), None

        syn, _ = lax.scan(gd_step, syn, None, length=traffic["syn_steps"])
        _, (gw, dot, gg) = objective(syn)
        s = dot / (gg + 1e-12)
        return _tmap(lambda g_: (s * g_).astype(dtype), gw)

    def encode_sign(u):
        return _tmap(lambda l: (jnp.mean(jnp.abs(l))
                                * jnp.where(l >= 0, 1.0, -1.0)).astype(dtype),
                     u)

    def client(w, e, bx, by, key):
        g, loss = local_train(w, bx, by)
        u = _tmap(lambda a, b: (a + b).astype(dtype), g, e)
        msg = encode_3sfc(w, u, key) if strategy == "threesfc" \
            else encode_sign(u)
        return msg, _tmap(lambda a, b: (a - b).astype(dtype), u, msg), loss

    def mean_over(tree, m):
        # clients 0..m-1 added in index order, divided by m
        acc = _tmap(lambda x: jnp.zeros(x.shape[1:], jnp.float32), tree)
        acc, _ = lax.scan(lambda a, xi: (_tmap(jnp.add, a, xi), None), acc,
                          _tmap(lambda x: x[:m].astype(jnp.float32), tree))
        return _tmap(lambda a: a / m, acc)

    def round_fn(w, ef, x, y, index, size, data_key, round_key, r):
        kr = jax.random.fold_in(data_key, r)

        def draw(i):
            pos = jax.random.randint(jax.random.fold_in(kr, i),
                                     (k_steps, batch), 0, size[i])
            return index[i, pos]

        idx = jax.vmap(draw)(jnp.arange(n))
        keys = jax.random.split(jax.random.fold_in(round_key, r), n)
        msgs, ef, losses = jax.vmap(client, in_axes=(None, 0, 0, 0, 0))(
            w, ef, x[idx], y[idx], keys)
        if fault == "altered":
            msgs = _tmap(lambda m: m.at[0].multiply(-1), msgs)
        agg = mean_over(msgs, n // chips if fault == "no_exchange" else n)
        w = _tmap(lambda p, a: (p - a).astype(dtype), w, agg)
        return w, ef, mean_over(losses, n), jnp.sqrt(_vdot(agg, agg))

    return round_fn


def run_reference(config, traffic, seed: int, *, blocks: int = 3,
                  dtype=jnp.float32, fault: Optional[str] = None,
                  chips: int = 1):
    """Follow ``blocks`` eval blocks from the seed.

    Returns ``{"loss": (blocks * eval_every,), "agg": (blocks * eval_every,),
    "params": [w_0, w_b1, ...]}`` with every array on the host (numpy, float32), parameters as
    ``{"<layer>/<leaf>": array}``."""
    key = jax.random.PRNGKey(seed)
    n, every = traffic["clients"], traffic["eval_every"]
    x, y = jax.jit(make_images, static_argnums=(1, 2, 3))(
        key, config["train_size"], tuple(config["input_shape"]),
        config["num_classes"])
    index, size = dirichlet_pools(np.asarray(y), n, traffic["alpha"], seed,
                                  traffic["batch"])
    w = init_params(key, config["layers"])
    base = jax.random.PRNGKey(seed)
    data_key, round_key = (jax.random.fold_in(base, 0),
                           jax.random.fold_in(base, 1))
    cast = lambda t: _tmap(lambda a: a.astype(dtype), t)
    w, x = cast(w), x.astype(dtype)
    ef = _tmap(lambda p: jnp.zeros((n, *p.shape), dtype), w)
    round_fn = make_round(config, traffic, dtype=dtype, fault=fault,
                          chips=chips)

    @jax.jit
    def block(w, ef, x, y, index, size, data_key, round_key, r0):
        def body(carry, r):
            w_, ef_ = carry
            w_, ef_, loss, agg = round_fn(w_, ef_, x, y, index, size,
                                          data_key, round_key, r)
            return (w_, ef_), (loss, agg)

        (w, ef), (losses, aggs) = lax.scan(body, (w, ef),
                                           r0 + jnp.arange(every))
        return w, ef, losses, aggs

    index, size = jnp.asarray(index), jnp.asarray(size)
    snaps, losses, aggs = [flat_params(w)], [], []
    for b in range(blocks):
        # everything that depends on the seed is an argument, so that one
        # compiled block serves every seed
        w, ef, ls, ag = block(w, ef, x, y, index, size, data_key, round_key,
                              jnp.int32(b * every))
        snaps.append(flat_params(w))
        losses.append(np.asarray(ls, np.float32))
        aggs.append(np.asarray(ag, np.float32))
    return {"loss": np.concatenate(losses), "agg": np.concatenate(aggs),
            "params": snaps}


def flat_params(tree) -> Dict[str, np.ndarray]:
    """``{"l1/w": array, ...}`` on the host, float32."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        out[name] = np.asarray(jnp.asarray(leaf, jnp.float32))
    return out
