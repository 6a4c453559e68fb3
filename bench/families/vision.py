"""The system under test for the vision cells: the round ``train_vision``
builds, driven block by block.

``make_paper_model`` -> ``matched_compressors`` -> ``make_strategy`` ->
``build_fl_round`` (with the codec when ``wire`` is ``codec``) ->
``RoundEngine(vision_batcher(...), shardings=...)``, exactly as
``repro.launch.train.train_vision`` wires them, with the data made by
``make_class_image_dataset`` from the seed at the configuration's train
size. One call of ``run_block`` is one closed-loop block: the engine's
scanned, donated ``run_block(state, eval_every)`` and the held-out accuracy
eval that ``train_vision`` runs after it.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.run import RunConfig
from repro.core import flat
from repro.core.strategy import make_strategy
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_class_image_dataset
from repro.fl.budget import matched_compressors
from repro.fl.engine import RoundEngine, device_pools, vision_batcher
from repro.fl.round import build_fl_round
from repro.fl.sharding import make_fl_shardings
from repro.launch.mesh import make_host_mesh
from repro.models.build import vision_syn_spec
from repro.models.cnn import DATASETS, accuracy, make_paper_model


# every client's pool row is as wide as the training set, so that the seed
# changes no program's shapes; False keeps the engine's own width (the
# largest pool), as train_vision builds it
FIXED_POOL_WIDTH = True


class VisionProgram:
    """One cell's program, built from its configuration and traffic files."""

    def __init__(self, config, traffic, seed: int):
        t_init = time.perf_counter()
        spec = DATASETS[config["dataset"]]
        if (list(spec.input_shape) != config["input_shape"]
                or spec.num_classes != config["num_classes"]):
            raise ValueError(f"{config['name']}: shapes {config['input_shape']}"
                             f"/{config['num_classes']} are not the program's "
                             f"{spec.input_shape}/{spec.num_classes}")
        n, every = traffic["clients"], traffic["eval_every"]
        self.every = every
        model = make_paper_model(config["model"], spec)
        key = jax.random.PRNGKey(seed)
        params = model.init(key)
        d = flat.tree_size(params)
        if d != config["params"]:
            raise ValueError(f"{config['name']}: the program's model has {d} "
                             f"parameters, the configuration {config['params']}")
        comp = matched_compressors(config["model"], spec, d)[traffic["strategy"]]
        for knob in ("syn_steps", "syn_batch", "syn_lr"):
            if knob in traffic and getattr(comp, knob) != traffic[knob]:
                raise ValueError(f"traffic {traffic['name']}: {knob} "
                                 f"{traffic[knob]} is not the program's "
                                 f"{getattr(comp, knob)}")
        strategy = make_strategy(comp, loss_fn=model.syn_loss,
                                 syn_spec=vision_syn_spec(spec, comp),
                                 local_lr=traffic["lr"])
        mesh = shardings = None
        if traffic["fanout"] == "shard_map":
            mesh = make_host_mesh()
            shardings = make_fl_shardings(mesh)
            shardings.check_divisible(n)
        flags = argparse.Namespace(
            clients=n, local_steps=traffic["local_steps"], lr=traffic["lr"],
            batch=traffic["batch"], rounds=every, alpha=traffic["alpha"],
            seed=seed, wire=traffic["wire"])
        run = RunConfig.from_flags(flags, compressor=comp,
                                   client_parallel=traffic["fanout"], mesh=mesh)
        codec = strategy.wire_codec(params, policy=run.wire_policy) \
            if run.wire == "codec" else None
        t0 = time.perf_counter()
        train = make_class_image_dataset(key, config["train_size"],
                                         spec.input_shape, spec.num_classes)
        test = make_class_image_dataset(jax.random.fold_in(key, 1),
                                        config["test_size"], spec.input_shape,
                                        spec.num_classes)
        t1 = time.perf_counter()
        parts = dirichlet_partition(train.y, n, alpha=traffic["alpha"],
                                    seed=seed, min_per_client=traffic["batch"])
        pools = device_pools(parts)
        if FIXED_POOL_WIDTH:
            # padding is never sampled (engine.ClientPools)
            pools = pools._replace(index=jnp.pad(
                pools.index, ((0, 0), (0, config["train_size"]
                                       - pools.index.shape[1]))))
        place = None
        if shardings is not None:
            pools = shardings.place_pools(pools)
            place = shardings.replicated
        # the sets become the programs' arguments (run.py hoists closed-over
        # arrays), so they go to the device once here, to every chip of a
        # mesh, as an embedded constant would, rather than at every call
        train_x, train_y, test_x, test_y = jax.device_put(
            (train.x, train.y, test.x, test.y), place)
        self.engine = RoundEngine(
            build_fl_round(model.loss, strategy, run, codec=codec),
            vision_batcher(train_x, train_y, pools, traffic["local_steps"],
                           traffic["batch"]),
            seed=seed, shardings=shardings)
        self.state = self.engine.init_state(params, n, strategy,
                                            staleness_max=run.staleness_max)
        self.params0 = host_params(params)
        # seconds of set-up by phase, printed on an earlier line of a run
        self.phases = {"model": t0 - t_init, "data": t1 - t0,
                       "partition_engine": time.perf_counter() - t1}

        @jax.jit
        def eval_acc(p):
            return accuracy(model.apply(p, jnp.asarray(test_x)),
                            jnp.asarray(test_y))

        self._eval = eval_acc

    def run_block(self, mark):
        """One block: ``eval_every`` scanned rounds, then the eval, each
        inside a ``mark(name)`` span. Returns (host ``RoundMetrics`` of the
        block, accuracy)."""
        with mark("bench.run_block"):
            self.state, ms = self.engine.run_block(self.state, self.every)
        with mark("bench.eval"):
            acc = float(self._eval(self.state.params))
        return ms, acc

    def params(self):
        return host_params(self.state.params)

    def block_hlo(self) -> str:
        """Optimized HLO text of the block executable ``run_block`` drives,
        with the op_names that carry the program's scopes; compiles or
        loads it from the compile cache, and runs nothing."""
        return self.engine.block_hlo_text(self.state, self.every)

    def describe(self) -> str:
        s = self.engine.stats
        return (f"engine: {s.dispatches} dispatches, {s.host_syncs} host "
                f"syncs, {s.rounds} rounds")

    def close(self) -> None:
        """Drop the program's device state so a reference can run after."""
        self.state = self.engine = self._eval = None


Program = VisionProgram


def host_params(tree):
    """``{"l1/w": float32 array, ...}`` on the host."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        out[name] = np.asarray(leaf, np.float32)
    return out
