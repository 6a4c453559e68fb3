"""The harness end to end on the CPU at a toy size: no TPU means no run;
the sound program comes out correct; the control, and the program broken
in each way a one-chip training cell can break (its step returns the state
unchanged; half of each batch left out, the mean taken over the rest),
come out not correct."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import correct, run, spec  # noqa: E402

TINY_CONFIG = {"train_size": 2000, "test_size": 100}
TINY_TRAFFIC = {"clients": 4, "local_steps": 2, "batch": 8, "eval_every": 2}
SEED = 4_000_000_123          # wider than 32 bits, as a --seed may be


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def _no_result(p):
    last = (p.stdout.strip().splitlines() or [""])[-1]
    return p.returncode != 0 and not last.startswith("{")


def test_run_exits_without_a_tpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mlp-3sfc", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert _no_result(p), p.stdout
    assert "no TPU" in p.stderr


def test_run_exits_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mlp-3sfc", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert _no_result(p), p.stdout


def tiny_cell(name="mlp-3sfc"):
    cell = spec.Cell(spec.benchmark(), name)
    cell.config = dict(cell.config, **TINY_CONFIG)
    cell.traffic = dict(cell.traffic, **TINY_TRAFFIC)
    return cell


def _cpu():
    import jax
    return jax.devices("cpu")


def _run(cell):
    peaks = spec.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    return run.run(cell, SEED, 0.5, False, _cpu(), peaks["TPU v5 lite"])


CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    result, lines = _run(tiny_cell(name))
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    cell = tiny_cell(name)
    assert set(result["checks"]) == set(cell.limits) <= set(correct.NUMBERS)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"rounds_per_s", "block_ms.p95", "setup_s"} <= set(result["metrics"])
    assert len(lines) == len(cell.limits)


def _stale(monkeypatch):
    # the round returns the parameters it was given
    monkeypatch.setattr("repro.fl.round.server_update",
                        lambda params, agg, lr=1.0: params)


def _half_batch(monkeypatch):
    import jax
    from repro.fl import round as fl_round
    orig = fl_round.local_train

    def half(loss_fn, params, batches, lr, **kw):
        return orig(loss_fn, params, jax.tree_util.tree_map(
            lambda x: x[:, : x.shape[1] // 2], batches), lr, **kw)

    monkeypatch.setattr(fl_round, "local_train", half)


@pytest.mark.parametrize("fault", [_stale, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_program_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    result, lines = _run(tiny_cell(name))
    assert not result["correct"], lines


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference in bfloat16, put in the program's place."""
    import jax.numpy as jnp
    from bench.families import vision_ref
    cell = tiny_cell(name)
    seed = SEED % run.SEED_MOD

    def reference(**kw):
        return correct.summarize(vision_ref.run_reference(
            cell.config, cell.traffic, seed, blocks=correct.STEPS, **kw))

    ref = reference()
    assert correct.judge(correct.numbers(ref, ref), cell.limits)
    values = correct.numbers(reference(dtype=jnp.bfloat16), ref)
    assert not correct.judge(values, cell.limits), values
