"""The per-layer readers (``bench/metrics/``) on the recorded fixtures, the
named-scope chains they read (``bench/devtrace.py``), and a model family
that comes as new files alone: its configuration holds no vision shapes,
its operation count is its own file, its scope reader another, and no
file of the harness changes."""
import gzip
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace, flops, run, spec  # noqa: E402
from bench.tests._traces import (SCOPED_HLO, ctx_for, ev,  # noqa: E402
                                 scoped_trace)

TESTDATA = os.path.join(ROOT, "bench", "testdata")
# the five readers of the harness before the scope readers came, on each
# committed fixture (six rounds in three blocks at the toy round, a TPU v5e)
PINNED = {
    "tiny": {"idle_share.device": 61.094038357935375,
             "host_gap_ms.per_block": 1.6037813333333333,
             "mfu.round": 0.031044859496481742,
             "device_ms.per_round": 1.0797661666666667,
             "pallas_share.device": 0.8460936835552513},
    "scoped": {"idle_share.device": 61.979603579996635,
               "host_gap_ms.per_block": 1.8596520000000003,
               "mfu.round": 0.030509474160638612,
               "device_ms.per_round": 1.0737055000000002,
               "pallas_share.device": 0.8412921420259094},
}
SCOPE_READERS = {"batch_ms.per_round": ("fl.batch",),
                 "local_train_ms.per_round": ("fl.local_train",),
                 "encode_ms.per_round": ("fl.encode",),
                 "server_ms.per_round": ("fl.gather", "fl.aggregate",
                                         "fl.update")}


def _fixture(name, hlo=False):
    v = devtrace.load(os.path.join(TESTDATA, f"{name}.xplane.pb.gz"), 1)
    if hlo:
        with gzip.open(os.path.join(TESTDATA, f"{name}.hlo.txt.gz"), "rt") as f:
            v.attach_hlo(f.read())
    return v


@pytest.mark.parametrize("name,hlo", [("tiny", False), ("scoped", False),
                                      ("scoped", True)])
def test_earlier_readers_read_the_same_on_the_fixtures(name, hlo):
    ctx = ctx_for(_fixture(name, hlo), rounds=6, blocks=3)
    # 2 clients x (local 3*397600*1*8 + 3SFC encode) + 3 * 199210
    assert ctx.round_flops == 169734250
    got = {m: run.load_reader(m)(ctx) for m in PINNED[name]}
    assert got == pytest.approx(PINNED[name], rel=1e-12)


def test_scope_readers_match_the_hand_count():
    """``scoped.json``'s ``phase_s`` was counted from the raw events and
    the HLO text apart from ``devtrace``."""
    want = spec.load_json(os.path.join(TESTDATA, "scoped.json"))["phase_s"]
    rounds = 6
    ctx = ctx_for(_fixture("scoped", hlo=True), rounds=rounds, blocks=3)
    for metric, scopes in SCOPE_READERS.items():
        by_hand = 1e3 * sum(want.get(s, 0.0) for s in scopes) / rounds
        assert run.load_reader(metric)(ctx) == pytest.approx(by_hand,
                                                             rel=1e-9)
    assert run.load_reader("unscoped_share.round")(ctx) == pytest.approx(
        100 * want["unscoped"] / sum(want.values()), rel=1e-9)
    # the phases and the unscoped rest are the block's whole self time
    total = sum(run.load_reader(m)(ctx) for m in SCOPE_READERS) * rounds
    share = run.load_reader("unscoped_share.round")(ctx) / 100
    assert total / (1 - share) == pytest.approx(
        1e3 * ctx.view.block_self_s(), rel=1e-9)


@pytest.mark.parametrize("op_name,chain", [
    ("jit(blk)/while/body/transpose(jvp(fl.local_train))/lm.moe/dot",
     ("fl.local_train", "lm.moe")),
    ("jit(blk)/while/body/vmap(fl.encode)/jit(ef_update_2d)/ef_update/"
     "pallas_call", ("fl.encode",)),
    ("jit(blk)/while/body/checkpoint(remat(lm.mla))/vmap()/add", ("lm.mla",)),
    # merged operations: the second name is relative to a shared prefix
    ("jit(blk)/while/body/fl.batch/gather;vmap(fl.encode)/broadcast_in_dim",
     ("fl.batch", "fl.encode")),
    # a function's qualified name, an argument's path, JAX's own names
    ("jit(blk)/while/body/closed_call/vision_batcher.<locals>.batch_fn/add",
     ()),
    ("state.params['l1']['w']", ()),
    ("jit(blk)/while", ()),
    ("", ()),
])
def test_scopes_strip_transforms_and_keep_dotted_names(op_name, chain):
    assert devtrace.scopes(op_name) == chain


NESTED_HLO = '''HloModule jit_blk, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%body (p.2: f32[4]) -> f32[4] {
  %p.2 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p.2), kind=kLoop, calls=%fc.1, metadata={op_name="jit(blk)/while/body/transpose(jvp(fl.local_train))/lm.moe/dot"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fc.2, metadata={op_name="jit(blk)/while/body/transpose(jvp(fl.local_train))/mul"}
  ROOT %fusion.3 = f32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%fc.3, metadata={op_name="jit(blk)/while/body/vmap(fl.encode)/add"}
}

ENTRY %main (p.3: f32[4]) -> f32[4] {
  %p.3 = f32[4]{0} parameter(0)
  ROOT %while.1 = f32[4]{0} while(%p.3), condition=%cond, body=%body, metadata={op_name="jit(blk)/while"}
}
'''


def _nested_trace():
    """``while.1`` [0,40] holds ``fusion.1`` [0,10] (``lm.moe`` inside
    ``fl.local_train``), ``fusion.2`` [10,14] (``fl.local_train``) and
    ``fusion.3`` [20,30] (``fl.encode``)."""
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("while.1", 0, 40), ev("fusion.1", 0, 10), ev("fusion.2", 10, 14),
        ev("fusion.3", 20, 30)]),
        NS(name="XLA Modules", events=[ev("jit_blk(1)", 0, 40)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.block", 0, 50), ev("bench.run_block", 0, 42)])])
    return NS(planes=[device, host])


def test_a_nested_scope_counts_toward_both():
    v = devtrace.from_profile(_nested_trace())
    v.attach_hlo(NESTED_HLO)
    assert v.scope_s("lm.moe") == pytest.approx(10e-9)
    assert v.scope_s("fl.local_train") == pytest.approx(14e-9)
    # an operation under both of two names asked together counts once
    assert v.scope_s("fl.local_train", "lm.moe") == pytest.approx(14e-9)
    assert v.scope_s("fl.encode") == pytest.approx(10e-9)
    assert v.unscoped_s() == pytest.approx(16e-9)
    assert v.block_self_s() == pytest.approx(40e-9)
    assert v.scope_names() == ["fl.encode", "fl.local_train", "lm.moe"]


def test_scopes_need_the_block_text_to_name_its_operations():
    """Where under 99% of the block's traced operations are found in the
    attached text (here 3 of 4), no scope is read."""
    v = devtrace.from_profile(scoped_trace())
    v.attach_hlo("\n".join(line for line in SCOPED_HLO.splitlines()
                           if "%fusion.1 =" not in line))
    assert v.hlo_matched() == (3, 4)
    assert v.scope_s("fl.encode") is None and v.unscoped_s() is None
    assert v.block_self_s() is None
    ctx = ctx_for(v, rounds=1)
    for m in list(SCOPE_READERS) + ["unscoped_share.round"]:
        assert run.load_reader(m)(ctx) is None, m


def test_each_family_has_its_count():
    mlp = spec.load_json(spec.config_path("mlp-mnist"))
    assert flops.for_family(mlp) is flops
    assert flops.for_family(dict(mlp, family="no-such-family")) is None


# -- a family added as new files alone ---------------------------------------
TOY_CONFIG = {"name": "toy-lm", "family": "toylm", "vocab_size": 512,
              "hidden_size": 64, "num_hidden_layers": 2, "params": 73728,
              "reduced": []}
TOY_FILES = {
    "bench/families/toylm.py": '"""A stand-in program; no test runs it."""\n',
    "bench/families/toylm_ref.py": '"""A stand-in reference."""\n',
    "bench/families/toylm_flops.py": (
        '"""Operations of the toy family: two embeddings and h x h layers."""\n'
        "def param_count(config):\n"
        "    h = config['hidden_size']\n"
        "    return 2 * config['vocab_size'] * h + "
        "config['num_hidden_layers'] * h * h\n\n\n"
        "def round_flops(config, traffic):\n"
        "    return 6 * param_count(config) * traffic['tokens']\n"),
    "bench/metrics/moe_ms.per_round.py": (
        '"""Self time per round under the scope ``lm.moe``, in ms."""\n'
        "SCOPES = ('lm.moe',)\n\n\n"
        "def read(ctx):\n"
        "    s = ctx.view.scope_s(*SCOPES)\n"
        "    return None if s is None else 1e3 * s / ctx.rounds\n"),
    "bench/configs/toy-lm.json": json.dumps(TOY_CONFIG),
    "bench/configs/toy-nocount.json": json.dumps(
        dict(TOY_CONFIG, name="toy-nocount", family="nocount")),
    "bench/families/nocount.py": '"""A family with no operation count."""\n',
    "bench/families/nocount_ref.py": '"""Its reference."""\n',
    "bench/traffic/toy-short.json": json.dumps({"name": "toy-short",
                                                "tokens": 4096}),
    "bench/workloads/toy-lm.short.json": json.dumps(
        {"name": "toy-lm.short", "limits": {"loss0_gap": 1e-4}}),
    "bench/workloads/toy-nocount.short.json": json.dumps(
        {"name": "toy-nocount.short", "limits": {"loss0_gap": 1e-4}}),
}

CHECK = r'''
import gzip, json, sys
from bench import devtrace, flops, run, spec
from bench.tests._traces import SCOPED_HLO, scoped_trace
bench = spec.benchmark()
assert spec.problems(bench) == [], spec.problems(bench)
peaks = spec.load_json("bench/peaks.json")["TPU v5 lite"]
out = {}
for name in ("toy-lm.short", "toy-nocount.short"):
    cell = spec.Cell(bench, name)
    counter = flops.for_family(cell.config)
    out[name] = {"params": counter and counter.param_count(cell.config),
                 "per_layer": [m["name"] for m in cell.per_layer],
                 "reads": []}
    views = [devtrace.from_profile(scoped_trace()),
             devtrace.from_profile(scoped_trace()),
             devtrace.load("bench/testdata/scoped.xplane.pb.gz", 1),
             devtrace.load("bench/testdata/scoped.xplane.pb.gz", 1)]
    views[1].attach_hlo(SCOPED_HLO)
    with gzip.open("bench/testdata/scoped.hlo.txt.gz", "rt") as f:
        views[3].attach_hlo(f.read())
    for view in views:
        ctx = run.layer_context(cell, view, 6, 3, peaks)
        every = {m["name"]: run.load_reader(m["name"])(ctx)
                 for m in bench["per_layer"]}
        out[name]["reads"].append({"every": every,
                                   "cell": run.layer_metrics(cell, ctx)})
print(json.dumps(out))
'''


def test_a_family_added_as_new_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, text in TOY_FILES.items():
        assert not (tmp_path / rel).exists(), rel
        (tmp_path / rel).write_text(text)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for config in ("toy-lm", "toy-nocount"):
        bench["configs"].append({"name": config, "source": "made up",
                                 "file": f"bench/configs/{config}.json",
                                 "reduced": [], "why": "a test"})
        bench["workloads"].append({"name": f"{config}.short",
                                   "config": config, "traffic": "toy-short",
                                   "chips": 1, "why": "a test"})
    cells = ["mlp-3sfc", "toy-lm.short", "toy-nocount.short"]
    for m in bench["per_layer"]:
        m["workloads"] = cells
    bench["per_layer"].append({"name": "moe_ms.per_round", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "mixture of experts",
                               "moves": "rounds_per_s",
                               "workloads": cells[1:]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", CHECK], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["toy-lm.short"]["params"] == TOY_CONFIG["params"]
    assert out["toy-nocount.short"]["params"] is None
    names = [m["name"] for m in bench["per_layer"]]
    for name, got in out.items():
        assert got["per_layer"] == names
        for k, read in enumerate(got["reads"]):
            for metric, value in read["every"].items():
                assert value is None or isinstance(value, float), \
                    (name, k, metric)
            assert set(read["cell"]) == {m for m, v in read["every"].items()
                                         if v is not None}
        # no HLO text (0, 2): no scope is read; with it (1, 3): each is
        moe = [r["every"]["moe_ms.per_round"] for r in got["reads"]]
        assert moe[0] is None and moe[2] is None
        assert moe[1] == 0.0 and moe[3] == 0.0
        encode = [r["every"]["encode_ms.per_round"] for r in got["reads"]]
        assert encode[0] is None and encode[1] > 0 and encode[3] > 0
        mfu = [r["every"]["mfu.round"] for r in got["reads"]]
        if name == "toy-nocount.short":
            assert mfu == [None] * 4
        else:
            assert all(v > 0 for v in mfu)
