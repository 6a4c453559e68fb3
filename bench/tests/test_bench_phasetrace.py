"""The scope and host-span reduction that ``bench/phasetrace.py`` prints
and the benchmark's phase readers read (``bench/devtrace.py``), against
figures worked out by hand: on a small made-up trace, and on a small trace
of the scoped program recorded on a TPU v5e chip with its block's HLO text
(``bench/testdata/scoped.*``, made by ``bench/phasetrace.py --tiny``)."""
import gzip
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace, run, spec  # noqa: E402
from bench.devtrace import Op  # noqa: E402
from bench.tests._traces import SCOPED_HLO, ctx_for, scoped_trace  # noqa: E402

PHASE_READERS = ("batch_ms.per_round", "local_train_ms.per_round",
                 "encode_ms.per_round", "server_ms.per_round",
                 "unscoped_share.round")


def _read(names, ctx):
    return {n: run.load_reader(n)(ctx) for n in names}


def test_scoped_made_up_trace_by_hand():
    v = devtrace.from_profile(scoped_trace())
    # without HLO text no operation has a scope; the kernel is named by
    # its instruction
    assert v.scope_s("fl.encode") is None and v.unscoped_s() is None
    assert "custom-call tpu_custom_call" in dict(v.breakdown()["device_ops"])
    names = v.attach_hlo(SCOPED_HLO)
    assert names.module == "jit_blk"
    assert names.kernel == {"custom-call.1": "ef_update"}
    # fusion.2 has no op_name: its computation's caller, the conditional,
    # is under fl.encode; the while is under no scope
    assert names.chain["fusion.2"] == ("fl.encode",)
    assert names.chain["while.1"] == ()
    # self times: while 36 - 10 - 3 - 10 = 13, fusion.1 10, fusion.2 3,
    # kernel 10; the eval's op is another executable's
    assert v.scope_s("fl.batch") == pytest.approx(10e-9)
    assert v.scope_s("fl.encode") == pytest.approx(13e-9)
    assert v.scope_s("fl.local_train") == 0.0
    assert v.unscoped_s() == pytest.approx(13e-9)
    assert v.block_self_s() == pytest.approx(36e-9)
    assert v.scope_names() == ["fl.batch", "fl.encode"]
    assert v.hlo_matched() == (4, 4)
    # boundary idle: [0,4] before the scan (run_block [0,1], dispatch
    # [1,4]); after it [40,43] sync, [43,44] run_block, [44,46] block,
    # [46,48] + [52,56] eval, [56,60] block
    assert v.boundary_gaps_s() == [pytest.approx(20e-9)]
    assert v.boundary_idle_s() == {
        "bench.run_block": pytest.approx(2e-9),
        "engine.dispatch": pytest.approx(3e-9),
        "engine.sync": pytest.approx(3e-9),
        "bench.block": pytest.approx(6e-9),
        "bench.eval": pytest.approx(6e-9)}
    # each idle interval is named at its midpoint: [0,4] at 2, [40,48] at
    # 44 (run_block ends there), [52,60] at 56 (the eval ends there)
    assert v.idle_gaps() == [("engine.dispatch", pytest.approx(4e-9)),
                             ("bench.run_block", pytest.approx(8e-9)),
                             ("bench.eval", pytest.approx(8e-9))]
    b = v.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert dict(b["device_ops"])["ef_update tpu_custom_call"] == \
        pytest.approx(10e-9)
    assert v.unscoped_ops() == [["while.1 = (f32[4]) while",
                                 pytest.approx(13e-9)]]
    # one round in the window: nanoseconds -> 1e-6 ms
    assert _read(PHASE_READERS, ctx_for(v, rounds=1)) == {
        "batch_ms.per_round": pytest.approx(10e-6),
        "local_train_ms.per_round": 0.0,
        "encode_ms.per_round": pytest.approx(13e-6),
        "server_ms.per_round": 0.0,
        "unscoped_share.round": pytest.approx(100 * 13 / 36)}


def test_metrics_leave_out_what_the_trace_lacks():
    """A trace of a program without the spans or the scopes (no HLO text
    attached, no ``engine.*`` span) gives no number."""
    trace = scoped_trace()
    host = trace.planes[1].lines[0]
    host.events = [e for e in host.events if not e.name.startswith("engine.")]
    v = devtrace.from_profile(trace)
    assert _read(PHASE_READERS, ctx_for(v, rounds=1)) == \
        dict.fromkeys(PHASE_READERS)
    assert not {"engine.dispatch", "engine.sync"} & set(v.boundary_idle_s())
    assert v.hlo_matched() == (0, 0) and v.unscoped_ops() == []


def test_self_times_take_no_parent_from_a_rounding_overlap():
    """A ``while`` [0,40] holding siblings [0,10] and [9,20]: the second
    seems to start a nanosecond before the first ends, as whole-nanosecond
    rounding can make it, and is still the ``while``'s child."""
    ops = [Op(0, 40, "while.1", False, 0), Op(0, 10, "a", False, 0),
           Op(9, 20, "b", False, 0)]
    assert devtrace._self_times(ops) == [40 - 10 - 11, 10, 11]


SCOPED = os.path.join(ROOT, "bench", "testdata", "scoped.xplane.pb.gz")
SCOPED_TEXT = os.path.join(ROOT, "bench", "testdata", "scoped.hlo.txt.gz")
SCOPED_BY_HAND = os.path.join(ROOT, "bench", "testdata", "scoped.json")


def test_recorded_scoped_trace_by_hand():
    """Three blocks of the toy ``mlp-3sfc`` of the scoped program on one
    v5e chip, with its block's HLO text; the figures in ``scoped.json``
    come from the raw events by other means (``how`` there)."""
    want = spec.load_json(SCOPED_BY_HAND)
    v = devtrace.load(SCOPED, 1)
    assert v.scope_s("fl.encode") is None
    with gzip.open(SCOPED_TEXT, "rt") as f:
        v.attach_hlo(f.read())
    assert v.window_s() == pytest.approx(want["window_s"], rel=1e-12)
    assert v.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert list(v.hlo_matched()) == want["hlo_matched"]
    phases = {s: v.scope_s(s) for s in v.scope_names()}
    phases["unscoped"] = v.unscoped_s()
    assert phases == pytest.approx(want["phase_s"], rel=1e-9)
    assert v.block_self_s() == pytest.approx(sum(want["phase_s"].values()),
                                             rel=1e-9)
    assert v.boundary_gaps_s() == pytest.approx(want["boundary_gaps_s"],
                                                rel=1e-9)
    assert v.boundary_idle_s() == pytest.approx(want["boundary_idle_s"],
                                                rel=1e-9)
    ops = dict(v.breakdown(top=10 ** 6)["device_ops"])
    for kernel, seconds in want["kernel_s"].items():
        assert ops[f"{kernel} tpu_custom_call"] == pytest.approx(seconds,
                                                                 rel=1e-9)
    # the phases, the unscoped rest and the eval make up the device time
    # the benchmark's reader reads; the two program spans split part of the
    # boundary gap its other reader reads
    rounds = 2 * want["blocks"]
    ctx = ctx_for(v, rounds=rounds, blocks=want["blocks"])
    got = _read(PHASE_READERS, ctx)
    device_ms = run.load_reader("device_ms.per_round")(ctx)
    gap_ms = run.load_reader("host_gap_ms.per_block")(ctx)
    scoped = sum(got[m] for m in PHASE_READERS[:4])
    block_ms = scoped / (1 - got["unscoped_share.round"] / 100)
    eval_ms = 1e3 * want["other_s"] / rounds
    # (busy time also counts, clipped, an op that began before the window)
    assert block_ms + eval_ms == pytest.approx(device_ms, rel=0.01)
    split = v.boundary_idle_s()
    program_ms = 1e3 * (split.get("engine.dispatch", 0.0)
                        + split.get("engine.sync", 0.0)) / want["blocks"]
    assert 0 < program_ms <= gap_ms
    assert {"engine.dispatch", "engine.sync"} & \
        {name for name, _ in v.idle_gaps()}
