"""The phase and host-span reduction (``bench/phasetrace.py``) against
figures worked out by hand: on a small made-up trace, and on a small trace
of the scoped program recorded on a TPU v5e chip with its block's HLO text
(``bench/testdata/scoped.*``, made by ``bench/phasetrace.py --tiny``)."""
import gzip
import os
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import phasetrace, run, spec  # noqa: E402
from bench.devtrace import Op  # noqa: E402

KERNEL = ('%custom-call.1 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), '
          'custom_call_target="tpu_custom_call"')


def _ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start),
              stats=list(stats.items()))


SCOPED_HLO = '''HloModule jit_blk, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%branch_1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  ROOT %fusion.2 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, calls=%fc.2
}

%body (p.2: f32[4]) -> f32[4] {
  %p.2 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p.2), kind=kLoop, calls=%fc.1, metadata={op_name="jit(blk)/while/body/fl.batch/vmap()/gather" source_file="a.py" source_line=3}
  %conditional.1 = f32[4]{0} conditional(%c, %fusion.1, %fusion.1), branch_computations={%branch_0, %branch_1}, metadata={op_name="jit(blk)/while/body/vmap(fl.encode)/cond"}
  ROOT %custom-call.1 = f32[8,128]{1,0} custom-call(%conditional.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(blk)/while/body/vmap(fl.encode)/jit(ef_update_2d)/ef_update/pallas_call"}
}

ENTRY %main (p.3: f32[4]) -> f32[4] {
  %p.3 = f32[4]{0} parameter(0)
  ROOT %while.1 = f32[4]{0} while(%p.3), condition=%cond, body=%body, metadata={op_name="jit(blk)/while"}
}
'''


def scoped_trace():
    """One chip. The block executable ``jit_blk`` runs over [4,40]: a
    ``while`` [4,40] holding ``fusion.1`` [4,14] (``fl.batch``),
    ``fusion.2`` [16,19] (no op_name; a branch of the ``fl.encode``
    conditional) and the ``ef_update`` kernel [24,34] (``fl.encode``); the
    eval's ``fusion.9`` runs in ``jit_eval_acc`` over [48,52]. Spans: block
    [0,60], run_block [0,44], engine.dispatch [1,6], engine.sync [6,43],
    eval [46,56]."""
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("%while.1 = (f32[4]{0}) while(f32[4]{0} %p)", 4, 40),
        _ev("fusion.1", 4, 14),
        _ev("fusion.2", 16, 19),
        _ev("custom-call.1", 24, 34, long_name=KERNEL),
        _ev("fusion.9", 48, 52),
    ]), NS(name="XLA Modules", events=[_ev("jit_blk(1)", 4, 40),
                                       _ev("jit_eval_acc(2)", 48, 52)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.block", 0, 60), _ev("bench.run_block", 0, 44),
        _ev("engine.dispatch", 1, 6), _ev("engine.sync", 6, 43),
        _ev("bench.eval", 46, 56), _ev("other", 2, 3)])])
    return NS(planes=[device, host])


def test_scoped_made_up_trace_by_hand():
    v = phasetrace.from_profile(scoped_trace())
    # without HLO text no operation has a phase; the kernel is named by
    # its instruction
    assert v.phase_s() is None
    assert "custom-call tpu_custom_call" in dict(v.breakdown()["device_ops"])
    names = v.attach_hlo(SCOPED_HLO)
    assert names.module == "jit_blk"
    assert names.kernel == {"custom-call.1": "ef_update"}
    # fusion.2 has no op_name: its computation's caller, the conditional,
    # is under fl.encode; the while is under no scope
    assert names.phase["fusion.2"] == "fl.encode"
    assert names.phase["while.1"] == phasetrace.UNSCOPED
    # self times: while 36 - 10 - 3 - 10 = 13, fusion.1 10, fusion.2 3,
    # kernel 10; the eval's op is another executable's
    assert v.phase_s() == {phasetrace.UNSCOPED: pytest.approx(13e-9),
                           "fl.batch": pytest.approx(10e-9),
                           "fl.encode": pytest.approx(13e-9)}
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
    assert dict(b["device_ops"])["ef_update tpu_custom_call"] == \
        pytest.approx(10e-9)
    assert b["unscoped_ops"] == [["while.1 = (f32[4]) while",
                                  pytest.approx(13e-9)]]
    # one round in the window: nanoseconds -> 1e-6 ms
    assert phasetrace.metrics(v, 1) == {
        "batch_ms.per_round": pytest.approx(10e-6),
        "local_train_ms.per_round": 0.0,
        "encode_ms.per_round": pytest.approx(13e-6),
        "server_ms.per_round": 0.0,
        "unscoped_share.round": pytest.approx(100 * 13 / 36),
        "dispatch_idle_ms.per_block": pytest.approx(3e-6),
        "sync_idle_ms.per_block": pytest.approx(3e-6)}


def test_metrics_leave_out_what_the_trace_lacks():
    """A trace of a program without the spans or the scopes (no HLO text
    attached, no ``engine.*`` span) gives no number."""
    trace = scoped_trace()
    host = trace.planes[1].lines[0]
    host.events = [e for e in host.events if not e.name.startswith("engine.")]
    assert phasetrace.metrics(phasetrace.from_profile(trace), 1) == {}


def test_self_times_take_no_parent_from_a_rounding_overlap():
    """A ``while`` [0,40] holding siblings [0,10] and [9,20]: the second
    seems to start a nanosecond before the first ends, as whole-nanosecond
    rounding can make it, and is still the ``while``'s child."""
    ops = [Op(0, 40, "while.1", False, 0), Op(0, 10, "a", False, 0),
           Op(9, 20, "b", False, 0)]
    assert phasetrace._self_times(ops) == [40 - 10 - 11, 10, 11]


SCOPED = os.path.join(ROOT, "bench", "testdata", "scoped.xplane.pb.gz")
SCOPED_TEXT = os.path.join(ROOT, "bench", "testdata", "scoped.hlo.txt.gz")
SCOPED_BY_HAND = os.path.join(ROOT, "bench", "testdata", "scoped.json")


def test_recorded_scoped_trace_by_hand():
    """Three blocks of the toy ``mlp-3sfc`` of the scoped program on one
    v5e chip, with its block's HLO text; the figures in ``scoped.json``
    come from the raw events by other means (``how`` there)."""
    want = spec.load_json(SCOPED_BY_HAND)
    v = phasetrace.load(SCOPED, 1)
    assert v.phase_s() is None
    with gzip.open(SCOPED_TEXT, "rt") as f:
        v.attach_hlo(f.read())
    assert v.window_s() == pytest.approx(want["window_s"], rel=1e-12)
    assert v.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert list(v.hlo_matched()) == want["hlo_matched"]
    assert v.phase_s() == pytest.approx(want["phase_s"], rel=1e-9)
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
    got = phasetrace.metrics(v, rounds)
    ctx = NS(view=v, rounds=rounds, blocks=want["blocks"], chips=1)
    device_ms = run.load_reader("device_ms.per_round")(ctx)
    gap_ms = run.load_reader("host_gap_ms.per_block")(ctx)
    phases = sum(got[m] for m in ("batch_ms.per_round",
                                  "local_train_ms.per_round",
                                  "encode_ms.per_round",
                                  "server_ms.per_round"))
    block_ms = phases / (1 - got["unscoped_share.round"] / 100)
    eval_ms = 1e3 * want["other_s"] / rounds
    # (busy time also counts, clipped, an op that began before the window)
    assert block_ms + eval_ms == pytest.approx(device_ms, rel=0.01)
    assert 0 < got["dispatch_idle_ms.per_block"] + \
        got["sync_idle_ms.per_block"] <= gap_ms
    assert {"engine.dispatch", "engine.sync"} & \
        {name for name, _ in v.idle_gaps()}
