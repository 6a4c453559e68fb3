"""The trace reduction (``bench/devtrace.py``) against figures worked out
by hand: on a small made-up trace, and on a small trace recorded on a TPU
v5e chip (``bench/testdata/tiny.xplane.pb``, made by
``bench/record_testdata.py``)."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devtrace, spec  # noqa: E402
from bench.tests._traces import KERNEL, ev as _ev  # noqa: E402


def made_up_trace():
    """One chip. Ops: a ``while`` over [0,32] holding [0,10], [12,15] and a
    Pallas kernel at [20,30]; the eval's op at [36,40]. Spans: block
    [0,50], run_block [0,32], eval [35,45]."""
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("%while.1 = (f32[4]{0}) while(f32[4]{0} %p)", 0, 32),
        _ev("fusion.1", 0, 10),
        _ev("fusion.2", 12, 15),
        _ev("custom-call.1", 20, 30, long_name=KERNEL),
        _ev("fusion.9", 36, 40),
    ]), NS(name="XLA Modules", events=[_ev("jit_blk", 0, 32)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.block", 0, 50), _ev("bench.run_block", 0, 32),
        _ev("bench.eval", 35, 45), _ev("other", 1, 2)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), device, host])


def test_made_up_trace_by_hand():
    v = devtrace.from_profile(made_up_trace())
    assert v.window_s() == pytest.approx(50e-9)
    # busy: [0,32] + [36,40] = 36 ns of 50
    assert v.busy_s() == pytest.approx(36e-9)
    assert v.idle_share() == pytest.approx(14 / 50)
    (k,) = v.pallas_ops()
    assert k.nbytes == 2 * 8 * 128 * 4
    assert v.pallas_s() == pytest.approx(10e-9)
    # the block's scan ends at 32; idle after it: [32,36] + [40,50] = 14 ns
    assert v.boundary_gaps_s() == [pytest.approx(14e-9)]
    assert sorted(v.idle_gaps(), key=lambda g: -g[1]) == [
        ("bench.eval", pytest.approx(10e-9)),
        ("bench.block", pytest.approx(4e-9))]
    ops = dict(v.breakdown()["device_ops"])
    # the while's self time: 32 - 10 - 3 - 10
    assert ops["while.1 = (f32[4]) while"] == pytest.approx(9e-9)
    assert ops["fusion.1"] == pytest.approx(10e-9)


def test_hlo_bytes_counts_the_shapes_in_hbm():
    assert devtrace.hlo_bytes("bf16[2,3]{1,0} f(u32[4], pred[])") == 12 + 16 + 1
    # operands staged in on-chip memory (S(1)) move nothing through HBM
    assert devtrace.hlo_bytes(
        "%k = f32[2,3,8,128]{3,2,1,0:T(8,128)S(1)} custom-call("
        "f32[2,200,1024]{2,1,0:T(8,128)S(1)} %a, f32[4]{0:T(128)} %b), "
        'custom_call_target="tpu_custom_call", '
        "operand_layout_constraints={f32[9]{0}}") == 16


RECORDED = os.path.join(ROOT, "bench", "testdata", "tiny.xplane.pb.gz")
BY_HAND = os.path.join(ROOT, "bench", "testdata", "tiny.json")


def test_recorded_chip_trace_by_hand():
    """Three blocks of the toy ``mlp-3sfc`` on one v5e chip; the figures in
    ``tiny.json`` come from a sweep-line count of the raw events."""
    want = spec.load_json(BY_HAND)
    v = devtrace.load(RECORDED, 1)
    assert v.window_s() == pytest.approx(want["window_s"], rel=1e-12)
    assert v.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert len(v.pallas_ops()) == want["pallas_ops"]
    assert v.pallas_s() == pytest.approx(want["pallas_s"], rel=1e-9)
    assert sum(o.nbytes for o in v.pallas_ops()) == want["pallas_bytes"]
    assert v.boundary_gaps_s() == pytest.approx(want["boundary_gaps_s"],
                                                rel=1e-9)
    assert {o.name.split(".")[0] for o in v.pallas_ops()} == {
        "fused_cosine_2d", "vmap_jit_ef_update_2d__"}
