"""The ConvNet cell's convolutions scope and its three readers.

On the CPU at a toy size, each cell's block executable: the ConvNet's
names ``cnn.conv`` (``models/layers.CONV_SCOPE``) inside local training
and inside the 3SFC encode, the MLP's nowhere, and the scopes change no
instruction. On a made-up trace: ``conv_train_ms.per_round`` and
``conv_encode_ms.per_round`` read the operations under both their phase
and ``cnn.conv``, ``conv_peak_share.round`` counts the operations a round
requires by hand, and all three read nothing where the program names no
``cnn.conv``, as a program without the scope does."""
import contextlib
import os
import re
import sys
from types import SimpleNamespace as NS

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import devtrace, run, spec  # noqa: E402
from bench.families import vision  # noqa: E402
from bench.tests._traces import SCOPED_HLO, ctx_for, ev, scoped_trace  # noqa: E402
from bench.tests.test_bench_scopes import (TINY_CONFIG,  # noqa: E402
                                           TINY_TRAFFIC, _instructions)
from repro.fl.round import PHASE_SCOPES  # noqa: E402
from repro.models.layers import CONV_SCOPE  # noqa: E402

TESTDATA = os.path.join(ROOT, "bench", "testdata")
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
_NAME = re.compile(r"%[\w.\-]+")


def _block_text(name: str) -> str:
    cell = spec.Cell(spec.benchmark(), name)
    program = vision.Program(dict(cell.config, **TINY_CONFIG),
                             dict(cell.traffic, **TINY_TRAFFIC), 7)
    return program.block_hlo()


def _renamed(lines):
    """Every ``%name`` numbered by its first appearance: XLA:CPU numbers
    some instructions of the ConvNet's block in an order that the op_name
    metadata moves, so two texts are the same program when they are equal
    up to these names."""
    names = {}
    return [_NAME.sub(lambda m: names.setdefault(m.group(), f"%{len(names)}"),
                      line) for line in lines]


@pytest.mark.parametrize("name", CELLS)
def test_block_names_the_convolutions_and_the_scope_changes_no_instruction(
        monkeypatch, name):
    text = _block_text(name)
    chains = devtrace.read_hlo(text).chain.values()
    # the phases that hold the convolutions' scope
    conv = {s for c in chains if CONV_SCOPE in c for s in c
            if s in PHASE_SCOPES}
    model = spec.Cell(spec.benchmark(), name).config["model"]
    assert conv == ({"fl.local_train", "fl.encode"} if model == "convnet"
                    else set()), conv
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _block_text(name)
    assert CONV_SCOPE not in bare
    assert len(_instructions(text)) > 1000
    assert _renamed(_instructions(bare)) == _renamed(_instructions(text))


CONV_HLO = '''HloModule jit_blk, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%body (p.2: f32[4]) -> f32[4] {
  %p.2 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p.2), kind=kOutput, calls=%fc.1, metadata={op_name="jit(blk)/while/body/vmap(fl.local_train)/while/body/jvp(cnn.conv)/conv_general_dilated"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kOutput, calls=%fc.2, metadata={op_name="jit(blk)/while/body/vmap(fl.local_train)/while/body/transpose(jvp(cnn.conv))/conv_general_dilated"}
  %fusion.3 = f32[4]{0} fusion(%fusion.2), kind=kOutput, calls=%fc.3, metadata={op_name="jit(blk)/while/body/vmap(fl.local_train)/while/body/transpose(jvp())/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%fusion.3), kind=kOutput, calls=%fc.4, metadata={op_name="jit(blk)/while/body/vmap(fl.encode)/while/body/jvp(transpose(jvp(cnn.conv)))/conv_general_dilated"}
  %fusion.5 = f32[4]{0} fusion(%fusion.4), kind=kLoop, calls=%fc.5, metadata={op_name="jit(blk)/while/body/vmap(fl.encode)/while/body/mul"}
  %fusion.6 = f32[4]{0} fusion(%fusion.5), kind=kLoop, calls=%fc.6, metadata={op_name="jit(blk)/while/body/vmap(fl.local_train)/cnn.conv/add;vmap(fl.encode)/add"}
  ROOT %fusion.8 = f32[4]{0} fusion(%fusion.6), kind=kLoop, calls=%fc.8, metadata={op_name="jit(blk)/while/body/fl.update/sub"}
}

ENTRY %main (p.3: f32[4]) -> f32[4] {
  %p.3 = f32[4]{0} parameter(0)
  ROOT %while.1 = f32[4]{0} while(%p.3), condition=%cond, body=%body, metadata={op_name="jit(blk)/while"}
}
'''


def conv_trace():
    """One chip, one block of ``jit_blk`` over [0,100]: a ``while`` holding
    convolutions under ``cnn.conv`` in local training (``fusion.1``
    [0,20], ``fusion.2`` [20,30]) and in the encode (``fusion.4``
    [40,52]), a merged operation under both phases and ``cnn.conv``
    (``fusion.6`` [60,64]), and operations under a phase alone: the dense
    layer's ``fusion.3`` [30,35] (local training), ``fusion.5`` [52,60]
    (encode) and ``fusion.8`` [80,90] (``fl.update``)."""
    device = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("while.1", 0, 100), ev("fusion.1", 0, 20), ev("fusion.2", 20, 30),
        ev("fusion.3", 30, 35), ev("fusion.4", 40, 52), ev("fusion.5", 52, 60),
        ev("fusion.6", 60, 64), ev("fusion.8", 80, 90)]),
        NS(name="XLA Modules", events=[ev("jit_blk(1)", 0, 100)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.block", 0, 110), ev("bench.run_block", 0, 102)])])
    return NS(planes=[device, host])


CONV_READERS = ("conv_train_ms.per_round", "conv_encode_ms.per_round",
                "conv_peak_share.round")
# the ConvNet's four 3x3 SAME convolutions at 32x32x3, forward operations
# per sample: 2 * H_out * W_out * 9 * C_in * C_out
CONV_F = (2 * 32 * 32 * 9 * 3 * 32 + 2 * 16 * 16 * 9 * 32 * 64
          + 2 * 8 * 8 * 9 * 64 * 128 + 2 * 4 * 4 * 9 * 128 * 256)


def _convnet_ctx(view, rounds):
    return ctx_for(view, rounds=rounds,
                   cell=spec.Cell(spec.benchmark(), "convnet-3sfc"))


def test_conv_readers_read_the_convolutions_inside_each_phase():
    """Local training's convolutions are ``fusion.1``, ``.2`` and the
    merged ``.6`` (34 ns), the encode's ``fusion.4`` and ``.6`` (16 ns);
    ``fusion.3`` and ``.5`` lie under a phase alone and count nowhere. The
    share counts ``.6`` once (46 ns)."""
    v = devtrace.from_profile(conv_trace())
    v.attach_hlo(CONV_HLO)
    rounds = 2
    read = {m: run.load_reader(m)(_convnet_ctx(v, rounds))
            for m in CONV_READERS}
    assert read["conv_train_ms.per_round"] == pytest.approx(
        1e3 * 34e-9 / rounds, rel=1e-9)
    assert read["conv_encode_ms.per_round"] == pytest.approx(
        1e3 * 16e-9 / rounds, rel=1e-9)
    # per client 3*F*K*B in local training and (3*S + 1)*3*F*n in the
    # encode (K = 5, B = 32, S = 10, n = 1), times N = 10 clients
    assert 10 * (3 * CONV_F * 5 * 32 + 31 * 3 * CONV_F * 1) \
        == 172_364_267_520
    assert read["conv_peak_share.round"] == pytest.approx(
        100 * 172_364_267_520 / (46e-9 / rounds * 197e12), rel=1e-9)


def _short_match():
    v = devtrace.from_profile(conv_trace())
    v.attach_hlo("\n".join(line for line in CONV_HLO.splitlines()
                           if "%fusion.3 =" not in line))
    assert v.hlo_matched() == (7, 8) and CONV_SCOPE in v.scope_names()
    return v


def _no_scope():
    v = devtrace.from_profile(scoped_trace())
    v.attach_hlo(SCOPED_HLO)
    return v


def _recorded_mlp():
    import gzip
    v = devtrace.load(os.path.join(TESTDATA, "scoped.xplane.pb.gz"), 1)
    with gzip.open(os.path.join(TESTDATA, "scoped.hlo.txt.gz"), "rt") as f:
        v.attach_hlo(f.read())
    return v


@pytest.mark.parametrize("view", [
    lambda: devtrace.from_profile(conv_trace()), _short_match, _no_scope,
    _recorded_mlp], ids=["no_text", "short_match", "no_scope", "recorded_mlp"])
def test_conv_readers_read_nothing_without_the_scope(view):
    ctx = _convnet_ctx(view(), rounds=6)
    for m in CONV_READERS:
        assert run.load_reader(m)(ctx) is None, m
