"""Made-up traces and reader contexts that ``bench/tests`` share."""
import os
from types import SimpleNamespace as NS

from bench import run, spec
from bench.record_testdata import TINY_TRAFFIC

KERNEL = ('%custom-call.1 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), '
          'custom_call_target="tpu_custom_call"')


def ev(name, start, end, **stats):
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
        ev("%while.1 = (f32[4]{0}) while(f32[4]{0} %p)", 4, 40),
        ev("fusion.1", 4, 14),
        ev("fusion.2", 16, 19),
        ev("custom-call.1", 24, 34, long_name=KERNEL),
        ev("fusion.9", 48, 52),
    ]), NS(name="XLA Modules", events=[ev("jit_blk(1)", 4, 40),
                                       ev("jit_eval_acc(2)", 48, 52)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.block", 0, 60), ev("bench.run_block", 0, 44),
        ev("engine.dispatch", 1, 6), ev("engine.sync", 6, 43),
        ev("bench.eval", 46, 56), ev("other", 2, 3)])])
    return NS(planes=[device, host])


def tiny_cell(name="mlp-3sfc"):
    """The cell at the toy round at which the fixtures were recorded."""
    cell = spec.Cell(spec.benchmark(), name)
    cell.traffic = dict(cell.traffic, **TINY_TRAFFIC)
    return cell


def ctx_for(view, rounds, blocks=1, cell=None):
    """A reader's context for ``view``, as ``run.run`` builds it on a TPU
    v5e for ``cell`` (the toy ``mlp-3sfc`` by default)."""
    peaks = spec.load_json(os.path.join(run.BENCH, "peaks.json"))
    return run.layer_context(cell or tiny_cell(), view, rounds, blocks,
                             peaks["TPU v5 lite"])
