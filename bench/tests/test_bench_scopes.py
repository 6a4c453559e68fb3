"""The round's phase scopes in the cell's block executable, on the CPU at
a toy size: the optimized HLO names every phase of a vmap round in its
op_names, as ``bench/phasetrace.py`` reads them, and the scopes change no
instruction."""
import contextlib
import os
import re
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import phasetrace, spec  # noqa: E402
from bench.families import vision  # noqa: E402
from repro.fl.round import PHASE_SCOPES  # noqa: E402

TINY_CONFIG = {"train_size": 2000, "test_size": 100}
TINY_TRAFFIC = {"clients": 2, "local_steps": 1, "batch": 8, "eval_every": 2}
VMAP_PHASES = ("fl.batch", "fl.local_train", "fl.encode", "fl.aggregate",
               "fl.update")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_METADATA = re.compile(r', metadata=\{(?:[^{}"]|"[^"]*")*\}')


def _block_text() -> str:
    cell = spec.Cell(spec.benchmark(), "mlp-3sfc")
    program = vision.Program(dict(cell.config, **TINY_CONFIG),
                             dict(cell.traffic, **TINY_TRAFFIC), 7)
    return program.engine.block_hlo_text(program.state, program.every)


def _instructions(text: str):
    """The HLO text without its source-location tables and metadata."""
    out, table = [], False
    for line in text.splitlines():
        if line in _TABLES:
            table = True
        elif table and line.startswith(("%", "ENTRY")):
            table = False
        if not table:
            out.append(_METADATA.sub("", line))
    return out


def test_block_names_every_phase_and_scopes_change_no_instruction(
        monkeypatch):
    text = _block_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in VMAP_PHASES:
        assert any(scope in o for o in op_names), scope
    names = phasetrace.read_hlo(text)
    assert names.module == "jit_blk"
    assert set(VMAP_PHASES) <= set(names.phase.values())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _block_text()
    assert not any(s in bare for s in PHASE_SCOPES)
    assert len(_instructions(text)) > 1000
    assert _instructions(bare) == _instructions(text)


def test_devtrace_reads_the_programs_scopes():
    assert phasetrace.PHASES == PHASE_SCOPES
