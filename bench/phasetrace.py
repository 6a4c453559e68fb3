"""The round's device time by phase and the block boundary's idle time by
the program's own host span, from a ``jax.profiler`` trace of a cell.

    python3 bench/phasetrace.py --workload mlp-3sfc --seed 7      # on a TPU
    python3 bench/phasetrace.py --workload mlp-3sfc --seed 7 --tiny \\
        --out bench/testdata/scoped        # re-record the committed fixture

Builds the cell's program as ``bench/run.py`` does, warms it, traces a
``run.TRACE_SECONDS`` window of back-to-back blocks, takes the block
executable's optimized HLO text from the program after the window
(``RoundEngine.block_hlo_text``), and prints one JSON object: the seven
numbers of ``metrics`` beside the benchmark's ``device_ms.per_round`` and
``host_gap_ms.per_block`` read from the same trace, and a breakdown. With
``--out PREFIX`` it also writes ``PREFIX.xplane.pb.gz`` and
``PREFIX.hlo.txt.gz``; ``--tiny`` builds the cell at
``bench/record_testdata.py``'s toy size and traces three blocks.

It extends ``bench/devtrace.py``'s reduction with what the program puts in
a trace:

* the program's spans (``engine.dispatch`` around a block's dispatch,
  ``engine.sync`` around its metrics fetch; ``repro.obs.trace.Tracer`` is
  a ``TraceAnnotation`` while a profiler session records) are on the same
  host planes as the benchmark's ``bench.*`` spans, so the boundary's idle
  time and each idle gap are named by the innermost span open in them;
* the ``XLA Modules`` line of a chip says which executable ran when, so an
  operation belongs to the executable whose run holds its start; given
  that executable's optimized HLO text (``attach_hlo``), an operation's
  phase is the innermost of the round's named scopes (``PHASES``) in its
  instruction's ``op_name``, or, where that names none, the phase of the
  instruction that calls its computation (a ``while`` body or a
  ``conditional`` branch), and ``UNSCOPED`` where neither names one;
* a Pallas call is named by its kernel (``pallas_call(name=...)``).
"""
from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import importlib
import json
import os
import re
import shutil
import sys
import tempfile
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import devtrace, run, spec  # noqa: E402
from bench.devtrace import NS, Op, Span, TraceView  # noqa: E402

SPAN_PREFIXES = ("bench.", "engine.")   # the host spans read from a trace
MODULES_LINE = "XLA Modules"
# the round's named scopes (repro.fl.round.PHASE_SCOPES)
PHASES = ("fl.batch", "fl.local_train", "fl.encode", "fl.gather",
          "fl.aggregate", "fl.update")
SERVER = ("fl.gather", "fl.aggregate", "fl.update")
UNSCOPED = "unscoped"
_PHASE = re.compile(r"\bfl\.(?:batch|local_train|encode|gather|aggregate|"
                    r"update)\b")


class HloNames(NamedTuple):
    """What one executable's optimized HLO text says of its instructions."""
    module: str                      # the executable's name, as ``jit_blk``
    phase: Dict[str, str]            # instruction -> phase or UNSCOPED
    kernel: Dict[str, str]           # tpu_custom_call instruction -> kernel


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply|branch_computations|"
                     r"called_computations)=(?:\{([^}]*)\}|%?([\w.\-]+))")


def read_hlo(text: str) -> HloNames:
    """Each instruction's phase, and each Pallas call's kernel name, from an
    executable's optimized HLO text (``compiled.as_text()``).

    A kernel's name is the name stack's component before ``pallas_call``
    (``pallas_call(name=...)`` puts it there), else the instruction's name
    less its ``.N`` suffix."""
    head = re.match(r"HloModule ([\w.\-]+)", text)
    comp = None
    comp_of: Dict[str, str] = {}
    own: Dict[str, Optional[str]] = {}
    caller: Dict[str, str] = {}
    kernel: Dict[str, str] = {}
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        comp_of[name] = comp
        op = _OP_NAME.search(line)
        op_name = op.group(1) if op else ""
        found = _PHASE.findall(op_name)
        own[name] = found[-1] if found else None
        for group, single in _CALLED.findall(line):
            for c in (group or single).split(","):
                caller.setdefault(c.strip().lstrip("%"), name)
        if 'custom_call_target="tpu_custom_call"' in line:
            parts = op_name.split("/")
            kernel[name] = parts[-2] if len(parts) > 1 and \
                parts[-1] == "pallas_call" else _stem(name)
    phase: Dict[str, str] = {}
    for name in own:
        seen, cur = [], name
        while cur is not None and cur not in phase and own[cur] is None:
            seen.append(cur)
            cur = caller.get(comp_of[cur])
            if cur in seen:
                cur = None
        got = UNSCOPED if cur is None else phase.get(cur) or own[cur]
        for n in seen + [name]:
            phase.setdefault(n, got)
    return HloNames(head.group(1) if head else "", phase, kernel)


def _stem(instruction: str) -> str:
    """``fused_cosine.3`` -> ``fused_cosine``."""
    return re.sub(r"\.\d+$", "", instruction)


def _instruction(name: str) -> str:
    """An ``Op``'s name to its instruction's: ``fusion.12 = f32[8,128]
    fusion`` -> ``fusion.12``."""
    return name.split(" ", 1)[0].lstrip("%")


def _self_times(ops: Sequence[Op]) -> List[float]:
    """Each operation's duration less that of the operations it encloses
    (``ops`` as recorded on one chip's line). An operation's parent is the
    latest-starting one still open at its end: the trace rounds times to
    whole nanoseconds, so a sibling can seem to end a nanosecond after its
    neighbour starts, and is then no parent."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    self_t = [o.end - o.start for o in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end < ops[i].end:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return self_t


class PhaseView(TraceView):
    """A ``devtrace.TraceView`` that also holds the program's spans and
    each chip's executable runs, and names operations by phase once an
    executable's HLO text is attached."""

    def __init__(self, chips: List[List[Op]], spans: List[Span],
                 modules: List[List[Span]]):
        super().__init__(chips, spans)
        self.modules = [sorted(m) for m in modules]   # per chip, by start
        self._hlo: Dict[str, HloNames] = {}
        self._edges: Dict[Tuple[int, int], Tuple[float, float]] = {}
        self._self: Dict[int, List[Tuple[Op, float]]] = {}
        self._prefix: Dict[int, Tuple[list, list, list]] = {}

    def attach_hlo(self, text: str) -> HloNames:
        """Name the operations of the executable whose optimized HLO text
        this is (``read_hlo``) by phase and kernel."""
        names = read_hlo(text)
        self._hlo[names.module] = names
        return names

    def has_span(self, name: str) -> bool:
        return any(s.name == name for s in self.spans)

    # -- host boundaries ----------------------------------------------------
    def _scan_edges(self, k: int, chip: int) -> Tuple[float, float]:
        """First start and last end of chip ``chip``'s operations inside
        block ``k``'s ``bench.run_block`` span (the block's own scan, as
        ``boundary_gaps_s`` finds it); both the block's start where there
        are none."""
        if (k, chip) not in self._edges:
            blk = self.blocks[k]
            inner = [s for s in self.spans if s.name == "bench.run_block"
                     and s.start >= blk.start and s.end <= blk.end]
            mine = [o for o in self.chips[chip]
                    if inner and o.start >= inner[0].start
                    and o.end <= inner[0].end]
            if mine:
                edges = (min(o.start for o in mine), max(o.end for o in mine))
            else:
                edges = (blk.start, blk.start)
            self._edges[k, chip] = edges
        return self._edges[k, chip]

    def _busy_until(self, chip: int, t: float) -> float:
        """Busy nanoseconds of chip ``chip`` before ``t``."""
        if chip not in self._prefix:
            merged = self._merged[chip]
            cum = [0.0]
            for s, e in merged:
                cum.append(cum[-1] + e - s)
            self._prefix[chip] = ([s for s, _ in merged],
                                  [e for _, e in merged], cum)
        starts, ends, cum = self._prefix[chip]
        k = bisect.bisect_right(starts, t)
        if k == 0:
            return 0.0
        return cum[k - 1] + min(t, ends[k - 1]) - starts[k - 1]

    def _innermost(self, t: float) -> str:
        """The shortest span open at ``t``, or "none"."""
        open_ = [s for s in self.spans if s.start <= t <= s.end]
        return min(open_, key=lambda s: s.end - s.start).name \
            if open_ else "none"

    def boundary_idle_s(self) -> Dict[str, float]:
        """The idle time of ``boundary_gaps_s`` (summed over the blocks,
        averaged over the chips) by the innermost span open in it:
        ``engine.dispatch``, ``engine.sync``, ``bench.eval``, or a
        benchmark span with no program span inside."""
        out: Dict[str, float] = {}
        for k, blk in enumerate(self.blocks):
            for chip in range(len(self.chips)):
                a, b = self._scan_edges(k, chip)
                for p, q in ((blk.start, a), (b, blk.end)):
                    cuts = sorted({p, q} | {x for s in self.spans
                                            for x in (s.start, s.end)
                                            if p < x < q})
                    for x, y in zip(cuts, cuts[1:]):
                        idle = (y - x) - (self._busy_until(chip, y)
                                          - self._busy_until(chip, x))
                        if idle > 0:
                            name = self._innermost((x + y) / 2)
                            out[name] = out.get(name, 0.0) + idle * NS
        return {k: v / len(self.chips) for k, v in out.items()}

    # -- executables and phases ---------------------------------------------
    def _self_ops(self, chip: int) -> List[Tuple[Op, float]]:
        """Chip ``chip``'s operations inside the window, each with its self
        time in nanoseconds."""
        if chip not in self._self:
            inside = [o for o in self.chips[chip]
                      if o.start >= self.t0 and o.end <= self.t1]
            self._self[chip] = list(zip(inside, _self_times(inside)))
        return self._self[chip]

    def _names_of(self, chip: int, op: Op) -> Optional[HloNames]:
        """The attached HLO names of the executable whose run holds
        ``op``'s start, if any."""
        mods = self.modules[chip]
        k = bisect.bisect_right(mods, (op.start, float("inf"))) - 1
        if k < 0 or op.start > mods[k].end:
            return None
        return self._hlo.get(mods[k].name)

    def phase_s(self) -> Optional[Dict[str, float]]:
        """Self seconds of the operations of the executables whose HLO text
        is attached, by phase (``PHASES`` or ``UNSCOPED``; an instruction
        the text lacks is ``UNSCOPED``), averaged over the chips. None where
        no attached executable ran in the window."""
        out: Dict[str, float] = {}
        for chip in range(len(self.chips)):
            for o, t in self._self_ops(chip):
                names = self._names_of(chip, o)
                if names is not None:
                    ph = names.phase.get(_instruction(o.name), UNSCOPED)
                    out[ph] = out.get(ph, 0.0) + t * NS
        return {k: v / len(self.chips) for k, v in out.items()} or None

    def hlo_matched(self) -> Tuple[int, int]:
        """(operations found in their executable's attached HLO text,
        operations of executables with attached text), over the chips."""
        found = total = 0
        for chip in range(len(self.chips)):
            for o, _ in self._self_ops(chip):
                names = self._names_of(chip, o)
                if names is not None:
                    total += 1
                    found += _instruction(o.name) in names.phase
        return found, total

    def _label(self, chip: int, op: Op) -> str:
        """A Pallas call by its kernel's name, any other operation by its
        instruction."""
        if not op.pallas:
            return op.name
        names = self._names_of(chip, op)
        instr = _instruction(op.name)
        kernel = names.kernel.get(instr) if names is not None else None
        return (kernel or _stem(instr)) + " tpu_custom_call"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The operations with the most self time (summed over the chips,
        by instruction; Pallas calls by kernel) and the longest idle gaps
        of chip 0 (named by the benchmark's or the program's innermost
        span); the boundary's idle seconds by span; with HLO text
        attached, the seconds by phase and the unscoped operations with the
        most self time."""
        totals: Dict[str, float] = {}
        unscoped: Dict[str, float] = {}
        for chip in range(len(self.chips)):
            for o, t in self._self_ops(chip):
                key = self._label(chip, o)
                totals[key] = totals.get(key, 0.0) + t * NS
                names = self._names_of(chip, o)
                if names is not None and names.phase.get(
                        _instruction(o.name), UNSCOPED) == UNSCOPED:
                    unscoped[key] = unscoped.get(key, 0.0) + t * NS

        def _top(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        out = {"device_ops": _top(totals),
               "idle_gaps": [[k, v] for k, v in sorted(
                   self.idle_gaps(), key=lambda kv: -kv[1])[:top]],
               "boundary_idle_s": self.boundary_idle_s()}
        phases = self.phase_s()
        if phases is not None:
            out["phase_s"] = phases
            out["unscoped_ops"] = _top(unscoped)
        return out


def metrics(view: PhaseView, rounds: int) -> Dict[str, float]:
    """The per-phase and per-span numbers of a traced window of ``rounds``
    rounds, by the names a benchmark entry would give them; a number whose
    source the trace lacks (no HLO text attached, no such span) is left
    out."""
    out: Dict[str, float] = {}
    phases = view.phase_s()
    if phases:
        for name, scopes in (("batch_ms.per_round", ("fl.batch",)),
                             ("local_train_ms.per_round", ("fl.local_train",)),
                             ("encode_ms.per_round", ("fl.encode",)),
                             ("server_ms.per_round", SERVER)):
            out[name] = 1e3 * sum(phases.get(s, 0.0) for s in scopes) / rounds
        out["unscoped_share.round"] = \
            100.0 * phases.get(UNSCOPED, 0.0) / sum(phases.values())
    split = view.boundary_idle_s()
    for span in ("engine.dispatch", "engine.sync"):
        if view.has_span(span):
            name = span.split(".")[1] + "_idle_ms.per_block"
            out[name] = 1e3 * split.get(span, 0.0) / len(view.blocks)
    return out


def from_profile(pd, chips: Optional[int] = None) -> PhaseView:
    """Build the view from a ``jax.profiler.ProfileData``."""
    base = devtrace.from_profile(pd, chips)
    modules: Dict[int, List[Span]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m:
            modules[int(m.group(1))] = [
                Span(e.start_ns, e.start_ns + e.duration_ns,
                     e.name.split("(", 1)[0])
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [Span(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIXES)]
    ids = sorted(modules)[:len(base.chips)]
    return PhaseView(base.chips, spans, [modules[i] for i in ids])


def load(path: str, chips: Optional[int] = None) -> PhaseView:
    """From an ``.xplane.pb`` file, or a gzipped one (``.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()),
                                chips)
    return from_profile(ProfileData.from_file(path), chips)


def record(workload: str, seed: int, tiny: bool, out: Optional[str]):
    """Trace a window of ``workload`` on the chip; returns the view with
    the block's HLO text attached, and the window's rounds."""
    os.environ[run.HOIST_ENV] = "1"
    import jax
    cell = spec.Cell(spec.benchmark(), workload)
    run.tpu_devices(cell.chips)
    run.enable_cache()
    run.hoist_constants()
    config, traffic = cell.config, cell.traffic
    if tiny:
        from bench.record_testdata import TINY_CONFIG, TINY_TRAFFIC
        config, traffic = dict(config, **TINY_CONFIG), \
            dict(traffic, **TINY_TRAFFIC)
    family = importlib.import_module(f"bench.families.{config['family']}")
    program = family.Program(config, traffic, seed % run.SEED_MOD)
    run.window(program, 0.0, 2)
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tdir)
        try:
            w = run.window(program, 0.0 if tiny else run.TRACE_SECONDS,
                           run.MIN_TRACED_BLOCKS)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True)
        text = program.engine.block_hlo_text(program.state, program.every)
        view = load(path, cell.chips)
        if out:
            with open(path, "rb") as f, \
                    gzip.open(out + ".xplane.pb.gz", "wb") as g:
                g.write(f.read())
            with gzip.open(out + ".hlo.txt.gz", "wt") as g:
                g.write(text)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    program.close()
    view.attach_hlo(text)
    return view, w.rounds


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    view, rounds = record(args.workload, args.seed, args.tiny, args.out)
    gaps = view.boundary_gaps_s()
    found, total = view.hlo_matched()
    result = dict(metrics(view, rounds), rounds=rounds,
                  blocks=len(view.blocks), hlo_matched=[found, total])
    result["device_ms.per_round"] = 1e3 * view.busy_s() / rounds
    result["host_gap_ms.per_block"] = 1e3 * sum(gaps) / len(gaps)
    result["breakdown"] = view.breakdown()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
