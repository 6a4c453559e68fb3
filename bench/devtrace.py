"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to what the
per-layer metrics read.

A trace holds one plane per TPU chip (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per executed HLO operation and whose ``XLA Modules``
line has one event per run of an executable, and host planes whose threads
carry the benchmark's ``jax.profiler.TraceAnnotation`` spans
(``bench.block`` around a whole block, ``bench.run_block`` around the
engine's dispatch and metrics fetch, ``bench.eval`` around the eval) and
the program's own (``engine.dispatch`` and ``engine.sync``:
``repro.obs.trace.Tracer.span`` is a ``TraceAnnotation`` while a profiler
session records), on the same clock. From these:

* the traced window is the first ``bench.block`` start to the last end;
* a chip is busy while at least one operation runs on it (the union of the
  operation intervals), and idle otherwise;
* an operation's text is its HLO instruction (the event's ``long_name``
  stat where the trace has one, else its name, which on a TPU is that
  text); operations nest (a ``while`` holds its body's), so the breakdown
  counts each operation's self time;
* a Pallas kernel is an operation whose HLO is a ``tpu_custom_call``; its
  HBM bytes are those of its operands (read once) and results (written
  once) that lie in HBM, from the shapes and layouts in its text; XLA
  stages many kernel operands in on-chip memory first (layout ``S(1)``),
  and those calls move nothing through HBM;
* a block's boundary gap is the idle time inside its ``bench.block`` span
  that lies before the first or after the last operation of its
  ``bench.run_block`` span: the time the chip waits for the host to
  dispatch, fetch metrics and run the eval; an idle interval is named by
  the innermost span open in it;
* an operation belongs to the executable whose run holds its start. Given
  that executable's optimized HLO text (``TraceView.attach_hlo``), each of
  its operations has a chain of named scopes (``read_hlo``) and each
  Pallas call its kernel's name.
"""
from __future__ import annotations

import bisect
import gzip
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "engine.")   # the host spans read from a trace
MIN_MATCHED = 0.99       # share of the block's operations found in its HLO
NS = 1e-9


class Op(NamedTuple):
    start: float                     # ns, trace clock
    end: float
    name: str                        # HLO instruction text, shortened
    pallas: bool
    nbytes: int                      # operand + result bytes (Pallas only)


class Span(NamedTuple):
    start: float
    end: float
    name: str


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip_len(merged, a: float, b: float) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged)


_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                    r"\[([0-9,]*)\](\{[^{}]*\})?")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def hlo_bytes(text: str) -> int:
    """HBM bytes of an HLO instruction: every array in its result(s) and
    operands whose layout puts it in memory space 0. An array with
    ``S(n)``, n > 0, in its layout sits in on-chip memory (XLA stages it
    there ahead of the call), so the instruction does not move it through
    HBM. Attributes after the operand list, such as a kernel's backend
    config, are not read."""
    total = 0
    head = re.split(r"\), (?:custom_call_target|backend_config|metadata)=",
                    text, maxsplit=1)[0]
    for dtype, dims, layout in _SHAPE.findall(head):
        if re.search(r"S\([1-9]", layout):
            continue
        n = 1
        for x in dims.split(","):
            if x:
                n *= int(x)
        total += n * _BYTES[dtype]
    return total


def _stat(stats: Dict, *names: str) -> str:
    for n in names:
        if n in stats:
            return str(stats[n])
    return ""


# -- the program's named scopes, from an executable's optimized HLO text ----
class HloNames(NamedTuple):
    """What one executable's optimized HLO text says of its instructions."""
    module: str                          # the executable's name, as ``jit_blk``
    chain: Dict[str, Tuple[str, ...]]    # instruction -> named scopes, outer first
    kernel: Dict[str, str]               # tpu_custom_call instruction -> kernel


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply|branch_computations|"
                     r"called_computations)=(?:\{([^}]*)\}|%?([\w.\-]+))")
_WRAPPER = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
_SCOPE = re.compile(r"^[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+$")


def scopes(op_name: str) -> Tuple[str, ...]:
    """The named scopes of an ``op_name``, outermost first.

    An ``op_name`` is JAX's name stack: components joined by ``/``, the
    last of them the operation's own name; XLA joins the names of merged
    operations with ``;``. A transform wraps the component it applies to
    (``vmap(fl.encode)``, ``transpose(jvp(fl.local_train))``, ``jit(f)``)
    and is stripped. A named scope is a component of the form
    ``<area>.<part>`` (``fl.encode``, ``lm.moe``); JAX's own components
    (``while``, ``body``, ``cond``, a jitted function's name) and argument
    names hold no such dotted name before the last component."""
    out: List[str] = []
    for segment in op_name.split(";"):
        for part in segment.split("/")[:-1]:
            m = _WRAPPER.match(part)
            while m:
                part = m.group(1)
                m = _WRAPPER.match(part)
            if _SCOPE.match(part) and part not in out:
                out.append(part)
    return tuple(out)


def read_hlo(text: str) -> HloNames:
    """Each instruction's chain of named scopes, and each Pallas call's
    kernel name, from an executable's optimized HLO text
    (``compiled.as_text()``).

    An instruction whose ``op_name`` names no scope takes the chain of the
    instruction that calls its computation (a ``while`` body, a
    ``conditional`` branch, a fusion), and the empty chain where no caller
    names one. A kernel's name is the name stack's component before
    ``pallas_call`` (``pallas_call(name=...)`` puts it there), else the
    instruction's name less its ``.N`` suffix."""
    head = re.match(r"HloModule ([\w.\-]+)", text)
    comp = None
    comp_of: Dict[str, str] = {}
    own: Dict[str, Tuple[str, ...]] = {}
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
        own[name] = scopes(op_name)
        for group, single in _CALLED.findall(line):
            for c in (group or single).split(","):
                caller.setdefault(c.strip().lstrip("%"), name)
        if 'custom_call_target="tpu_custom_call"' in line:
            parts = op_name.split("/")
            kernel[name] = parts[-2] if len(parts) > 1 and \
                parts[-1] == "pallas_call" else _stem(name)
    chain: Dict[str, Tuple[str, ...]] = {}
    for name in own:
        seen, cur = [], name
        while cur is not None and cur not in chain and not own[cur]:
            seen.append(cur)
            cur = caller.get(comp_of[cur])
            if cur in seen:
                cur = None
        got = () if cur is None else chain.get(cur) or own[cur]
        for n in seen + [name]:
            chain.setdefault(n, got)
    return HloNames(head.group(1) if head else "", chain, kernel)


def _stem(instruction: str) -> str:
    """``fused_cosine.3`` -> ``fused_cosine``."""
    return re.sub(r"\.\d+$", "", instruction)


def _instruction(name: str) -> str:
    """An ``Op``'s name to its instruction's: ``fusion.12 = f32[8,128]
    fusion`` -> ``fusion.12``."""
    return name.split(" ", 1)[0].lstrip("%")


class TraceView:
    """Per-chip operations and executable runs, and the host spans, of one
    traced window."""

    def __init__(self, chips: List[List[Op]], spans: List[Span],
                 modules: List[List[Span]]):
        self.chips = chips
        self.spans = sorted(spans)
        blocks = [s for s in self.spans if s.name == "bench.block"]
        if not blocks:
            raise ValueError("the trace holds no bench.block span")
        self.blocks = blocks
        self.t0, self.t1 = blocks[0].start, blocks[-1].end
        self._merged = [_union([(o.start, o.end) for o in ops])
                        for ops in chips]
        self.modules = [sorted(m) for m in modules]   # per chip, by start
        self._hlo: Dict[str, HloNames] = {}
        self._self: Dict[int, List[Tuple[Op, float]]] = {}
        self._edges: Dict[Tuple[int, int], Tuple[float, float]] = {}
        self._prefix: Dict[int, Tuple[list, list, list]] = {}
        self._chains: Optional[Tuple[Dict, int, int]] = None

    # -- window -------------------------------------------------------------
    def window_s(self) -> float:
        return (self.t1 - self.t0) * NS

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        return sum(_clip_len(m, self.t0, self.t1) for m in self._merged) \
            * NS / len(self.chips)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def _self_ops(self, chip: int) -> List[Tuple[Op, float]]:
        """Chip ``chip``'s operations inside the window, each with its self
        time in nanoseconds."""
        if chip not in self._self:
            inside = [o for o in self.chips[chip]
                      if o.start >= self.t0 and o.end <= self.t1]
            self._self[chip] = list(zip(inside, _self_times(inside)))
        return self._self[chip]

    # -- kernels ------------------------------------------------------------
    def pallas_ops(self) -> List[Op]:
        return [o for ops in self.chips for o in ops
                if o.pallas and o.start >= self.t0 and o.end <= self.t1]

    def pallas_s(self) -> float:
        return sum(o.end - o.start for o in self.pallas_ops()) * NS \
            / len(self.chips)

    # -- host boundaries ----------------------------------------------------
    def _scan_edges(self, k: int, chip: int) -> Tuple[float, float]:
        """First start and last end of chip ``chip``'s operations inside
        block ``k``'s ``bench.run_block`` span (the block's own scan); both
        the block's start where there are none."""
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

    def boundary_gaps_s(self) -> List[float]:
        """Per block, the chips' idle seconds outside the span of the
        block's own scan (averaged over chips)."""
        out = []
        for k, blk in enumerate(self.blocks):
            gap = 0.0
            for chip, merged in enumerate(self._merged):
                a, b = self._scan_edges(k, chip)
                gap += (a - blk.start) - _clip_len(merged, blk.start, a)
                gap += (blk.end - b) - _clip_len(merged, b, blk.end)
            out.append(gap * NS / len(self.chips))
        return out

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

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle interval of chip 0 inside the window, named by the
        innermost span open at its midpoint."""
        merged = [(s, e) for s, e in self._merged[0]
                  if e > self.t0 and s < self.t1]
        edges = [self.t0] + [x for s, e in merged for x in (s, e)] + [self.t1]
        out = []
        for a, b in zip(edges[::2], edges[1::2]):
            a, b = max(a, self.t0), min(b, self.t1)
            if b > a:
                out.append((self._innermost((a + b) / 2), (b - a) * NS))
        return out

    # -- executables and named scopes ---------------------------------------
    def attach_hlo(self, text: str) -> HloNames:
        """Name the operations of the executable whose optimized HLO text
        this is (``read_hlo``) by scope and kernel."""
        names = read_hlo(text)
        self._hlo[names.module] = names
        self._chains = None
        return names

    def _names_of(self, chip: int, op: Op) -> Optional[HloNames]:
        """The attached HLO names of the executable whose run holds
        ``op``'s start, if any."""
        mods = self.modules[chip]
        k = bisect.bisect_right(mods, (op.start, float("inf"))) - 1
        if k < 0 or op.start > mods[k].end:
            return None
        return self._hlo.get(mods[k].name)

    def _block_chains(self) -> Tuple[Dict[Tuple[str, ...], float], int, int]:
        """Self nanoseconds of the window's operations of executables with
        attached HLO text, summed over the chips by chain of named scopes
        (an instruction the text lacks has the empty chain); the number of
        those operations found in the text, and of all of them."""
        if self._chains is None:
            by_chain: Dict[Tuple[str, ...], float] = {}
            found = total = 0
            for chip in range(len(self.chips)):
                for o, t in self._self_ops(chip):
                    names = self._names_of(chip, o)
                    if names is None:
                        continue
                    chain = names.chain.get(_instruction(o.name))
                    total += 1
                    found += chain is not None
                    key = chain or ()
                    by_chain[key] = by_chain.get(key, 0.0) + t
            self._chains = (by_chain, found, total)
        return self._chains

    def hlo_matched(self) -> Tuple[int, int]:
        """(operations found in their executable's attached HLO text,
        operations of executables with attached text), over the chips."""
        _, found, total = self._block_chains()
        return found, total

    def _block_s(self, keep) -> Optional[float]:
        """Self seconds of the block executable's operations whose chain
        ``keep`` accepts, averaged over the chips; None without attached
        HLO text that names ``MIN_MATCHED`` of the window's operations."""
        by_chain, found, total = self._block_chains()
        if total == 0 or found < MIN_MATCHED * total:
            return None
        return sum(t for c, t in by_chain.items() if keep(c)) * NS \
            / len(self.chips)

    def scope_s(self, *names: str) -> Optional[float]:
        """Self seconds of the block executable's operations whose chain
        holds any of ``names`` (a scope nested in another counts toward
        both), averaged over the chips; None as ``_block_s`` says."""
        return self._block_s(lambda c: any(n in c for n in names))

    def unscoped_s(self) -> Optional[float]:
        """The same, of the operations under no named scope."""
        return self._block_s(lambda c: not c)

    def block_self_s(self) -> Optional[float]:
        """The same, of all the block executable's operations."""
        return self._block_s(lambda c: True)

    def scope_names(self) -> List[str]:
        """Every named scope of the block executable's operations."""
        by_chain, _, _ = self._block_chains()
        return sorted({s for c in by_chain for s in c})

    # -- breakdown ----------------------------------------------------------
    def _label(self, chip: int, op: Op) -> str:
        """A Pallas call by its kernel's name (its instruction's, less the
        ``.N``, without attached HLO text), any other operation by its
        instruction."""
        if not op.pallas:
            return op.name
        names = self._names_of(chip, op)
        instr = _instruction(op.name)
        kernel = names.kernel.get(instr) if names is not None else None
        return (kernel or _stem(instr)) + " tpu_custom_call"

    def _top_ops(self, top: int, unscoped: bool) -> List[list]:
        totals: Dict[str, float] = {}
        for chip in range(len(self.chips)):
            for o, t in self._self_ops(chip):
                if unscoped:
                    names = self._names_of(chip, o)
                    if names is None or names.chain.get(_instruction(o.name)):
                        continue
                key = self._label(chip, o)
                totals[key] = totals.get(key, 0.0) + t * NS
        return [[k, v] for k, v in
                sorted(totals.items(), key=lambda kv: -kv[1])[:top]]

    def unscoped_ops(self, top: int = 10) -> List[list]:
        """The block executable's operations under no named scope with the
        most self time (summed over the chips, by instruction)."""
        return self._top_ops(top, unscoped=True)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The operations with the most self time (summed over the chips,
        by instruction; Pallas calls by kernel) and the longest idle gaps
        of chip 0 (named by the innermost span)."""
        gaps = sorted(self.idle_gaps(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": self._top_ops(top, unscoped=False),
                "idle_gaps": [[k, v] for k, v in gaps]}


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


def _short(text: str) -> str:
    """``%fusion.12 = f32[8,128]{1,0} fusion(...), ...`` ->
    ``fusion.12 = f32[8,128] fusion`` (layouts dropped, the type cut to 60
    characters)."""
    m = re.match(r"%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(", text)
    if not m:
        return text[:120]
    name, typ, opcode = m.groups()
    return f"{name} = {re.sub(r'{[^{}]*}', '', typ)[:60]} {opcode}"


def from_profile(pd, chips: Optional[int] = None) -> TraceView:
    """Build the view from a ``jax.profiler.ProfileData``."""
    devices: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Span]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, runs = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        text = _stat(dict(e.stats), "long_name") or e.name
                        pallas = "tpu_custom_call" in text
                        ops.append(Op(e.start_ns, e.start_ns + e.duration_ns,
                                      _short(text), pallas,
                                      hlo_bytes(text) if pallas else 0))
                elif line.name == MODULES_LINE:
                    runs += [Span(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name.split("(", 1)[0])
                             for e in line.events]
            devices[int(m.group(1))] = ops
            modules[int(m.group(1))] = runs
        elif plane.name.startswith("/host:"):
            spans += [Span(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIXES)]
    ids = sorted(devices)
    if chips is not None:
        ids = ids[:chips]
    if not ids:
        raise ValueError("the trace holds no TPU plane")
    return TraceView([devices[i] for i in ids], spans,
                     [modules[i] for i in ids])


def load(path: str, chips: Optional[int] = None) -> TraceView:
    """From an ``.xplane.pb`` file, or a gzipped one (``.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()),
                                chips)
    return from_profile(ProfileData.from_file(path), chips)
