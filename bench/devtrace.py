"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to what the
per-layer metrics read.

A trace holds one plane per TPU chip (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per executed HLO operation, and host planes whose
threads carry the benchmark's ``jax.profiler.TraceAnnotation`` spans
(``bench.block`` around a whole block, ``bench.run_block`` around the
engine's dispatch and metrics fetch, ``bench.eval`` around the eval), on
the same clock. From these:

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
  dispatch, fetch metrics and run the eval.
"""
from __future__ import annotations

import gzip
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
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


class TraceView:
    """Per-chip operations and the host spans of one traced window."""

    def __init__(self, chips: List[List[Op]], spans: List[Span]):
        self.chips = chips
        self.spans = sorted(spans)
        blocks = [s for s in self.spans if s.name == "bench.block"]
        if not blocks:
            raise ValueError("the trace holds no bench.block span")
        self.blocks = blocks
        self.t0, self.t1 = blocks[0].start, blocks[-1].end
        self._merged = [_union([(o.start, o.end) for o in ops])
                        for ops in chips]

    # -- window -------------------------------------------------------------
    def window_s(self) -> float:
        return (self.t1 - self.t0) * NS

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        return sum(_clip_len(m, self.t0, self.t1) for m in self._merged) \
            * NS / len(self.chips)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # -- kernels ------------------------------------------------------------
    def pallas_ops(self) -> List[Op]:
        return [o for ops in self.chips for o in ops
                if o.pallas and o.start >= self.t0 and o.end <= self.t1]

    def pallas_s(self) -> float:
        return sum(o.end - o.start for o in self.pallas_ops()) * NS \
            / len(self.chips)

    # -- host boundaries ----------------------------------------------------
    def boundary_gaps_s(self) -> List[float]:
        """Per block, the chips' idle seconds outside the span of the
        block's own scan (averaged over chips)."""
        dispatch = [s for s in self.spans if s.name == "bench.run_block"]
        out = []
        for blk in self.blocks:
            inner = [s for s in dispatch
                     if s.start >= blk.start and s.end <= blk.end]
            gap = 0.0
            for ops, merged in zip(self.chips, self._merged):
                mine = [o for o in ops if inner and o.start >= inner[0].start
                        and o.end <= inner[0].end]
                if mine:
                    a, b = min(o.start for o in mine), max(o.end for o in mine)
                else:
                    a = b = blk.start
                gap += (a - blk.start) - _clip_len(merged, blk.start, a)
                gap += (blk.end - b) - _clip_len(merged, b, blk.end)
            out.append(gap * NS / len(self.chips))
        return out

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle interval of chip 0 inside the window, named by the
        innermost benchmark span open at its midpoint."""
        merged = [(s, e) for s, e in self._merged[0]
                  if e > self.t0 and s < self.t1]
        edges = [self.t0] + [x for s, e in merged for x in (s, e)] + [self.t1]
        out = []
        for a, b in zip(edges[::2], edges[1::2]):
            a, b = max(a, self.t0), min(b, self.t1)
            if b <= a:
                continue
            mid = (a + b) / 2
            open_ = [s for s in self.spans if s.start <= mid <= s.end]
            name = min(open_, key=lambda s: s.end - s.start).name \
                if open_ else "none"
            out.append((name, (b - a) * NS))
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The operations with the most self time (summed over the chips,
        by instruction) and the longest idle gaps of chip 0."""
        totals: Dict[str, float] = {}
        for ops in self.chips:
            inside = [o for o in ops if o.start >= self.t0 and o.end <= self.t1]
            for o, t in zip(inside, _self_times(inside)):
                totals[o.name] = totals.get(o.name, 0.0) + t * NS
        ops_top = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops_top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _self_times(ops: Sequence[Op]) -> List[float]:
    """Each operation's duration less that of the operations it encloses
    (``ops`` as recorded on one chip's line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    self_t = [o.end - o.start for o in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack and ops[i].end <= ops[stack[-1]].end:
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
    spans: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    text = _stat(dict(e.stats), "long_name") or e.name
                    pallas = "tpu_custom_call" in text
                    ops.append(Op(e.start_ns, e.start_ns + e.duration_ns,
                                  _short(text), pallas,
                                  hlo_bytes(text) if pallas else 0))
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Span(e.start_ns,
                                          e.start_ns + e.duration_ns, e.name))
    ids = sorted(devices)
    if chips is not None:
        ids = ids[:chips]
    if not ids:
        raise ValueError("the trace holds no TPU plane")
    return TraceView([devices[i] for i in ids], spans)


def load(path: str, chips: Optional[int] = None) -> TraceView:
    """From an ``.xplane.pb`` file, or a gzipped one (``.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()),
                                chips)
    return from_profile(ProfileData.from_file(path), chips)
