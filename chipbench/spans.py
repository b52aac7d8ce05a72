"""The program's own spans in a profiler trace, and what its host was doing
while the device idled.

The decode loop and the boot engine mark their host work with the spans of
``repro.core.metrics.SPANS`` and ``boot.<stage>``. They land on the trace's
``/host:CPU`` plane, one line per thread. ``load_spans`` reads them with
their thread and stats; the reductions below are plain functions of them, so
they are checked on hand-made spans as well as on a trace recorded on the
CPU. From the root of a checkout,

    python3 -m chipbench.spans <trace dir>

prints, for a trace of a serving gateway (``jax.profiler.trace``), the host
time per step, the wall time per admission and the first device's idle time
by innermost span, as one JSON object. The benchmark's run does not read
spans: its harness removes the trace before the readers run.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace_reduce
from chipbench.trace_reduce import Interval

SPAN_PREFIXES = ("decode.", "boot.")          # the program's own spans
CALLER_SPANS = ("decode.submit",)             # spans on the callers' threads, not the loop's
WAIT_SPANS = {"decode.step.run": "step",      # span -> the program it waits for, one run each
              "decode.admit.run": "admit"}


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                               # seconds, the trace's clock
    end: float
    thread: int                                # the host plane's line: one per thread
    stats: Dict[str, object]


def load_spans(path: Path) -> List[Span]:
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            out.extend(Span(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                            i, dict(e.stats))
                       for e in line.events if e.name.startswith(SPAN_PREFIXES))
    return out


def with_children(spans: Sequence[Span], name: str) -> List[Tuple[Span, List[Span]]]:
    """Each span called ``name``, with the spans that lie inside it on its
    thread."""
    by_thread: Dict[int, List[Span]] = {}
    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        by_thread.setdefault(s.thread, []).append(s)
    out = []
    for line in by_thread.values():
        starts = [s.start for s in line]
        for i, p in enumerate(line):
            if p.name != name:
                continue
            j = bisect.bisect_right(starts, p.end)
            out.append((p, [c for c in line[i + 1:j] if c.end <= p.end]))
    return out


def step_host_ms(spans: Sequence[Span]) -> Optional[float]:
    """Mean over the ``decode.step`` spans of the span less its
    ``decode.step.run`` child: the time of a step in which the step program
    neither runs nor is waited on (inputs, the logits' pull, sampling)."""
    vals = [p.end - p.start - sum(c.end - c.start for c in kids if c.name == "decode.step.run")
            for p, kids in with_children(spans, "decode.step")]
    return 1e3 * sum(vals) / len(vals) if vals else None


def admit_wall_ms(spans: Sequence[Span]) -> Optional[float]:
    """Mean duration of the ``decode.admit`` spans that hold no ``decode.boot``:
    how long one admission holds every resident request."""
    vals = [p.end - p.start for p, kids in with_children(spans, "decode.admit")
            if not any(c.name == "decode.boot" for c in kids)]
    return 1e3 * sum(vals) / len(vals) if vals else None


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The time the spans cover, cut wherever one starts or ends, each piece
    named by the innermost span over it: of those covering it, the last to
    start. Sorted, disjoint ``(start, end, name)``."""
    points = sorted({t for s in spans for t in (s.start, s.end)})
    by_start = sorted(spans, key=lambda s: s.start)
    active: List[Span] = []
    out: List[Tuple[float, float, str]] = []
    j = 0
    for a, b in zip(points, points[1:]):
        while j < len(by_start) and by_start[j].start <= a:
            active.append(by_start[j])
            j += 1
        active = [s for s in active if s.end > a]
        if active:
            out.append((a, b, max(active, key=lambda s: (s.start, -s.end)).name))
    return out


def clock_offset(spans: Sequence[Span], modules: Sequence[Interval]) -> float:
    """Seconds to add to the device's times so that each program execution
    lies inside the host span that waits for it (``WAIT_SPANS``): it cannot
    start before the host called it, nor end after the host saw it end.

    The profiler aligns the device's clock to the host's only to about a
    millisecond (a TPU v5e trace showed every step program starting 0.9-1.3
    ms before the host called it). The offset is the one nearest 0 within
    the bounds every pair sets; 0 where the trace meets them, or where no
    pair is found; the bounds' middle where they cross."""
    lo, hi = -float("inf"), float("inf")
    for name, function in WAIT_SPANS.items():
        waits = sorted((s for s in spans if s.name == name), key=lambda s: s.start)
        starts = [w.start for w in waits]
        pat = re.compile(rf"^jit_{re.escape(function)}(\(|$)")
        for _, a, b in (m for m in modules if pat.match(m[0])):
            i = bisect.bisect_right(starts, a)
            near = waits[max(i - 1, 0):i + 1]
            w = max(near, key=lambda w: min(w.end, b) - max(w.start, a), default=None)
            if w is None or min(w.end, b) - max(w.start, a) < (b - a) / 2:
                continue                   # its span lies outside the trace
            lo, hi = max(lo, w.start - a), min(hi, w.end - b)
    if hi == float("inf"):
        return 0.0
    return min(max(0.0, lo), hi) if lo <= hi else (lo + hi) / 2


def idle_by_span(gaps: Sequence[Tuple[float, float]], spans: Sequence[Span]) -> List[List]:
    """Seconds of the idle ``gaps`` (on the spans' clock) under each
    innermost program span of the decode loop's and the boot tracks' threads
    (``CALLER_SPANS`` left out); idle time under none of them is
    ``outside_spans``. Longest first."""
    pieces = innermost([s for s in spans if s.name not in CALLER_SPANS])
    starts = [a for a, _, _ in pieces]
    total: Dict[str, float] = {"outside_spans": 0.0}
    for g0, g1 in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(pieces) and pieces[i][0] < g1:
            a, b, name = pieces[i]
            overlap = min(b, g1) - max(a, g0)
            if overlap > 0:
                total[name] = total.get(name, 0.0) + overlap
                covered += overlap
            i += 1
        total["outside_spans"] += (g1 - g0) - covered
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def reduce(path: Path, **load_kw) -> Dict:
    """A trace's span readings, with the first device plane's idle gaps set
    beside the spans (``clock_offset``) and put under them (``idle_by_span``).
    ``load_kw`` goes to ``trace_reduce.load``."""
    trace = trace_reduce.load(path, **load_kw)
    spans = load_spans(path)
    planes = sorted(trace.ops)
    if not planes:
        raise ValueError("the trace has no device plane")
    first = planes[0]
    offset = clock_offset(spans, trace.modules[first])
    gaps = [(a + offset, b + offset) for a, b in trace_reduce.idle_gaps(trace.ops[first])]
    return {"steps": len(with_children(spans, "decode.step")),
            "step_host_ms": step_host_ms(spans),
            "admit_wall_ms": admit_wall_ms(spans),
            "clock_offset_ms": 1e3 * offset,
            "idle_by_span": idle_by_span(gaps, spans)}


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit("usage: python3 -m chipbench.spans <trace dir>")
    print(json.dumps(reduce(trace_reduce.find_xplane(Path(sys.argv[1])))))
