"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device numbers.

``load`` reads the trace with ``jax.profiler.ProfileData`` and keeps three
kinds of event, each as ``(name, start_s, end_s)``:

- device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
- program executions: the ``XLA Modules`` line of the same planes, named
  ``jit_<function>(<id>)``, so the served ``step`` and ``admit`` programs
  are found by their function names;
- host events: every line of the ``/host:CPU`` plane.

The reductions are plain functions of those intervals, so they are checked
on hand-made intervals as well as on a trace recorded on the CPU.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[str, float, float]           # (name, start_s, end_s)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]            # plane name -> device operations
    modules: Dict[str, List[Interval]]        # plane name -> program executions
    host: List[Interval]


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line) -> List[Interval]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def load(path: Path, device_plane: re.Pattern = DEVICE_PLANE,
         ops_line: str = OPS_LINE, modules_line: str = MODULES_LINE) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if device_plane.match(plane.name):
            ops.setdefault(plane.name, [])
            modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name.startswith(ops_line):
                    ops[plane.name].extend(_events(line))
                elif line.name == modules_line:
                    modules[plane.name].extend(_events(line))
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    return Trace(ops=ops, modules=modules, host=host)


# ------------------------------------------------------------ reductions

def merge(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    """Union of intervals as sorted, disjoint (start, end) pairs."""
    spans = sorted((s, e) for _, s, e in intervals if e > s)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_seconds(intervals: Iterable[Interval]) -> float:
    """Seconds in which at least one interval runs."""
    return sum(e - s for s, e in merge(intervals))


def idle_gaps(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    """Gaps between the busy spans, from the first start to the last end."""
    spans = merge(intervals)
    return [(a[1], b[0]) for a, b in zip(spans, spans[1:]) if b[0] > a[1]]


def program_times(modules: Sequence[Interval], function: str) -> List[float]:
    """Device seconds of each execution of the jitted ``function``."""
    pat = re.compile(rf"^jit_{re.escape(function)}(\(|$)")
    return [e - s for name, s, e in modules if pat.match(name)]


def _family(name: str) -> str:
    """An operation's kind: the HLO instruction's name (the trace names an op
    by its whole instruction, ``%fusion.12 = bf16[...] fusion(...)``) without
    its instance number."""
    m = re.match(r"^%?([^\s=]+)\s*=", name)
    return re.sub(r"(\.\d+)+$", "", m.group(1) if m else name)


def top_ops(ops: Sequence[Interval], n: int = 10) -> List[List]:
    total: Dict[str, float] = {}
    for name, s, e in ops:
        total[_family(name)] = total.get(_family(name), 0.0) + (e - s)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(gaps: Sequence[Tuple[float, float]], host: Sequence[Interval],
               n: int = 10) -> List[List]:
    """The ``n`` longest gaps, each named by the host event that starts
    inside it and overlaps it most (``host_untraced`` where none does)."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, best_overlap = "host_untraced", 0.0
        for name, hs, he in host:
            if s <= hs < e:
                overlap = min(he, e) - hs
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
        out.append([best, e - s])
    return out


def ops_within(ops: Sequence[Interval], modules: Sequence[Interval],
               function: str, pattern: str) -> List[float]:
    """Device seconds of each operation matching ``pattern`` that runs inside
    an execution of the jitted ``function``."""
    pat = re.compile(rf"^jit_{re.escape(function)}(\(|$)")
    runs = sorted((s, e) for name, s, e in modules if pat.match(name))
    starts = [a for a, _ in runs]
    op_pat = re.compile(pattern)
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= runs[i][1] and op_pat.search(name):
            out.append(e - s)
    return out


@dataclasses.dataclass
class DeviceSummary:
    busy_s: float                      # mean over the device planes
    window_s: float
    ops: List[Interval]                # the first device plane's operations
    modules: List[Interval]            # and its program executions
    breakdown: Dict[str, List[List]]

    def program_times(self, function: str) -> List[float]:
        return program_times(self.modules, function)

    def op_times_in(self, function: str, pattern: str) -> List[float]:
        return ops_within(self.ops, self.modules, function, pattern)


def summarize(trace: Trace, window_s: float) -> DeviceSummary:
    planes = sorted(trace.ops)
    if not planes:
        raise ValueError("the trace has no device plane")
    busy = [busy_seconds(trace.ops[p]) for p in planes]
    first = planes[0]
    spans = [(s, e) for _, s, e in trace.ops[first]]
    if spans:
        window_s = max(window_s, max(e for _, e in spans) - min(s for s, _ in spans))
    return DeviceSummary(
        busy_s=sum(busy) / len(busy),
        window_s=window_s,
        ops=trace.ops[first],
        modules=trace.modules[first],
        breakdown={"device_ops": top_ops(trace.ops[first]),
                   "idle_gaps": label_gaps(idle_gaps(trace.ops[first]), trace.host)},
    )
