"""From a profiler trace (``.xplane.pb``) to device busy time, time per op
class and idle gaps attributed to the host's spans.

    python bench/trace.py <file.xplane.pb>     # print what the trace holds

The reduction reads only names and times that the trace shows:

- Device planes are those named ``/device:TPU:<k>``; their ops are the
  events of the line ``XLA Ops``, each named by its HLO instruction
  (``%while.10 = ...`` is ``while.10``). Ops inside a loop body show as
  events nested in the loop's own event; they count as part of it. Busy
  time is the union of the ops' intervals; an op's own time is the
  duration of its outermost event.
- The benchmark brackets every timed call in a host span named
  ``bench.call`` (``jax.profiler.TraceAnnotation``). The traced window runs
  from the first such span's start to the last one's end. The calling
  thread's events are those of the host line that holds these spans and of
  the lines named ``main/<thread id>``, where the TPU runtime puts the
  main thread's own events.
- Each device op falls in one class by the first rule of :data:`OP_CLASSES`
  whose pattern its name matches: the Pallas kernels by their kernel
  names, the Stage-2 scan by its ``while`` loop, and everything else
  (transposes, gathers, pads, concatenations, copies) as XLA glue.
- An idle gap is a stretch of the window in which no op of the device
  runs, cut where a call span starts or ends. It is named by what the host thread that issued the calls was
  doing at its middle: the innermost host event there, below ``bench.call``
  (``call/<event>``), or ``harness`` between calls.
"""

from __future__ import annotations

import bisect
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

CALL_SPAN = "bench.call"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

MAIN_THREAD = re.compile(r"^main/\d+$")

#: (class, pattern on the op's name) in order; the first match wins. On a
#: v5e the Pallas kernels show as custom calls named after their jitted
#: wrappers (``_stage1_impl``, ``_stage1_impl_wide``, ``_thomas_impl``...).
OP_CLASSES: Tuple[Tuple[str, str], ...] = (
    ("stage1", r"^_stage1_(impl|kernel)"),
    ("stage3", r"^_stage3_(impl|kernel)"),
    ("stage2", r"^_thomas_(impl|kernel)|^while"),
    ("xla_glue", r""),
)


@dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns


@dataclass
class Trace:
    """What the reduction needs of one profile: the outermost device ops
    per device, and the events of the thread that made the calls."""

    ops: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def op_name(hlo: str) -> str:
    """``%while.10 = (u32[], ...) while(...)`` -> ``while.10``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n) -> Iterable[Event]:
    for e in line.events:
        start = int(e.start_ns)
        yield Event(name(e.name), start, start + int(e.duration_ns))


def outermost(events: Sequence[Event]) -> List[Event]:
    """The events not nested inside another (``events`` sorted by start)."""
    out: List[Event] = []
    for e in events:
        if out and e.end <= out[-1].end:
            continue
        out.append(e)
    return out


def load(path: str) -> Trace:
    """Read device ops and the calling host thread's events from ``path``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = sorted(_events(line, op_name), key=lambda e: (e.start, -e.end))
                    trace.ops[int(m.group(1))] = outermost(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(_events(line))
                if MAIN_THREAD.match(line.name) or any(
                    e.name == CALL_SPAN for e in events
                ):
                    host += events
    trace.host = sorted(host, key=lambda e: (e.start, -e.end))
    return trace


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, disjoint union of ``[start, end)`` intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(merged: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of ``[lo, hi)`` covered by a sorted disjoint interval list."""
    total = 0
    i = max(bisect.bisect_right(merged, (lo, lo)) - 1, 0)
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        total += max(0, min(e, hi) - max(s, lo))
        i += 1
    return total


def op_class(name: str) -> str:
    for cls, pattern in OP_CLASSES:
        if re.search(pattern, name):
            return cls
    raise AssertionError("the last class matches every name")


@dataclass
class Reduction:
    """The trace's numbers over the traced window, per device used."""

    calls: int
    window_ns: int
    busy_ns: float  # mean over devices of the busy union inside the window
    class_ns: Dict[str, float]  # mean over devices, per op class
    op_ns: Dict[str, float]  # mean over devices, per op name
    call_ns: List[int]  # each call span's length
    call_busy_ns: List[float]  # device busy time inside each call span
    idle_gaps: Dict[str, float]  # ns of idle, by what the host was doing

    @property
    def host_ns_per_call(self) -> float:
        return sum(c - b for c, b in zip(self.call_ns, self.call_busy_ns)) / self.calls


def innermost_at(host: Sequence[Event], times: Sequence[int]) -> List[Optional[Event]]:
    """For each of the sorted ``times``, the innermost host event covering
    it. The events are one thread's, so they nest: a stack sweep finds them."""
    out: List[Optional[Event]] = []
    stack: List[Event] = []
    i = 0
    for t in times:
        while i < len(host) and host[i].start <= t:
            e = host[i]
            while stack and stack[-1].end <= e.start:
                stack.pop()
            stack.append(e)
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        out.append(stack[-1] if stack else None)
    return out


def reduce(trace: Trace) -> Optional[Reduction]:
    """Reduce the trace over its window; None where it holds no call span
    or no device op inside the window."""
    calls = [e for e in trace.host if e.name == CALL_SPAN]
    if not calls or not trace.ops:
        return None
    lo, hi = calls[0].start, calls[-1].end
    ndev = len(trace.ops)
    busy = 0.0
    class_ns: Dict[str, float] = {}
    op_ns: Dict[str, float] = {}
    call_busy = [0.0] * len(calls)
    gaps: Dict[str, float] = {}
    classes: Dict[str, str] = {}
    inner = [e for e in trace.host if e.name != CALL_SPAN]
    starts = [c.start for c in calls]
    cuts = sorted({t for c in calls for t in (c.start, c.end)})
    for ops in trace.ops.values():
        inside = [
            Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in ops
            if e.end > lo and e.start < hi
        ]
        merged = union((e.start, e.end) for e in inside)
        busy += sum(e - s for s, e in merged) / ndev
        for e in inside:
            dur = (e.end - e.start) / ndev
            if e.name not in classes:
                classes[e.name] = op_class(e.name)
            cls = classes[e.name]
            class_ns[cls] = class_ns.get(cls, 0.0) + dur
            op_ns[e.name] = op_ns.get(e.name, 0.0) + dur
        for k, c in enumerate(calls):
            call_busy[k] += overlap(merged, c.start, c.end) / ndev
        edges = [lo] + [t for s, e in merged for t in (s, e)] + [hi]
        idle = []
        for s, e in zip(edges[::2], edges[1::2]):
            # cut at call boundaries, so each piece is inside or outside a call
            inner_cuts = cuts[bisect.bisect_right(cuts, s) : bisect.bisect_left(cuts, e)]
            points = [s, *inner_cuts, e]
            idle += [(a, b) for a, b in zip(points, points[1:]) if b > a]
        mids = [(s + e) // 2 for s, e in idle]
        for (s, e), mid, ev in zip(idle, mids, innermost_at(inner, mids)):
            k = bisect.bisect_right(starts, mid) - 1
            if k >= 0 and mid < calls[k].end:
                name = f"call/{ev.name}" if ev is not None else "call"
            else:
                name = "harness"
            gaps[name] = gaps.get(name, 0.0) + (e - s) / ndev
    if busy <= 0:
        return None
    return Reduction(
        calls=len(calls),
        window_ns=hi - lo,
        busy_ns=busy,
        class_ns=class_ns,
        op_ns=op_ns,
        call_ns=[c.end - c.start for c in calls],
        call_busy_ns=call_busy,
        idle_gaps=gaps,
    )


def top(d: Dict[str, float], k: int = 10) -> List[List]:
    """The ``k`` largest entries as ``[[name, seconds], ...]``."""
    return [[n, v / 1e9] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def summarize(path: str, per_line: int = 8) -> None:
    """Print every plane and line of the trace, with the most frequent event
    names and their summed durations: the look to take before trusting
    :func:`reduce` on a new program or platform."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            names: Dict[str, List[float]] = {}
            for e in line.events:
                acc = names.setdefault(e.name, [0, 0.0])
                acc[0] += 1
                acc[1] += e.duration_ns
            count = sum(v[0] for v in names.values())
            print(f"  line {line.name!r}: {count} events")
            for n, (c, ns) in sorted(names.items(), key=lambda kv: -kv[1][1])[:per_line]:
                print(f"    {c:8d} x {ns / 1e6:12.3f} ms  {n[:120]}")


if __name__ == "__main__":
    for p in sys.argv[1:]:
        summarize(p)
