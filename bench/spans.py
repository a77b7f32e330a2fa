"""From a profiler trace (``.xplane.pb``) to the solver's own split of each
timed call: host time by the program's spans, and the device time of the
layout gathers by their named scope.

    python -m bench.spans <file.xplane.pb>     # from the root: the split per call

The solver emits host spans (``jax.profiler.TraceAnnotation``) on the
thread that calls it and tags the device ops of each stage with a
``jax.named_scope``; ``repro.core.tridiag.spans`` defines both. The
benchmark names them here as strings and imports nothing of the solver, so
it reads a program that emits none of them: the reduction then finds
nothing and returns None.

- The calls are the ``bench.call`` spans, as in :mod:`bench.trace`; a
  program span counts where it lies inside one, on the thread that made the
  calls (the line that holds the call spans).
- ``tridiag.compile`` nests in ``tridiag.lookup`` on an executable-cache
  miss; lookup time is counted without it.
- A device op's scope is its HLO ``op_name``, which a TPU profile keeps in
  the :data:`SCOPE_STAT` stat of the op's event metadata (on a v5e, read by
  hand; ``jax.profiler.ProfileData`` shows only the events' own stats, so
  :func:`op_scopes` reads the metadata from the file's protobuf). An op is
  a layout gather where that name holds one of :data:`GATHER_SCOPES`; its
  time is that of its outermost event inside the traced window, as
  :mod:`bench.trace` counts ops.
"""

from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace as trace_mod
from bench.trace import CALL_SPAN, DEVICE_PLANE, OPS_LINE, Event

FUSE = "tridiag.fuse"
LOOKUP = "tridiag.lookup"
LAUNCH = "tridiag.launch"
WAIT = "tridiag.wait"
FETCH = "tridiag.fetch"
SPLIT = "tridiag.split"
COMPILE = "tridiag.compile"

#: The program spans this reduction sums, each over the traced calls.
HOST_SPANS = (FUSE, LOOKUP, LAUNCH, WAIT, FETCH, SPLIT, COMPILE)
#: The named scopes of the interleave and deinterleave gathers.
GATHER_SCOPES = ("tridiag/interleave", "tridiag/deinterleave")
SCOPE_STAT = "tf_op"


@dataclass
class SpanTrace:
    """What the reduction needs of one profile: the call spans and the
    program's spans on the calling thread, and the outermost device ops of
    each device with their scope (``""`` where the op has none)."""

    calls: List[Event] = field(default_factory=list)
    spans: List[Event] = field(default_factory=list)
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    scopes: Dict[int, List[str]] = field(default_factory=dict)


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, int, int, int]]:
    """``(field, wire type, start, end)`` of each field of the message in
    ``buf[lo:hi]``; a varint's value is its start, its end the next field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field_no, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field_no, wire, v, i
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field_no, wire, i, i + n
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def op_scopes(path: str) -> Dict[int, Dict[str, str]]:
    """Per TPU device, each op's HLO text (its event name) to the
    :data:`SCOPE_STAT` of its event metadata. Reads the ``XSpace`` protobuf
    directly (planes 1; in a plane: name 2, event metadata 4, stat metadata
    5; in event metadata: name 2, stats 5; in a stat: metadata id 1, string
    5, ref 7), skipping the events."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[int, Dict[str, str]] = {}
    for fno, _, plo, phi in _fields(buf, 0, len(buf)):
        if fno != 1:
            continue
        name = ""
        metas: List[Tuple[int, int]] = []
        stat_names: Dict[int, str] = {}
        for f, w, lo, hi in _fields(buf, plo, phi):
            if f == 2 and w == 2:
                name = buf[lo:hi].decode()
            elif f == 4 and w == 2:
                metas += [(a, b) for k, _, a, b in _fields(buf, lo, hi) if k == 2]
            elif f == 5 and w == 2:
                for k, _, a, b in _fields(buf, lo, hi):
                    if k == 2:
                        sm = {g: buf[x:y] for g, gw, x, y in _fields(buf, a, b) if gw == 2}
                        ids = [x for g, gw, x, _ in _fields(buf, a, b) if g == 1 and gw == 0]
                        if ids:
                            stat_names[ids[0]] = sm.get(2, b"").decode()
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        scope_id = [k for k, v in stat_names.items() if v == SCOPE_STAT]
        scopes: Dict[str, str] = {}
        for lo, hi in metas:
            op, scope = "", ""
            for f, w, a, b in _fields(buf, lo, hi):
                if f == 2 and w == 2:
                    op = buf[a:b].decode()
                elif f == 5 and w == 2:
                    stat = {g: (gw, x, y) for g, gw, x, y in _fields(buf, a, b)}
                    if scope_id and stat.get(1, (0, -1))[1] == scope_id[0]:
                        if 5 in stat:
                            scope = buf[stat[5][1] : stat[5][2]].decode()
                        elif 7 in stat:
                            scope = stat_names.get(stat[7][1], "")
            if scope and not scopes.get(op):
                scopes[op] = scope
        out[int(m.group(1))] = scopes
    return out


def load(path: str) -> SpanTrace:
    """Read the call spans, the program's spans and the scoped device ops."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    named = op_scopes(path)
    out = SpanTrace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = sorted(
                    ((int(e.start_ns), -int(e.duration_ns), i, e) for i, e in enumerate(line.events)),
                    key=lambda t: t[:3],
                )
                ops: List[Event] = []
                scopes: List[str] = []
                for start, neg, _, e in events:
                    if ops and start - neg <= ops[-1].end:
                        continue  # nested in the op before: part of it
                    ops.append(Event(trace_mod.op_name(e.name), start, start - neg))
                    scopes.append(named.get(int(m.group(1)), {}).get(e.name, ""))
                out.ops[int(m.group(1))] = ops
                out.scopes[int(m.group(1))] = scopes
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [
                    Event(e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                    for e in line.events
                ]
                if any(e.name == CALL_SPAN for e in events):
                    out.calls += [e for e in events if e.name == CALL_SPAN]
                    out.spans += [e for e in events if e.name in HOST_SPANS]
    out.calls.sort(key=lambda e: e.start)
    out.spans.sort(key=lambda e: (e.start, -e.end))
    return out


@dataclass
class Split:
    """The program's spans summed over the traced calls, and the device
    time of the layout gathers (None where no op carries a scope)."""

    calls: int
    call_ns: int  # summed length of the call spans
    span_ns: Dict[str, int]  # per span name, inside the calls; lookup without compile
    gather_ns: Optional[float]  # mean over devices, inside the window

    def per_call_ms(self, *names: str) -> Optional[float]:
        """Summed time of ``names`` per call; None where none was seen."""
        if not any(n in self.span_ns for n in names):
            return None
        return sum(self.span_ns.get(n, 0) for n in names) / self.calls / 1e6

    @property
    def gather_ms(self) -> Optional[float]:
        return None if self.gather_ns is None else self.gather_ns / self.calls / 1e6

    @property
    def covered(self) -> float:
        """Share of the call spans that the five call steps cover."""
        steps = (FUSE, SPLIT, LOOKUP, LAUNCH, WAIT, FETCH)
        return sum(self.span_ns.get(n, 0) for n in steps) / self.call_ns


def reduce(t: SpanTrace) -> Optional[Split]:
    """Sum the program's spans inside the calls; None where the trace holds
    no call span or no program span."""
    if not t.calls or not t.spans:
        return None
    span_ns: Dict[str, int] = {}
    lookups: List[Event] = []
    k = 0
    for s in t.spans:
        while k < len(t.calls) and t.calls[k].end <= s.start:
            k += 1
        if k == len(t.calls) or s.start < t.calls[k].start or s.end > t.calls[k].end:
            continue  # outside every call
        span_ns[s.name] = span_ns.get(s.name, 0) + (s.end - s.start)
        if s.name == LOOKUP:
            lookups.append(s)
        elif s.name == COMPILE and any(u.start <= s.start and s.end <= u.end for u in lookups):
            span_ns[LOOKUP] -= s.end - s.start
    if not span_ns:
        return None
    lo, hi = t.calls[0].start, t.calls[-1].end
    gather: Optional[float] = None
    for dev, ops in t.ops.items():
        scopes = t.scopes[dev]
        if not any(scopes):
            continue
        ns = sum(
            min(e.end, hi) - max(e.start, lo)
            for e, scope in zip(ops, scopes)
            if e.end > lo and e.start < hi and any(g in scope for g in GATHER_SCOPES)
        )
        gather = (gather or 0.0) + ns / len(t.ops)
    return Split(
        calls=len(t.calls),
        call_ns=sum(c.end - c.start for c in t.calls),
        span_ns=span_ns,
        gather_ns=gather,
    )


def summary(split: Split) -> Dict[str, Optional[float]]:
    """The split per call, in ms, under the names of its metrics."""
    return {
        "calls": split.calls,
        "call_ms": split.call_ns / split.calls / 1e6,
        "fuse_split_ms": split.per_call_ms(FUSE, SPLIT),
        "lookup_ms": split.per_call_ms(LOOKUP),
        "launch_ms": split.per_call_ms(LAUNCH),
        "wait_ms": split.per_call_ms(WAIT),
        "fetch_ms": split.per_call_ms(FETCH),
        "compile_ms": split.per_call_ms(COMPILE),
        "layout_gather_ms": split.gather_ms,
        "covered_pct": 100.0 * split.covered,
    }


if __name__ == "__main__":
    for p in sys.argv[1:]:
        split = reduce(load(p))
        print(json.dumps(None if split is None else summary(split)))
