"""The program's own spans in a profiler trace: the device's idle time split
by what the host was doing inside the batcher's tick, and the decode batch.

The serving path writes ``jax.profiler.TraceAnnotation`` spans on the
profiler's host plane, on the same clock as the device's ops
(``repro.serving.batching.SPANS``). They nest: a ``batcher.step`` (one
tick) holds ``batcher.admit``, ``batcher.prefill``, ``batcher.decode`` and
``batcher.retire``, which enqueue every device op of the tick, and
``batcher.sync``, each blocking device->host copy. ``router.submit`` lies
outside the ticks.

A span is kept as a ``chipbench.trace.Event`` whose name carries its stats
the way the profiler's own TraceMe encoding does (``batcher.decode#lanes=8#``),
so ``save_events`` and ``load_events`` record it unchanged.
``chipbench.trace.reduce`` never sees these spans: its idle gaps and window
come from the harness's spans alone.

Idle device time in the window of ``reduce`` goes to the innermost program
span over it:
  * ``dispatch``: ``batcher.admit``, ``batcher.prefill``, ``batcher.decode``,
    ``batcher.retire`` (the host enqueuing work);
  * ``sync``: ``batcher.sync`` (the host waiting for the device's result);
  * ``bookkeeping``: ``batcher.step`` outside all of its children;
  * ``outside``: in no ``batcher.step`` (the harness's loop, routing,
    waiting for an arrival).
The four parts sum to the window less the device's busy time.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from chipbench import trace as T
from chipbench.trace import Event

PROGRAM_SPANS = ("router.submit", "batcher.step", "batcher.admit",
                 "batcher.prefill", "batcher.decode", "batcher.sync",
                 "batcher.retire")
PART_OF = {"batcher.admit": "dispatch", "batcher.prefill": "dispatch",
           "batcher.decode": "dispatch", "batcher.retire": "dispatch",
           "batcher.sync": "sync", "batcher.step": "bookkeeping"}
PARTS = ("dispatch", "sync", "bookkeeping", "outside")


def with_stats(name: str, stats) -> str:
    """``name`` with its ``(key, value)`` stats, as TraceMe writes them."""
    stats = list(stats)
    if not stats:
        return name
    return name + "#" + ",".join(f"{k}={v}" for k, v in stats) + "#"


def parse(name: str) -> tuple:
    """(span name, {stat: value}); whole-number values come back as int."""
    base, _, rest = name.partition("#")
    stats = {}
    for kv in rest.rstrip("#").split(","):
        if kv:
            k, _, v = kv.partition("=")
            stats[k] = int(v) if v.lstrip("-").isdigit() else v
    return base, stats


def read_spans(path: str) -> List[Event]:
    """The program's spans in an ``.xplane.pb``, stats in their names."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != T.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PROGRAM_SPANS:
                    out.append(Event(
                        plane.name, line.name, with_stats(ev.name, ev.stats),
                        float(ev.start_ns), float(ev.duration_ns)))
    return out


def is_program_span(e: Event) -> bool:
    return e.plane == T.HOST_PLANE and parse(e.name)[0] in PROGRAM_SPANS


@dataclass
class SpanSummary:
    window_s: float                  # as ``chipbench.trace.reduce`` has it
    busy_s: float
    idle_s: dict = field(default_factory=dict)    # part -> seconds
    decode_lanes: list = field(default_factory=list)  # per batcher.decode

    def idle_pct(self, part: str) -> float:
        return 100.0 * self.idle_s[part] / self.window_s

    def readings(self) -> dict:
        """``idle_<part>_pct`` for each part, and ``decode_batch_mean``, the
        mean ``lanes`` stat of the ``batcher.decode`` spans (None without
        any)."""
        out = {f"idle_{p}_pct": self.idle_pct(p) for p in PARTS}
        lanes = self.decode_lanes
        out["decode_batch_mean"] = sum(lanes) / len(lanes) if lanes else None
        return out


def _innermost(spans: List[Event]) -> list:
    """Sorted, disjoint (start, end, name) pieces of the spans' union, each
    under the innermost span that covers it. Spans of one thread nest."""
    out, stack, t = [], [], 0.0
    for s in sorted(spans, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0] <= s.start_ns:
            end, name = stack.pop()
            out.append((t, end, name))
            t = end
        if stack:
            out.append((t, s.start_ns, stack[-1][1]))
        stack.append((s.end_ns, parse(s.name)[0]))
        t = s.start_ns
    while stack:
        end, name = stack.pop()
        out.append((t, end, name))
        t = end
    return [(a, b, n) for a, b, n in out if b > a]


def summarize(events: List[Event],
              spans: List[Event]) -> Optional[SpanSummary]:
    """``events``: what ``chipbench.trace.reduce`` reads (device ops, the
    harness's spans); ``spans``: the program's. None where ``reduce`` finds
    nothing or the program wrote no span."""
    host = [e for e in events if e.plane == T.HOST_PLANE]
    ops = [e for e in events if e.line == T.OPS_LINE]
    if not host or not ops or not spans:
        return None
    w0 = min(e.start_ns for e in host)
    w1 = max(e.end_ns for e in host)
    planes = sorted({e.plane for e in ops})
    pieces = _innermost(spans)
    busy, idle = 0.0, dict.fromkeys(PARTS, 0.0)
    for plane in planes:
        merged = T._union((max(e.start_ns, w0), min(e.end_ns, w1))
                          for e in ops if e.plane == plane
                          and e.end_ns > w0 and e.start_ns < w1)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, ns in T._split_by_spans(gaps, pieces).items():
            part = PART_OF.get(name, "outside")
            idle[part] += ns * 1e-9 / len(planes)
    lanes = [stats["lanes"] for name, stats in (parse(s.name) for s in spans)
             if name == "batcher.decode"]
    return SpanSummary(window_s=(w1 - w0) * 1e-9,
                       busy_s=busy * 1e-9 / len(planes), idle_s=idle,
                       decode_lanes=lanes)


def excerpt(events: List[Event], start_ns: float, ms: float) -> List[Event]:
    """The host spans that lie in ``ms`` from ``start_ns``, and the device
    events that overlap them: a recording small enough to keep."""
    end_ns = start_ns + ms * 1e6
    host = [e for e in events if e.plane == T.HOST_PLANE
            and start_ns <= e.start_ns and e.end_ns <= end_ns]
    if not host:
        return []
    a, b = min(e.start_ns for e in host), max(e.end_ns for e in host)
    return host + [e for e in events if e.plane != T.HOST_PLANE
                   and e.end_ns > a and e.start_ns < b]
