"""From a profiler trace to device busy time, step and kernel times, and the
host work that idle gaps fall under.

The profile is read into a flat list of events (plane, line, name, start,
duration). The reduction works on that list alone, so a trimmed recording
of a chip trace tests it on the host.

Names it matches (a rename in the program shows up as a reader that finds
nothing):
  * device planes: ``/device:TPU:<n>``; ops on their ``XLA Ops`` line and
    jitted programs on their ``XLA Modules`` line;
  * the paged steps: modules whose name contains ``decode_step_paged`` or
    ``prefill_paged_chunk``. The engine jits ``functools.partial`` objects,
    which the trace names ``jit__unknown(<fingerprint>)``; such a module is
    the decode step where its ops call the paged decode kernel, and the
    prefill chunk where they hold a layer loop (``%while``) but not the
    kernel: no other program the window runs has a layer loop;
  * the Pallas paged decode kernel: ops whose name contains
    ``paged_decode``;
  * host spans the harness writes: ``submit``, ``tick.<pool>``,
    ``wait_arrival``.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field
from typing import Iterable, List, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
STEP_MODULES = ("decode_step_paged", "prefill_paged_chunk")
KERNEL_OP = "paged_decode"
HOST_SPANS = re.compile(r"^(submit|wait_arrival|tick\..+)$")
LOOP_OP = "%while"


def op_label(name: str) -> str:
    """An op's name and result type, without the operands:
    ``%fusion.138 = bf16[8,11008]{...} fusion(...)`` -> ``%fusion.138
    bf16[8,11008]``."""
    head, _, rest = name.partition(" = ")
    return f"{head} {rest.split('{')[0].split(' ')[0]}".strip()


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xplane(path: str) -> List[Event]:
    """Every event of the device planes' op and module lines, and the
    harness's host spans."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        if not dev and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if dev or HOST_SPANS.match(ev.name):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns), float(ev.duration_ns)))
    return out


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def save_events(events: Iterable[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([[e.plane, e.line, e.name, e.start_ns, e.dur_ns]
                   for e in events], f)


def load_events(path: str) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclass
class TraceSummary:
    window_ns: tuple                      # (start, end) of the host spans
    devices: int
    busy_ns: float                        # union of op intervals, per device
    step_ns: dict = field(default_factory=dict)   # module -> [durations]
    kernel_ns: list = field(default_factory=list)  # per kernel execution
    op_totals: list = field(default_factory=list)  # [(name, seconds)] desc
    idle_by_span: list = field(default_factory=list)  # [(span, seconds)] desc

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [[n, s] for n, s in self.op_totals[:top]],
                "idle_gaps": [[n, s] for n, s in self.idle_by_span[:top]]}


def reduce(events: List[Event]) -> Optional[TraceSummary]:
    """None where the trace holds no host span or no device op."""
    host = [e for e in events if e.plane == HOST_PLANE]
    ops = [e for e in events if e.line == OPS_LINE]
    if not host or not ops:
        return None
    w0 = min(e.start_ns for e in host)
    w1 = max(e.end_ns for e in host)
    planes = sorted({e.plane for e in ops})
    busy, idle = 0.0, {}
    spans = sorted((e.start_ns, e.end_ns, e.name) for e in host)
    for plane in planes:
        merged = _union((max(e.start_ns, w0), min(e.end_ns, w1))
                        for e in ops if e.plane == plane
                        and e.end_ns > w0 and e.start_ns < w1)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for label, ns in _split_by_spans(gaps, spans).items():
            idle[label] = idle.get(label, 0.0) + ns * 1e-9 / len(planes)
    inside = [e for e in events if e.plane != HOST_PLANE
              and w0 <= e.start_ns and e.end_ns <= w1]
    kinds = _step_kinds(inside)
    steps = {m: [e.dur_ns for e in inside
                 if e.line == MODULES_LINE and kinds.get(e.name) == m]
             for m in STEP_MODULES}
    kernel = [e.dur_ns for e in inside
              if e.line == OPS_LINE and KERNEL_OP in e.name]
    totals = {}
    for e in inside:
        if e.line == OPS_LINE and not e.name.startswith(LOOP_OP):
            key = op_label(e.name)
            totals[key] = totals.get(key, 0.0) + e.dur_ns * 1e-9 / len(planes)
    return TraceSummary(
        window_ns=(w0, w1), devices=len(planes), busy_ns=busy / len(planes),
        step_ns=steps, kernel_ns=kernel,
        op_totals=sorted(totals.items(), key=lambda kv: -kv[1]),
        idle_by_span=sorted(idle.items(), key=lambda kv: -kv[1]))


def _step_kinds(device_events: List[Event]) -> dict:
    """Module name -> the paged step it is, by name or by its ops."""
    kinds, ops_of = {}, {}
    mods = [e for e in device_events if e.line == MODULES_LINE]
    ops = sorted((e.plane, e.start_ns, e.name) for e in device_events
                 if e.line == OPS_LINE)
    for m in mods:
        named = next((k for k in STEP_MODULES if k in m.name), None)
        if named:
            kinds[m.name] = named
            continue
        seen = ops_of.setdefault(m.name, [set(), 0])
        if seen[1] >= 3:                     # a few executions tell
            continue
        seen[1] += 1
        i = bisect.bisect_left(ops, (m.plane, m.start_ns, ""))
        while i < len(ops) and ops[i][0] == m.plane and ops[i][1] <= m.end_ns:
            seen[0].add(ops[i][2].split(" ")[0].rstrip("0123456789."))
            i += 1
    for name, (names, _) in ops_of.items():
        if any(KERNEL_OP in n for n in names):
            kinds[name] = "decode_step_paged"
        elif LOOP_OP in names:
            kinds[name] = "prefill_paged_chunk"
    return kinds


def _split_by_spans(gaps, spans) -> dict:
    """Idle nanoseconds under each host span. The harness's spans follow
    one another without nesting; idle time under none of them is
    ``outside_spans``."""
    out, j = {}, 0
    for a, b in gaps:
        covered = 0.0
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            lo, hi, name = spans[k]
            ov = min(hi, b) - max(lo, a)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                covered += ov
            k += 1
        if b - a - covered > 0:
            out["outside_spans"] = out.get("outside_spans", 0.0) + (b - a - covered)
    return out
