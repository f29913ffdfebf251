"""End-to-end arithmetic over the host-clock stamps of one run.

Times are seconds from the window's start. A request belongs to the window
when it was due in it (open loop) or took a lane in it (backlog). Tails are
taken over every such request and every gap: nothing is averaged first.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np


@dataclass
class Record:
    index: int
    pool: str
    m: int
    n: int
    due_s: float
    submit_s: float
    in_window: bool = False
    admit_s: Optional[float] = None
    token_s: list = field(default_factory=list)
    prefilled: int = 0
    prompt: Any = None       # the benchmark's own copy of the prompt
    req: Any = None          # the program's request object


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, float), 95))


def median(values) -> float:
    return float(np.median(np.asarray(values, float)))


def window_records(records) -> list:
    return [r for r in records if r.in_window]


def ttft_s(records, end_s: float) -> list:
    """Due to first token for every request of the window. One that never
    got its first token counts its wait until ``end_s``, the end of
    observation."""
    return [(r.token_s[0] if r.token_s else end_s) - r.due_s
            for r in window_records(records)]


def failed(records) -> int:
    return sum(1 for r in window_records(records) if not r.token_s)


def itl_s(records, window_s: float) -> list:
    """Every gap between successive tokens of a request that ends inside
    the window."""
    gaps = []
    for r in records:
        t = r.token_s
        gaps += [b - a for a, b in zip(t, t[1:]) if 0.0 <= b <= window_s]
    return gaps


def output_tok_s(records, window_s: float) -> float:
    """Tokens that came out inside the window, over the whole window."""
    n = sum(1 for r in records for t in r.token_s if 0.0 <= t <= window_s)
    return n / window_s


def summary(records, window_s: float, end_s: float) -> dict:
    """The end-to-end numbers and what they rest on."""
    ttft = ttft_s(records, end_s)
    itl = itl_s(records, window_s)
    out = {"requests_in_window": len(ttft), "failed": failed(records),
           "itl_samples": len(itl),
           "output_tok_s": output_tok_s(records, window_s)}
    if ttft:
        out.update(ttft_p95_ms=1e3 * p95(ttft), ttft_p50_ms=1e3 * median(ttft))
    if itl:
        out.update(itl_p95_ms=1e3 * p95(itl), itl_p50_ms=1e3 * median(itl))
    return out
