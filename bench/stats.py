"""Client-side arithmetic: percentiles and the end-to-end metrics of one
window, from the host-clock stamps the harness took."""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def nearest_rank(values: Sequence[float] | Iterable[float],
                 pct: float) -> float:
    """Nearest-rank percentile: the ceil(pct/100 * n)-th smallest sample
    (copied from the program's ``orchestrator.telemetry.nearest_rank``).
    Empty input returns None: no sample, no percentile."""
    if not 0 <= pct <= 100:
        raise ValueError(f"percentile must be in [0, 100], got {pct}")
    vs = sorted(values)
    if not vs:
        return None
    rank = max(1, math.ceil(pct / 100.0 * len(vs)))
    return vs[rank - 1]


def due_in_window(records, t0: float, t1: float) -> list:
    return [r for r in records if r.due is not None and t0 <= r.due < t1]


def ttfts(records, t0: float, t1: float) -> list[float]:
    """Due time to first token, for every request due in the window. A
    request with no first token by the window's end (unfinished, rejected
    or shed) counts as the window end minus its due time."""
    out = []
    for r in due_in_window(records, t0, t1):
        first = r.times[0] if r.times and not r.failed else None
        out.append((first if first is not None else t1) - r.due)
    return out


def gaps(records, t0: float, t1: float) -> list[float]:
    """Every gap between consecutive tokens of one request, both stamped
    inside the window. Tokens delivered by one step share a stamp: their
    gap is 0."""
    out = []
    for r in records:
        ts = [t for t in r.times if t is not None and t0 <= t <= t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(records, t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r.times
               if t is not None and t0 < t <= t1)


def end_to_end(window) -> dict:
    """Every end-to-end metric the harness can take from one window."""
    t0, t1 = window.t0, window.t1
    recs = window.records
    tt = ttfts(recs, t0, t1)
    out = {"output_tok_per_s": tokens_in_window(recs, t0, t1) / (t1 - t0),
           "ttft_p50_s": nearest_rank(tt, 50),
           "ttft_p95_s": nearest_rank(tt, 95),
           "itl_p95_s": nearest_rank(gaps(recs, t0, t1), 95)}
    return {k: v for k, v in out.items() if v is not None}


def attempted_failed(window) -> tuple[int, int]:
    due = due_in_window(window.records, window.t0, window.t1)
    return len(due), sum(1 for r in due if r.failed)
