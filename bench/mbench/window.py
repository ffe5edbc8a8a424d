"""The window's arithmetic: latency percentiles, rate and failures.

A request is attempted when it fell due inside the window.  It failed
when it was answered with a non-2xx status or an error envelope (a 429
shed included), broke its connection, or was still unanswered when the
drain grace ran out.  A failed request counts as missing every latency
limit: it enters the percentiles at ``cap_s``, the longest latency the
run can observe (the window plus the grace).  The rate counts the
requests answered successfully before the window closed.
"""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank percentile (``p`` in (0, 100]): the smallest value
    with at least ``p``% of the sample at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of an empty sample")
    return v[max(int(math.ceil(p / 100.0 * len(v))) - 1, 0)]


def summarize(records, *, t0: float, end: float, cap_s: float) -> dict:
    due = [r for r in records if t0 <= r.due < end]
    lat = []
    failed = 0
    completed = 0
    for r in due:
        if r.done is not None and r.ok:
            lat.append(r.done - r.due)
            completed += r.done <= end
        else:
            failed += 1
            lat.append(cap_s)
    late = [max(r.sent - r.due, 0.0) for r in due if r.sent is not None]
    return {
        "attempted": len(due), "failed": failed, "completed": completed,
        "p50_ms": percentile(lat, 50) * 1e3 if lat else None,
        "p95_ms": percentile(lat, 95) * 1e3 if lat else None,
        "p90_ms": percentile(lat, 90) * 1e3 if lat else None,
        "p99_ms": percentile(lat, 99) * 1e3 if lat else None,
        "qps": completed / (end - t0),
        "late_p50_ms": percentile(late, 50) * 1e3 if late else 0.0,
        "late_max_ms": max(late) * 1e3 if late else 0.0,
        "beyond_p95": sum(x > percentile(lat, 95) for x in lat) if lat else 0,
    }
