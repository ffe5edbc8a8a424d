"""What the per-layer readers in ``bench/layers`` share: sums over the
window's answers, and device seconds from the reduced trace.

A reader gets a context with ``records`` (the window's requests),
``deltas`` (each answered request with the change of its ``ExecStats``),
``tier`` and ``sched`` (the change of the tier's and the scheduler's
counters over the window), ``compiles``, ``trace`` (see
:mod:`mbench.tracing`), ``window_s``, ``hbm_bytes_per_s`` and
``row_bytes``.  A reader that finds nothing to read returns ``None``."""

from __future__ import annotations

from . import tracing


def queries(ctx) -> list:
    """Answered requests that executed a query (not result-cache hits and
    not session pages), with their stats deltas."""
    return [(r, d) for r, d in ctx.deltas
            if r.op == "query" and not r.body.get("cache_hit")]


def stat_sum(ctx, key: str, *, pages: bool = True) -> float:
    return sum(d.get(key, 0) for r, d in ctx.deltas
               if (pages or r.op == "query") and not r.body.get("cache_hit"))


def idle_share(ctx):
    busy = ctx.trace.get("busy_s") if ctx.trace else None
    if busy is None or not ctx.window_s:
        return None
    return 100.0 * (1.0 - busy / ctx.window_s)


def hbm_share(ctx, nbytes: float, steps) -> float | None:
    """The steps' share of the HBM roofline, in %: the bytes the work
    needs at the peak bandwidth over the steps' device time."""
    if not ctx.trace or not ctx.hbm_bytes_per_s or nbytes <= 0:
        return None
    t = tracing.step_seconds(ctx.trace, steps)
    if t <= 0:
        return None
    return 100.0 * (nbytes / ctx.hbm_bytes_per_s) / t
