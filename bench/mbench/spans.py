"""The program's spans over a window: where each request's time went, and
what the host was doing while the device sat idle.

The service's tracer keeps running totals per span name (count, seconds,
self seconds; ``Tracer.totals()``); two readings around a window give the
window's.  :func:`split` turns them, with the change of the device
backend's transfer counters, into the per-request and per-query figures
below.  While a tracer is enabled each span also holds a
``jax.profiler.TraceAnnotation`` of its name, so in a profiled window the
spans are host events on the same clock as the device's ``XLA Ops``:
:func:`idle_by_span` hands each stretch of device idle time to the
innermost program span open during it.

======================== ===================================================
figure                   what it sums
======================== ===================================================
tier_ms_per_request      ``tier.read`` + ``tier.respond`` / requests
queue_wait_ms_per_request ``tier.queue`` + ``tier.resume`` / requests
service_ms_per_request   self time of ``service.execute``, ``service.item``,
                         ``service.finish``, ``parse``, ``plan.compile`` /
                         requests
bounds_host_ms_per_query self time of ``bounds``, ``bounds.tier`` / queries
verify_host_ms_per_query self time of ``scheduler.drive``,
                         ``scheduler.round``, ``scheduler.fused_pass``,
                         ``scheduler.pair_pass``, ``verify.round`` / queries
dispatch_ms_per_query    ``device.call`` / queries
device_wait_ms_per_query ``device.wait`` + ``device.fetch`` / queries
transfer_kib_per_query   (Δ``h2d_bytes`` + Δ``d2h_bytes``) / 1024 / queries
======================== ===================================================

"Requests" are the window's answered requests, "queries" the executed
ones (``layerlib.queries``).
"""

from __future__ import annotations

import heapq

from . import tracing

TIER = ("tier.read", "tier.respond")
QUEUE = ("tier.queue", "tier.resume")
SERVICE = ("service.execute", "service.item", "service.finish", "parse",
           "plan.compile")
BOUNDS = ("bounds", "bounds.tier")
VERIFY_HOST = ("scheduler.drive", "scheduler.round", "scheduler.fused_pass",
               "scheduler.pair_pass", "verify.round")
DISPATCH = ("device.call",)
DEVICE_WAIT = ("device.wait", "device.fetch")

#: Every span the program opens on the served path; the host events of a
#: profile that carry one of these names are the program's.
PROGRAM_SPANS = frozenset(
    TIER + QUEUE + SERVICE + BOUNDS + VERIFY_HOST + DISPATCH + DEVICE_WAIT
    + ("query",))

NONE = "(none)"


def delta(before: dict, after: dict) -> dict:
    """The window's totals from two ``Tracer.totals()`` readings."""
    out = {}
    for name, t in after.items():
        b = before.get(name, {})
        out[name] = {k: v - b.get(k, 0) for k, v in t.items()}
    return out


def seconds(totals: dict, names, key: str = "seconds") -> float:
    return sum(totals.get(n, {}).get(key, 0.0) for n in names)


def split(totals: dict, backend: dict, requests: int, queries: int) -> dict:
    """The figures of the module's table from a window's span totals and
    the change of the backend's counters; a figure whose denominator is 0
    is left out."""
    out = {}
    if requests:
        out["tier_ms_per_request"] = 1e3 * seconds(totals, TIER) / requests
        out["queue_wait_ms_per_request"] = \
            1e3 * seconds(totals, QUEUE) / requests
        out["service_ms_per_request"] = \
            1e3 * seconds(totals, SERVICE, "self_seconds") / requests
    if queries:
        out["bounds_host_ms_per_query"] = \
            1e3 * seconds(totals, BOUNDS, "self_seconds") / queries
        out["verify_host_ms_per_query"] = \
            1e3 * seconds(totals, VERIFY_HOST, "self_seconds") / queries
        out["dispatch_ms_per_query"] = \
            1e3 * seconds(totals, DISPATCH) / queries
        out["device_wait_ms_per_query"] = \
            1e3 * seconds(totals, DEVICE_WAIT) / queries
        if backend:
            out["transfer_kib_per_query"] = (
                backend.get("h2d_bytes", 0) + backend.get("d2h_bytes", 0)) \
                / 1024 / queries
    return out


def coverage(totals: dict, latency_s: float) -> float | None:
    """The self times of every span of the window over the summed client
    latencies of its answered requests: the share of what the clients
    waited that some span accounts for."""
    if latency_s <= 0:
        return None
    return sum(t["self_seconds"] for t in totals.values()) / latency_s


def idle_by_span(events: list, names=PROGRAM_SPANS) -> dict:
    """Device-idle seconds under each innermost program span.

    ``events`` are :func:`mbench.tracing.collect`'s tuples.  The window
    runs from the first event to the last; the device is idle where no
    ``XLA Ops`` event of the first chip runs.  Each idle stretch goes to
    the program span (a host event named in ``names``) that started last
    among those open over it, on any thread, or to ``"(none)"``.
    → ``{span name: seconds}``."""
    chips: dict = {}
    spans = []
    lo, hi = float("inf"), float("-inf")
    for plane, line, name, start, dur in events:
        lo, hi = min(lo, start), max(hi, start + dur)
        if plane.startswith("/host:"):
            if name in names:
                spans.append((start, start + dur, name))
        elif line == tracing.OPS_LINE:
            chips.setdefault(plane, []).append((start, start + dur))
    if not chips:
        return {}
    busy = tracing._union(chips[min(chips)])
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    return _attribute(idle, spans)


def _attribute(idle: list, spans: list) -> dict:
    """Sweep the idle intervals and the spans in time order; the state
    after the last change at a time holds until the next change, and an
    idle stretch goes to the latest-started span still open."""
    changes = []
    for i, (s, e, _) in enumerate(spans):
        changes.append((s, "open", i))
        changes.append((e, "close", i))
    for s, e in idle:
        changes.append((s, "idle", 1))
        changes.append((e, "idle", -1))
    changes.sort(key=lambda c: c[0])
    out: dict = {}
    heap: list = []                          # (-start, span index)
    closed: set = set()
    idle_depth = 0
    for j, (t, kind, x) in enumerate(changes):
        if kind == "open":
            heapq.heappush(heap, (-spans[x][0], x))
        elif kind == "close":
            closed.add(x)
        else:
            idle_depth += x
        nxt = changes[j + 1][0] if j + 1 < len(changes) else t
        if idle_depth > 0 and nxt > t:
            while heap and heap[0][1] in closed:
                heapq.heappop(heap)
            name = spans[heap[0][1]][2] if heap else NONE
            out[name] = out.get(name, 0.0) + (nxt - t) * 1e-9
    return out


def line(attributed: dict) -> str:
    """The ``idle_by_span`` log line: seconds per span, largest first."""
    items = sorted(attributed.items(), key=lambda kv: -kv[1])
    return "idle_by_span " + " ".join(f"{n}={s:.6f}" for n, s in items)
