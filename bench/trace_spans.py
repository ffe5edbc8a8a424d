#!/usr/bin/env python3
"""Where a served request's time goes: windows of one cell with the
program's span tracer on.

    python3 bench/trace_spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--modes off,spans,profile,off]

One set-up and one warm-up, as ``bench/run.py`` makes them, then one
window per mode, each on a service of its own (empty result and bounds
caches) over the same store:

* ``off``: the tracer off, as the benchmark's measured runs serve;
* ``spans``: the tracer on, the profiler off (what tracing costs);
* ``profile``: the tracer on under a JAX profiler trace.

Each window prints one JSON line on standard output: ``qps`` and the
latency percentiles; with the tracer on, the window's span totals, the
split of :mod:`mbench.spans` (tier, queue, service, bounds, verify host,
dispatch, device wait, transfers) and ``coverage``, the share of the
clients' summed latency that the spans' self times account for; with the
profiler, the device's busy seconds and the idle seconds under each
innermost program span (``idle_by_span``), also logged on standard
error.  Nothing is compared with the reference: ``bench/run.py`` does
that.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import shutil
import sys
import time
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from mbench import cell, deploy, layerlib, spans, tracing, window  # noqa: E402
from mbench.deploy import log  # noqa: E402
from mbench.load import LoadClient  # noqa: E402

MODES = ("off", "spans", "profile")


def _reading(service) -> tuple:
    stats = getattr(service.backend, "stats", None)
    return (service.tracer.totals(),
            dataclasses.asdict(stats) if stats is not None else {})


def one_window(paths, cfg: dict, mix: dict, store, boxes, seed: int,
               seconds: float, mode: str) -> dict:
    import jax
    service, handle = deploy.serve(cfg, store, boxes, {})
    service.tracer.enabled = mode != "off"
    trace_dir = os.path.join(paths.scratch, "trace_spans")
    try:
        if mode == "profile":
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_trace = time.perf_counter()
        spans0, backend0 = _reading(service)
        client = LoadClient(handle.tier.host, handle.tier.port, mix)
        t0 = client.clock() + 0.05
        asyncio.run(cell._drive(client, mix, seed, seconds, t0))
        spans1, backend1 = _reading(service)
        traced_s = time.perf_counter() - t_trace
        if mode == "profile":
            jax.profiler.stop_trace()
    finally:
        cell.stop_serving(service, handle)
    end = t0 + seconds
    records = [r for r in client.records if t0 <= r.due < end]
    summ = window.summarize(records, t0=t0, end=end,
                            cap_s=seconds + cell.GRACE_S)
    row = {"mode": mode, "seed": seed, "qps": summ["qps"],
           "attempted": summ["attempted"], "failed": summ["failed"],
           "p50_ms": summ["p50_ms"], "p90_ms": summ["p90_ms"],
           "p99_ms": summ["p99_ms"]}
    if mode == "off":
        return row
    answered = [r for r in records if r.ok]
    queries = len(layerlib.queries(
        types.SimpleNamespace(deltas=cell.stats_deltas(records))))
    totals = spans.delta(spans0, spans1)
    backend = {k: backend1[k] - backend0.get(k, 0) for k in backend1}
    latency = sum(r.done - r.due for r in answered)
    row.update(requests=len(answered), queries=queries,
               coverage=spans.coverage(totals, latency),
               latency_s=latency, backend=backend, spans=totals)
    row.update(spans.split(totals, backend, len(answered), queries))
    if mode == "profile":
        events = tracing.collect(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        idle = spans.idle_by_span(events)
        log(spans.line(idle))
        row.update(traced_s=traced_s,
                   busy_s=tracing.reduce(events)["busy_s"],
                   idle_by_span=idle)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--modes", default="off,spans,profile",
                    help="windows to serve, in order: off, spans, profile")
    args = ap.parse_args(argv)
    modes = args.modes.split(",")
    if not set(modes) <= set(MODES):
        ap.error(f"--modes takes {', '.join(MODES)}")
    paths = cell.Paths(os.path.dirname(BENCH))
    _, c, cfg, mix = cell.load_cell(paths, args.workload)
    cell.import_program(paths)
    cell.enable_compile_cache(paths)
    cell.device_info(c["chips"], True)
    params = deploy.params_for(cfg, args.seed)
    store = deploy.build_store(cfg, params, {})
    cell.warm(cfg, store, params["boxes"], mix,
              cell.window_requests(mix, args.seed, args.seconds))
    for mode in modes:
        row = one_window(paths, cfg, mix, store, params["boxes"], args.seed,
                         args.seconds, mode)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
