"""The knee sweep: one set-up, then an open-loop window of the cell's mix at
each offered rate.  The knee is the highest rate whose completions keep up
with its arrivals (no backlog left when the window closes)."""

from __future__ import annotations

import asyncio
import time

from . import cell, deploy, window
from .deploy import log
from .load import LoadClient


def run(paths, workload: str, seed: int, seconds: float, rates: list) -> list:
    _, c, cfg, mix = cell.load_cell(paths, workload)
    cell.import_program(paths)
    cell.enable_compile_cache(paths)
    cell.device_info(c["chips"], True)
    params = deploy.params_for(cfg, seed)
    store = deploy.build_store(cfg, params, {})
    top = dict(mix, rate=max(rates), loop="open")
    cell.warm(cfg, store, params["boxes"], mix,
              cell.window_requests(top, seed, seconds))
    out = []
    for i, rate in enumerate(rates):
        # a service of its own per rate: no window answers from the
        # caches an earlier window filled
        service, handle = deploy.serve(cfg, store, params["boxes"], {})
        host, port = handle.tier.host, handle.tier.port
        try:
            m = dict(mix, rate=rate, loop="open")
            client = LoadClient(host, port, m)
            t0 = client.clock() + 0.05
            asyncio.run(cell._drive(client, m, seed + i, seconds, t0))
            end = t0 + seconds
            recs = [r for r in client.records if t0 <= r.due < end]
            s = window.summarize(recs, t0=t0, end=end,
                                 cap_s=seconds + cell.GRACE_S)
            backlog = sum(r.done is None or r.done > end for r in recs)
            row = {"rate": rate, "attempted": s["attempted"],
                   "completed_in_window": s["completed"],
                   "backlog_at_close": backlog, "failed": s["failed"],
                   "p50_ms": s["p50_ms"], "p95_ms": s["p95_ms"],
                   "drain_s": client.clock() - end}
            log("sweep " + " ".join(f"{k}={v}" for k, v in row.items()))
            out.append(row)
        finally:
            cell.stop_serving(service, handle)
        time.sleep(1.0)
    return out
