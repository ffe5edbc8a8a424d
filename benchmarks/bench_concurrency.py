"""Concurrency benchmark — the async serving tier vs the threaded server
under thousands of concurrent zipfian sessions (DESIGN.md §14).

An asyncio load generator opens one connection per logical session and
drives a zipfian multi-tenant workload (tenant and query template both
zipf-distributed, like real multi-user traffic: one hot tenant, a long
tail) against each front in turn:

  * the legacy ``ThreadingHTTPServer`` (:mod:`repro.service.server`) —
    thread per request, HTTP/1.0 close-per-request, listen backlog 5;
    the client reconnects per request and retries refused connects,
    which is exactly the pain the tier removes;
  * the async tier (:mod:`repro.service.asyncserver`) — keep-alive
    connections, admission control, weighted-fair batch dispatch into
    cross-tenant fused verification.

Headlines: sustained QPS, p50/p99 latency, shed rate (clean 429s with
``Retry-After`` vs the baseline's refused connects), and fused-pass
tenant width (nonzero ``cross_tenant_passes`` is the tentpole
acceptance).  Prints ``name,us_per_call,derived`` CSV rows (harness
contract) and, with ``--json PATH``, writes the machine-readable record
(``BENCH_concurrency.json``).

    PYTHONPATH=src python benchmarks/bench_concurrency.py \
        --sessions 1200 --json BENCH_concurrency.json
    PYTHONPATH=src python benchmarks/bench_concurrency.py --tiny \
        --json /tmp/bench_concurrency.json        # the CI smoke flags
"""

from __future__ import annotations

import argparse
import asyncio
import json
import threading
import time

import numpy as np

TOPK_TMPL = ("SELECT mask_id FROM MasksDatabaseView ORDER BY "
             "CP(mask, full_img, ({lo:.2f}, {hi:.2f})) DESC LIMIT {k};")
FILTER_TMPL = ("SELECT mask_id FROM MasksDatabaseView WHERE "
               "CP(mask, full_img, (0.3, 0.7)) > {t};")


def _templates():
    sqls = [TOPK_TMPL.format(lo=0.1 + 0.05 * i, hi=0.5 + 0.05 * i, k=5 + i)
            for i in range(8)]
    sqls += [FILTER_TMPL.format(t=100 + 25 * i) for i in range(4)]
    return sqls


def _zipf_probs(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


# -- minimal asyncio HTTP/1.x client ---------------------------------------

async def _read_response(reader) -> tuple[int, dict, float | None]:
    status_line = await reader.readline()
    if not status_line:
        raise ConnectionError("server closed before status line")
    status = int(status_line.split()[1])
    headers: dict = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        name, _, value = line.decode("latin-1").partition(":")
        headers[name.strip().lower()] = value.strip()
    n = int(headers.get("content-length") or 0)
    body = json.loads(await reader.readexactly(n)) if n else {}
    retry_after = headers.get("retry-after")
    return status, body, (float(retry_after) if retry_after else None)


def _request_bytes(path: str, body: dict, tenant: str) -> bytes:
    data = json.dumps(body).encode()
    return (f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(data)}\r\nX-Tenant: {tenant}\r\n"
            f"\r\n").encode() + data


class _SessionConn:
    """One logical session's connection: keep-alive against the async
    tier, reconnect-per-request (with connect retries around the tiny
    listen backlog) against the threaded baseline."""

    def __init__(self, host: str, port: int, keep_alive: bool,
                 timeout: float):
        self.host = host
        self.port = port
        self.keep_alive = keep_alive
        self.timeout = timeout
        self.reader = self.writer = None
        self.connect_retries = 0

    async def _connect(self) -> None:
        delay = 0.005
        for _ in range(400):
            try:
                self.reader, self.writer = await asyncio.wait_for(
                    asyncio.open_connection(self.host, self.port),
                    timeout=self.timeout)
                return
            except (ConnectionError, OSError, asyncio.TimeoutError):
                self.connect_retries += 1
                await asyncio.sleep(delay)
                delay = min(delay * 1.5, 0.2)
        raise ConnectionError("could not connect after 400 attempts")

    async def request(self, path: str, body: dict,
                      tenant: str) -> tuple[int, dict, float | None]:
        if self.reader is None:
            await self._connect()
        try:
            self.writer.write(_request_bytes(path, body, tenant))
            await self.writer.drain()
            out = await asyncio.wait_for(_read_response(self.reader),
                                         timeout=self.timeout)
        except (ConnectionError, asyncio.IncompleteReadError,
                asyncio.TimeoutError):
            # stale keep-alive or dropped conn: one clean reconnect retry
            await self.close()
            await self._connect()
            self.writer.write(_request_bytes(path, body, tenant))
            await self.writer.drain()
            out = await asyncio.wait_for(_read_response(self.reader),
                                         timeout=self.timeout)
        if not self.keep_alive:
            await self.close()
        return out

    async def close(self) -> None:
        if self.writer is not None:
            try:
                self.writer.close()
            except Exception:       # noqa: BLE001 — teardown best-effort
                pass
        self.reader = self.writer = None


# -- the zipfian session driver --------------------------------------------

class LoadStats:
    def __init__(self):
        self.latencies: list = []
        self.completed = 0
        self.shed_429 = 0
        self.errors = 0
        self.connect_retries = 0


async def _drive_session(host, port, keep_alive, plan, stats: LoadStats,
                         timeout: float):
    """One logical session: a few requests (one-shots, or a /v1 session
    open + pages) drawn from the zipfian plan."""
    conn = _SessionConn(host, port, keep_alive, timeout)
    try:
        for kind, tenant, body in plan:
            t0 = time.perf_counter()
            try:
                status, out, retry_after = await conn.request(
                    "/v1/query" if kind != "page" else "/v1/page",
                    body, tenant)
            except Exception:       # noqa: BLE001 — load gen keeps going
                stats.errors += 1
                continue
            dt = time.perf_counter() - t0
            if status == 200:
                stats.completed += 1
                stats.latencies.append(dt)
                if kind == "open" and out.get("cursor"):
                    # chain one page onto the open (pages in the plan
                    # carry a placeholder cursor until the open lands)
                    for sub in plan:
                        if sub[0] == "page" and sub[2].get("cursor") is None:
                            sub[2]["cursor"] = out["cursor"]
                            break
            elif status == 429:
                stats.shed_429 += 1
                await asyncio.sleep(min(retry_after or 0.02, 0.1))
            else:
                stats.errors += 1
    finally:
        stats.connect_retries += conn.connect_retries
        await conn.close()


def _build_plans(n_sessions, tenants, zipf_s, pages, rng):
    """→ per-session request plans: zipfian tenant + template choice,
    every third session paginates instead of one-shotting."""
    sqls = _templates()
    t_probs = _zipf_probs(tenants, zipf_s)
    q_probs = _zipf_probs(len(sqls), zipf_s)
    plans = []
    for i in range(n_sessions):
        tenant = f"tenant-{rng.choice(tenants, p=t_probs)}"
        plan = []
        if i % 3 == 0:
            sql = sqls[rng.choice(len(sqls), p=q_probs)]
            plan.append(["open", tenant,
                         {"sql": sql, "session": True, "page_size": 3}])
            for _ in range(pages):
                plan.append(["page", tenant, {"cursor": None}])
        else:
            for _ in range(1 + pages):
                sql = sqls[rng.choice(len(sqls), p=q_probs)]
                plan.append(["oneshot", tenant, {"sql": sql}])
        plans.append(plan)
    return plans


async def _run_load(host, port, keep_alive, plans, timeout) -> tuple:
    stats = LoadStats()
    t0 = time.perf_counter()
    await asyncio.gather(*[
        _drive_session(host, port, keep_alive, plan, stats, timeout)
        for plan in plans])
    wall = time.perf_counter() - t0
    return stats, wall


def _summarize(stats: LoadStats, wall: float) -> dict:
    lat = np.sort(np.asarray(stats.latencies or [0.0]))
    total = stats.completed + stats.shed_429 + stats.errors
    return {
        "wall_s": wall,
        "completed": stats.completed,
        "shed_429": stats.shed_429,
        "errors": stats.errors,
        "connect_retries": stats.connect_retries,
        "qps": stats.completed / max(wall, 1e-9),
        "shed_rate": stats.shed_429 / max(total, 1),
        "p50_ms": float(lat[int(0.50 * (len(lat) - 1))]) * 1e3,
        "p99_ms": float(lat[int(0.99 * (len(lat) - 1))]) * 1e3,
    }


def _row(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


# -- the two server phases --------------------------------------------------

def _make_service(n_masks, size):
    from repro.service import MaskSearchService
    from repro.service.server import _synthetic_store
    store, rois = _synthetic_store(n_masks, size)
    return MaskSearchService(store, provided_rois=rois)


def bench_threaded(args, plans, record):
    from repro.service import make_server
    service = _make_service(args.n_masks, args.size)
    httpd = make_server(service, "127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    host, port = httpd.server_address[:2]
    try:
        stats, wall = asyncio.run(
            _run_load(host, port, False, plans, args.timeout))
    finally:
        httpd.shutdown()
        service.close()
    summ = _summarize(stats, wall)
    _row("concurrency_threaded", wall,
         f"qps={summ['qps']:.0f};p99={summ['p99_ms']:.1f}ms;"
         f"connect_retries={summ['connect_retries']}")
    record["threaded"] = summ
    return summ


def bench_async_tier(args, plans, record):
    from repro.service.asyncserver import serve_in_thread
    service = _make_service(args.n_masks, args.size)
    handle = serve_in_thread(
        service, tenant_rate=args.tenant_rate, tenant_burst=args.tenant_burst,
        queue_depth=args.queue_depth, batch_max=args.batch_max,
        max_connections=max(2 * len(plans), 64))
    try:
        stats, wall = asyncio.run(
            _run_load(handle.tier.host, handle.tier.port, True, plans,
                      args.timeout))
        sched = service.scheduler.stats
        tier = handle.tier.stats
        fusion = {
            "fused_passes": sched.fused_passes,
            "cross_tenant_passes": sched.cross_tenant_passes,
            "cross_tenant_jobs": sched.cross_tenant_jobs,
            "mean_fused_tenant_width": (
                sched.fused_tenant_width
                / max(sched.fused_passes + sched.pair_passes, 1)),
            "batches": tier.batches,
            "batched_requests": tier.batched_requests,
            "admitted": handle.tier.admission.stats.admitted,
        }
    finally:
        handle.stop()
        service.close()
    summ = _summarize(stats, wall)
    summ["fusion"] = fusion
    _row("concurrency_async_tier", wall,
         f"qps={summ['qps']:.0f};p99={summ['p99_ms']:.1f}ms;"
         f"shed={summ['shed_429']};"
         f"xtenant_passes={fusion['cross_tenant_passes']}")
    record["async_tier"] = summ
    return summ


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, default=1200,
                    help="concurrent zipfian sessions per server phase")
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--zipf", type=float, default=1.1,
                    help="zipf exponent for tenant/template popularity")
    ap.add_argument("--pages", type=int, default=2,
                    help="follow-up requests per session")
    ap.add_argument("--n-masks", type=int, default=200)
    ap.add_argument("--size", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=120.0)
    ap.add_argument("--tenant-rate", type=float, default=400.0)
    ap.add_argument("--tenant-burst", type=float, default=60.0)
    ap.add_argument("--queue-depth", type=int, default=2048)
    ap.add_argument("--batch-max", type=int, default=32)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke scale: 80 sessions, small store")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.tiny:
        args.sessions = 80
        args.tenants = 4
        args.pages = 1
        args.n_masks = 120
        args.tenant_burst = 10.0
        args.tenant_rate = 200.0

    print("name,us_per_call,derived")
    rng = np.random.default_rng(args.seed)
    record = {"config": {
        "sessions": args.sessions, "tenants": args.tenants,
        "zipf": args.zipf, "pages": args.pages, "n_masks": args.n_masks,
        "size": args.size, "tenant_rate": args.tenant_rate,
        "tenant_burst": args.tenant_burst,
    }}

    plans = _build_plans(args.sessions, args.tenants, args.zipf,
                         args.pages, rng)
    # independent (identically distributed) plans per phase so session
    # cursors never leak across servers
    plans_async = _build_plans(args.sessions, args.tenants, args.zipf,
                               args.pages, rng)

    threaded = bench_threaded(args, plans, record)
    tier = bench_async_tier(args, plans_async, record)

    record["qps_ratio"] = tier["qps"] / max(threaded["qps"], 1e-9)
    record["p99_ratio"] = threaded["p99_ms"] / max(tier["p99_ms"], 1e-9)
    _row("concurrency_ratios", 0.0,
         f"qps_ratio={record['qps_ratio']:.2f};"
         f"p99_ratio={record['p99_ratio']:.2f};"
         f"shed_rate={tier['shed_rate']:.3f}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
