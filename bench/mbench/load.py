"""The load generator: a keep-alive asyncio HTTP/1.1 client against the
async tier, driven open-loop (requests sent when due, whatever the
server's state) or closed-loop (each client sends its next request when
the last one returned).

Every request is timed from the moment it was due, so a stall also delays
the requests queued behind it.  A request still unanswered when the drain
grace after the window runs out is abandoned and counted as failed.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import time


def request_bytes(path: str, body: dict, tenant: str) -> bytes:
    data = json.dumps(body).encode()
    return (f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(data)}\r\nX-Tenant: {tenant}\r\n"
            f"\r\n").encode() + data


async def read_response(reader) -> tuple:
    """→ (status, parsed JSON body)."""
    status_line = await reader.readline()
    if not status_line:
        raise ConnectionError("server closed before the status line")
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
    return status, body


class Pool:
    """Idle keep-alive connections; a request takes one or opens one."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.idle: list = []
        self.opened = 0

    async def call(self, path: str, body: dict, tenant: str) -> tuple:
        if self.idle:
            reader, writer = self.idle.pop()
        else:
            reader, writer = await asyncio.open_connection(self.host,
                                                           self.port)
            self.opened += 1
        try:
            writer.write(request_bytes(path, body, tenant))
            await writer.drain()
            out = await read_response(reader)
        except BaseException:
            writer.close()
            raise
        self.idle.append((reader, writer))
        return out

    async def close(self) -> None:
        """Close the idle connections and wait until each is closed, so the
        server sees every connection end before it is stopped."""
        writers = [w for _, w in self.idle]
        self.idle.clear()
        for writer in writers:
            writer.close()
        await asyncio.gather(*(asyncio.wait_for(w.wait_closed(), 5.0)
                               for w in writers), return_exceptions=True)


class Record:
    """One request of the window: what was asked, when it was due, when
    it was sent and answered, and the answer."""

    __slots__ = ("req", "op", "due", "sent", "done", "status", "body",
                 "error", "opener", "page", "cursor")

    def __init__(self, req: dict, op: str, due: float, *, opener=None,
                 page: int = 0, cursor: str | None = None):
        self.req, self.op, self.due = req, op, due
        self.sent = self.done = None
        self.status = None
        self.body = None
        self.error = None
        self.opener = opener        # a page's session-opening Record
        self.page = page            # 0 = the opening request
        self.cursor = cursor        # what a page sends to /v1/page

    @property
    def ok(self) -> bool:
        return (self.status == 200 and isinstance(self.body, dict)
                and "error" not in self.body)


class LoadClient:
    """Runs one window of a mix against the tier at ``base`` (host, port)."""

    def __init__(self, host: str, port: int, mix: dict, clock=time.perf_counter):
        self.pool = Pool(host, port)
        self.mix = mix
        self.clock = clock
        self.records: list = []
        self.tasks: set = set()
        self.late: list = []          # seconds each send ran behind its due
        self.t0 = 0.0
        self.end = 0.0

    async def _send(self, rec: Record) -> None:
        sess = self.mix.get("sessions", {})
        rec.sent = self.clock()
        self.late.append(max(rec.sent - rec.due, 0.0))
        if rec.op == "page":
            path, body = "/v1/page", {"cursor": rec.cursor}
        else:
            path, body = "/v1/query", {"sql": rec.req["sql"]}
            if rec.req["session"]:
                body.update(session=True, page_size=sess["page_size"])
        try:
            rec.status, rec.body = await self.pool.call(path, body,
                                                        rec.req["tenant"])
        except (ConnectionError, OSError, ValueError,
                asyncio.IncompleteReadError) as e:
            rec.error = f"{type(e).__name__}: {e}"
        rec.done = self.clock()
        if rec.ok and rec.op == "query" and rec.req["session"]:
            self._follow(rec, rec, 1)
        elif rec.ok and rec.op == "page":
            self._follow(rec.opener, rec, rec.page + 1)

    def _follow(self, opener: Record, last: Record, page: int) -> None:
        """Schedule a session's next page, due ``delay_s`` after the last
        page returned; a page due after the window is not sent."""
        sess = self.mix["sessions"]
        if page > sess["pages"] or last.body.get("cursor") is None:
            return
        due = last.done + sess["delay_s"]
        if due >= self.end:
            return
        rec = Record(opener.req, "page", due, opener=opener, page=page,
                     cursor=last.body["cursor"])
        self.records.append(rec)
        self._spawn(self._at(rec))

    async def _at(self, rec: Record) -> None:
        await asyncio.sleep(max(rec.due - self.clock(), 0.0))
        await self._send(rec)

    def _spawn(self, coro) -> None:
        task = asyncio.ensure_future(coro)
        self.tasks.add(task)
        task.add_done_callback(self.tasks.discard)

    async def run_open(self, reqs: list, due: list, t0: float,
                       seconds: float) -> None:
        self.t0, self.end = t0, t0 + seconds
        for req, d in zip(reqs, due):
            rec = Record(req, "query", t0 + float(d))
            self.records.append(rec)
            await asyncio.sleep(max(rec.due - self.clock(), 0.0))
            self._spawn(self._send(rec))

    async def run_closed(self, lists: list, t0: float, seconds: float) -> None:
        self.t0, self.end = t0, t0 + seconds
        tenants = self.mix["tenants"]

        async def client(i: int, reqs: list) -> None:
            for req in itertools.cycle(reqs):
                now = self.clock()
                if now >= self.end:
                    return
                req = dict(req, tenant=f"t{i % tenants}", session=False)
                rec = Record(req, "query", now)
                self.records.append(rec)
                await self._send(rec)

        for i, reqs in enumerate(lists):
            self._spawn(client(i, reqs))
        await asyncio.sleep(max(self.end - self.clock(), 0.0))

    async def drain(self, grace: float) -> None:
        """Wait up to ``grace`` seconds for what is still in flight; then
        abandon it (those records stay unanswered and count as failed)."""
        deadline = self.clock() + grace
        while self.tasks and self.clock() < deadline:
            await asyncio.wait(set(self.tasks),
                               timeout=max(deadline - self.clock(), 0.0))
        for task in list(self.tasks):
            task.cancel()
        if self.tasks:
            await asyncio.gather(*self.tasks, return_exceptions=True)
        await self.pool.close()

