"""One run of one cell: set-up, warm-up, the measured window, the check
against the plain reference, and the result line."""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import types

from . import deploy, manifest as mf, reference, tracing, traffic, window
from .deploy import log
from .load import LoadClient, Record

GRACE_S = 60.0        # drain: how long the window's last requests may take
STOP_S = 30.0         # how long stopping the tier may wait for its connections
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class BadSetup(RuntimeError):
    """The benchmark cannot run from this directory."""


class _Compiles:
    """Times of JAX's backend compile events in this process (one
    listener for the process; a run reads the events between two marks).
    A compile event fires for a program built in the process, compiled or
    loaded from the persistent cache; ``hits`` are the loads."""

    times: list = []
    names: list = []
    hits: list = []
    registered = False

    @classmethod
    def start(cls) -> None:
        if not cls.registered:
            import jax

            def on_event(event, duration, fun_name="?", **_):
                if event == COMPILE_EVENT:
                    cls.times.append(time.perf_counter())
                    cls.names.append(fun_name)

            def on_hit(event, **_):
                if event == CACHE_HIT_EVENT:
                    cls.hits.append(time.perf_counter())

            jax.monitoring.register_event_duration_secs_listener(on_event)
            jax.monitoring.register_event_listener(on_hit)
            cls.registered = True

    @classmethod
    def between(cls, a: float, b: float) -> int:
        return sum(a <= t < b for t in cls.times)

    @classmethod
    def hits_between(cls, a: float, b: float) -> int:
        return sum(a <= t < b for t in cls.hits)

    @classmethod
    def names_between(cls, a: float, b: float) -> dict:
        out: dict = {}
        for t, n in zip(cls.times, cls.names):
            if a <= t < b:
                out[n] = out.get(n, 0) + 1
        return out


@dataclasses.dataclass
class Paths:
    root: str                      # the checkout

    @property
    def bench(self) -> str:
        return os.path.join(self.root, "bench")

    @property
    def scratch(self) -> str:
        return os.path.join(self.root, ".bench_run")


def load_cell(paths: Paths, name: str, cfg_override: dict | None = None,
              mix_override: dict | None = None) -> tuple:
    bench_json = os.path.join(paths.root, "BENCHMARK.json")
    if not os.path.isfile(bench_json):
        raise BadSetup(f"no BENCHMARK.json at {paths.root}")
    man = mf.load(bench_json)
    problems = mf.validate(man, paths.root)
    if problems:
        raise BadSetup("BENCHMARK.json: " + "; ".join(problems))
    cell = mf.cell(man, name)
    entry = mf.config_entry(man, cell["config"])
    with open(os.path.join(paths.root, entry["file"])) as f:
        cfg = json.load(f)
    mix = traffic.load_mix(paths.bench, cell["traffic"])
    return man, cell, {**cfg, **(cfg_override or {})}, \
        {**mix, **(mix_override or {})}


def import_program(paths: Paths) -> None:
    src = os.path.join(paths.root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BadSetup(f"the program (src/repro) is not in {paths.root}")
    if src not in sys.path:
        sys.path.insert(0, src)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoDevice(f"no accelerator: JAX found {d.platform!r} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peaks_for(paths: Paths, kind: str, require_tpu: bool) -> dict:
    with open(os.path.join(paths.bench, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        if require_tpu:
            raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
        return {"hbm_bytes_per_s": None}
    return table[kind]


def enable_compile_cache(paths: Paths) -> str:
    import jax
    path = os.path.join(paths.root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_peak() -> int | None:
    import jax
    st = jax.devices()[0].memory_stats() or {}
    return st.get("peak_bytes_in_use")


# -- the window --------------------------------------------------------------

def window_requests(mix: dict, seed: int, seconds: float) -> list:
    """What the window can send: an open loop's whole schedule, a closed
    loop's whole pool."""
    if mix["loop"] == "open":
        return traffic.open_schedule(mix, seed, seconds)[0]
    return [r for lst in traffic.closed_lists(mix) for r in lst]


def warm(cfg: dict, store, boxes, mix: dict, reqs: list) -> int:
    """Warm-up: every request the window will send, one at a time, on a
    service of its own over the same store (the window's service starts
    with empty result and bounds caches).  The device backend's jitted
    steps take shapes that depend on the data, so only the window's own
    queries build the programs the window needs.  → requests that failed."""
    from repro.service import MaskSearchService
    svc = cfg["service"]
    sess = mix.get("sessions", {})
    service = MaskSearchService(
        store, provided_rois=boxes, backend="device",
        verify_batch=svc["verify_batch"],
        result_cache_size=svc["result_cache_size"],
        bounds_cache_size=svc["bounds_cache_size"])
    failed = 0
    try:
        for req in reqs:
            item = {"op": "query", "sql": req["sql"], "tenant": req["tenant"]}
            if req["session"]:
                item.update(session=True, page_size=sess["page_size"])
            (status, out), = service.execute_many([item])
            failed += status != "ok"
            if status != "ok" or not req["session"]:
                continue
            for _ in range(sess.get("pages", 0)):
                (status, out), = service.execute_many(
                    [{"op": "page", "session_id": out["session"]}])
                failed += status != "ok"
                if status != "ok" or out.get("exhausted"):
                    break
    finally:
        service.close()
    return failed


async def _drive(client: LoadClient, mix: dict, seed: int, seconds: float,
                 start_at: float) -> None:
    await asyncio.sleep(max(start_at - client.clock(), 0.0))
    if mix["loop"] == "open":
        reqs, due = traffic.open_schedule(mix, seed, seconds)
        await client.run_open(reqs, due, start_at, seconds)
        await asyncio.sleep(max(client.end - client.clock(), 0.0))
    else:
        await client.run_closed(traffic.closed_lists(mix), start_at, seconds)
    await client.drain(GRACE_S)


def stop_serving(service, handle) -> None:
    """Stop the tier and close the service.  The tier's close waits for
    every open connection; one still held by a request that outlived the
    drain is logged (that request already counts as unanswered), not
    raised, so the run still reports what it measured."""
    try:
        handle.stop(timeout=STOP_S)
    except TimeoutError:
        log(f"tier_stop timed_out_s={STOP_S}")
    service.close()


def _counters(service, handle) -> dict:
    return {"tier": dataclasses.asdict(handle.tier.stats),
            "sched": dataclasses.asdict(service.scheduler.stats)}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def stats_deltas(records: list) -> list:
    """(record, ExecStats delta) for every answered record: session pages
    carry their run's cumulative stats, so each page gives the change
    since the session's previous answer."""
    last: dict = {}
    out = []
    for r in sorted((r for r in records if r.ok), key=lambda r: r.done):
        st = r.body.get("stats", {})
        key = id(r.opener or r)
        prev = last.get(key) if (r.op == "page" or r.req["session"]) else None
        if r.op == "page" or r.req["session"]:
            last[key] = st
        out.append((r, {k: v - (prev or {}).get(k, 0) for k, v in st.items()
                        if isinstance(v, (int, float))}))
    return out


# -- correctness ---------------------------------------------------------------

def _served(rec) -> dict:
    """What a record's answer says, as ``{"ids", "scores"?, "offset"?}``."""
    b = rec.body
    if "items" in b:
        return {"ids": [it["id"] for it in b["items"]],
                "scores": [it["score"] for it in b["items"]],
                "offset": b["offset"]}
    out = {"ids": b.get("ids")}
    if "scores" in b:
        out["scores"] = b["scores"]
    return out


def reference_answers(cfg: dict, params: dict, specs: list,
                      precision: str = "float32") -> reference.Answers:
    return reference.Answers(reference.Scan(
        params, cfg["height"], cfg["width"], binary=cfg["tier"] == "packed",
        precision=precision), specs)


def check(records: list, answers: reference.Answers, page_size: int) -> dict:
    """Compare every answered request with the reference.  → counts:
    ``wrong`` (an answer that differs, or an error other than a 429
    shed), ``missing`` (never answered), ``compared``, ``shed``."""
    wrong = missing = compared = shed = 0
    first_wrong = None
    for r in records:
        if r.done is None or r.error is not None:
            missing += 1
            continue
        if r.status == 429:
            shed += 1
            continue
        if not r.ok:
            wrong += 1
            first_wrong = first_wrong or f"{r.status} {str(r.body)[:200]}"
            continue
        got = _served(r)
        spec = r.req["spec"]
        if "offset" in got:
            lo = got["offset"]
            ids, scores = answers.ranking(spec, lo + page_size)
            want = {"ids": ids[lo:], "scores": scores[lo:], "offset": lo}
        else:
            want = answers.answer(spec)
        compared += 1
        if got != want:
            wrong += 1
            first_wrong = first_wrong or (
                f"{r.req['sql']} served {str(got)[:300]} reference "
                f"{str(want)[:300]}")
    return {"wrong": wrong, "missing": missing, "compared": compared,
            "shed": shed, "first_wrong": first_wrong}


# -- per-layer metrics ---------------------------------------------------------

def read_layers(paths: Paths, metrics: list, ctx) -> dict:
    out = {}
    for m in metrics:
        path = os.path.join(paths.bench, "layers", f"{m['name']}.py")
        spec = importlib.util.spec_from_file_location(
            "bench_layer_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- one run -----------------------------------------------------------------

def run(paths: Paths, workload: str, seed: int, seconds: float, trace: bool,
        *, process_start: float, require_tpu: bool = True,
        cfg_override: dict | None = None,
        mix_override: dict | None = None) -> dict:
    man, cell, cfg, mix = load_cell(paths, workload, cfg_override,
                                    mix_override)
    import_program(paths)
    import jax
    # the persistent cache serves the chip; a CPU rehearsal keeps none
    cache = enable_compile_cache(paths) if require_tpu else None
    device = device_info(cell["chips"], require_tpu)
    peaks = peaks_for(paths, device["kind"], require_tpu)
    _Compiles.start()
    log(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} compile_cache={cache}")
    log(f"cell {workload} config={cell['config']} traffic={cell['traffic']} "
        f"seed={seed} seconds={seconds} trace={int(trace)} "
        f"masks={cfg['n_masks']} shape={cfg['height']}x{cfg['width']} "
        f"tier={cfg['tier']}")

    phases: dict = {}
    t = time.perf_counter()
    params = deploy.params_for(cfg, seed)
    phases["params_s"] = time.perf_counter() - t
    store = deploy.build_store(cfg, params, phases)
    service, handle = deploy.serve(cfg, store, params["boxes"], phases)
    host, port = handle.tier.host, handle.tier.port
    try:
        t = time.perf_counter()
        warm_failed = warm(cfg, store, params["boxes"], mix,
                           window_requests(mix, seed, seconds))
        phases["warmup_s"] = time.perf_counter() - t
        if warm_failed:
            log(f"warmup failed_requests={warm_failed}")
        trace_dir = os.path.join(paths.scratch, "trace")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # Python frames: costly, unread
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        trace_t0 = time.perf_counter()
        log(f"memory after_setup peak_bytes_in_use={memory_peak()}")
        c0 = _counters(service, handle)
        client = LoadClient(host, port, mix)
        t0 = client.clock() + 0.05
        setup_s = t0 - process_start
        log("setup " + " ".join(f"{k}={v:.3f}" for k, v in phases.items())
            + f" total_s={setup_s:.3f}")
        asyncio.run(_drive(client, mix, seed, seconds, t0))
        t_end = client.clock()
        # the traced window ends where the trace stops recording, not after
        # the profiler has written it out
        trace_window_s = time.perf_counter() - trace_t0 if trace else None
        if trace:
            jax.profiler.stop_trace()
        compiles = _Compiles.between(t0, t_end)
        peak = memory_peak()
        c1 = _counters(service, handle)
    finally:
        stop_serving(service, handle)
    del service, handle, store
    gc.collect()

    end = t0 + seconds
    records = [r for r in client.records if t0 <= r.due < end]
    summ = window.summarize(records, t0=t0, end=end, cap_s=seconds + GRACE_S)
    log(f"window attempted={summ['attempted']} failed={summ['failed']} "
        f"completed_in_window={summ['completed']} "
        f"generator_late_p50_ms={summ['late_p50_ms']:.3f} "
        f"generator_late_max_ms={summ['late_max_ms']:.3f} "
        f"beyond_p95={summ['beyond_p95']} compiles_in_window={compiles} "
        f"of_them_cache_loads={_Compiles.hits_between(t0, t_end)} "
        f"connections={client.pool.opened}")
    log(f"latency p50_ms={summ['p50_ms']} p90_ms={summ['p90_ms']} "
        f"p95_ms={summ['p95_ms']} p99_ms={summ['p99_ms']}")
    if compiles:
        log("compiled_in_window " + " ".join(
            f"{k}={v}" for k, v in _Compiles.names_between(t0, t_end).items()))

    t = time.perf_counter()
    answers = reference_answers(cfg, params, [r.req["spec"] for r in records])
    checked = check(records, answers, mix.get("sessions", {}).get(
        "page_size", 25))
    log(f"reference_s={time.perf_counter() - t:.3f} "
        f"compared={checked['compared']} shed={checked['shed']}")
    if checked["first_wrong"]:
        log(f"first_wrong {checked['first_wrong']}")

    result = {"correct": checked["wrong"] == 0 and checked["missing"] == 0,
              "attempted": summ["attempted"], "failed": summ["failed"]}
    device["memory_peak_bytes"] = peak
    if trace:
        events = tracing.collect(trace_dir)
        reduced = tracing.reduce(events)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = trace_window_s
        ctx = types.SimpleNamespace(
            records=records, deltas=stats_deltas(records),
            tier=_delta(c0["tier"], c1["tier"]),
            sched=_delta(c0["sched"], c1["sched"]),
            compiles=compiles, trace=reduced, window_s=trace_window_s,
            hbm_bytes_per_s=peaks["hbm_bytes_per_s"],
            row_bytes=row_bytes(cfg))
        result["metrics"] = read_layers(
            paths, mf.metrics_for(man, "per_layer", workload), ctx)
        result["breakdown"] = tracing.breakdown(reduced)
    else:
        values = {"query_p50_ms": summ["p50_ms"], "query_p95_ms": summ["p95_ms"],
                  "qps": summ["qps"], "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in mf.metrics_for(man, "end_to_end", workload)
            if values.get(m["name"]) is not None}
    result["device"] = device
    result["checks"] = {
        "wrong_answers": {"value": checked["wrong"], "limit": 0},
        "unanswered": {"value": checked["missing"], "limit": 0}}
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']} limit={c['limit']}")
    return result


def row_bytes(cfg: dict) -> int:
    """Bytes of one stored mask: float32 pixels, or packed uint32 words."""
    if cfg["tier"] == "packed":
        return cfg["height"] * ((cfg["width"] + 31) // 32) * 4
    return cfg["height"] * cfg["width"] * 4


def answered(req: dict, body: dict, op: str = "query", *, opener=None,
             page: int = 0) -> Record:
    """A record of ``req`` answered with ``body``, as the served path
    answers it."""
    rec = Record(req, op, 0.0, opener=opener, page=page)
    rec.sent = rec.done = 0.0
    rec.status, rec.body = 200, body
    return rec


def control_records(reqs: list, answers: reference.Answers, page_size: int,
                    pages: int) -> list:
    """The window's requests answered by ``answers`` in the program's
    place: one-shot ids (and scores), or every page of a session at its
    offset."""
    out = []
    for req in reqs:
        spec = req["spec"]
        if not req["session"]:
            out.append(answered(req, answers.answer(spec)))
            continue
        ids, scores = answers.ranking(spec, page_size * (pages + 1))
        opener = None
        for p in range(pages + 1):
            lo = p * page_size
            items = [{"id": i, "score": v} for i, v in
                     zip(ids[lo:lo + page_size], scores[lo:lo + page_size])]
            rec = answered(req, {"items": items, "offset": lo},
                           "page" if p else "query", opener=opener, page=p)
            opener = opener or rec
            out.append(rec)
    return out


def control(paths: Paths, workload: str, seeds: list, seconds: float, *,
            require_tpu: bool = True, cfg_override: dict | None = None,
            mix_override: dict | None = None) -> list:
    """The control: the reference computed in bfloat16, put in the
    program's place on the requests a window of each seed sends, and judged
    by :func:`check` as a run's answers are.  → per seed, ``correct`` and
    the compared numbers."""
    _, cell, cfg, mix = load_cell(paths, workload, cfg_override,
                                  mix_override)
    import_program(paths)
    if require_tpu:
        enable_compile_cache(paths)
    device_info(cell["chips"], require_tpu)
    page = mix.get("sessions", {}).get("page_size", 25)
    pages = mix.get("sessions", {}).get("pages", 0)
    out = []
    for seed in seeds:
        params = deploy.params_for(cfg, seed)
        reqs = window_requests(mix, seed, seconds)
        specs = [r["spec"] for r in reqs]
        records = control_records(
            reqs, reference_answers(cfg, params, specs, "bfloat16"), page,
            pages)
        checked = check(records, reference_answers(cfg, params, specs), page)
        row = {"seed": seed,
               "correct": checked["wrong"] == 0 and checked["missing"] == 0,
               "wrong_answers": checked["wrong"],
               "unanswered": checked["missing"],
               "compared": checked["compared"]}
        log("control " + " ".join(f"{k}={v}" for k, v in row.items()))
        out.append(row)
    return out


def parse_seeds(text: str) -> list:
    return [int(s) for s in str(text).split(",") if s.strip()]

