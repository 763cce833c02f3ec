"""L7 — HTTP API servers (modal: all / ingest / query).

Parity target (reference: src/handlers/http/modal/{mod,server,ingest_server,
query_server}.rs route tables + middleware.rs auth). One aiohttp application
whose route set depends on the mode, with:

- basic-auth + session-cookie auth, RBAC per route (middleware.rs:106-558)
- `/api/v1/*` management plane compatible with the reference's paths
- OTLP ingest at /v1/{logs,metrics,traces}
- SSE livetail (the reference's Flight livetail, over HTTP here)
- an intra-cluster data-plane endpoint serving staging batches as Arrow IPC
  (the reference's querier->ingestor Flight do_get; SURVEY §5 maps DCN data
  plane to HTTP+Arrow in this build)
- background sync loops (arrows->parquet 60s, parquet->object store 30s,
  retention daily; reference src/sync.rs) and graceful drain on shutdown.

CPU-bound work (JSON parse/flatten/encode) runs on a worker thread pool —
the analogue of the reference's rayon ingest pool (ingest.rs:60).
"""

from __future__ import annotations

import asyncio
import contextvars
import json
import logging
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from datetime import UTC, datetime

from aiohttp import web

from parseable_tpu import DEFAULT_TIMESTAMP_KEY, __version__
from parseable_tpu.config import Mode, Options, StorageOptions, parse_cli
from parseable_tpu.core import Parseable, StreamError, StreamNotFound, validate_stream_name
from parseable_tpu.event.format import LogSource
from parseable_tpu.event.json_format import EventError
from parseable_tpu.livetail import LIVETAIL
from parseable_tpu.query.session import QueryError, QuerySession
from parseable_tpu.query.sql import SqlError
from parseable_tpu.rbac import Action, RbacStore, bootstrap_admin, role_privileges
from parseable_tpu.server.ingest_utils import IngestError, flatten_and_push_logs
from parseable_tpu.storage import rfc3339_now
from parseable_tpu.utils import metrics as prom
from parseable_tpu.utils import telemetry
from parseable_tpu.utils.timeutil import TimeParseError

logger = logging.getLogger(__name__)

STREAM_HEADER = "X-P-Stream"
LOG_SOURCE_HEADER = "X-P-Log-Source"
CUSTOM_FIELD_PREFIX = "x-p-meta-"
UPDATE_STREAM_HEADER = "X-P-Update-Stream"
TIME_PARTITION_HEADER = "X-P-Time-Partition"
CUSTOM_PARTITION_HEADER = "X-P-Custom-Partition"
STATIC_SCHEMA_HEADER = "X-P-Static-Schema-Flag"
TELEMETRY_TYPE_HEADER = "X-P-Telemetry-Type"


class ServerState:
    """Wires Parseable + RBAC + sessions + workers for one server process."""

    def __init__(self, p: Parseable):
        self.p = p
        # stamp this process's cluster identity onto every span it records
        # (node = the owner tag files/snapshots already carry), so a
        # stitched cross-node trace can attribute spans to nodes
        telemetry.set_node_identity(p.owner_tag.rstrip("."), p.options.mode.to_str())
        self.rbac = self._load_rbac()
        self.workers = ThreadPoolExecutor(max_workers=8, thread_name_prefix="ingest")
        # dedicated bounded executor for query CPU work: scans/aggregation
        # saturating it must not starve ingest, metastore I/O, or the other
        # run_in_executor users riding the general pool
        self.query_workers = ThreadPoolExecutor(
            max_workers=max(1, p.options.query_workers), thread_name_prefix="query"
        )
        # admission control for /api/v1/query + /api/v1/counts (reference:
        # resource_check.rs:41-137, previously applied only to ingest):
        # bounded concurrency, bounded wait queue, 503 + Retry-After past it
        from parseable_tpu.server.admission import QueryAdmission

        self.query_gate = (
            QueryAdmission(
                p.options.query_max_concurrent,
                p.options.query_queue_depth,
                p.options.query_queue_timeout_ms,
            )
            if p.options.query_max_concurrent > 0
            else None
        )
        self.started_at = time.time()
        self.shutting_down = False
        self._sync_stop = threading.Event()
        self._sync_threads: list[threading.Thread] = []
        self._hot_tier = None
        # 503-on-pressure for ingest (reference: resource_check.rs:41-137)
        from parseable_tpu.utils.resources import ResourceMonitor

        self.resources = ResourceMonitor(
            p.options.cpu_threshold_pct, p.options.memory_threshold_pct
        )
        from parseable_tpu.tenants import TenantRegistry

        self.tenants = TenantRegistry(p.metastore)
        # native HTTP ingest edge (native/edge.py) — started by run_server
        # when P_EDGE_PORT > 0, stopped in stop(); RBAC mutations push a
        # fresh auth snapshot through it
        self.edge = None
        # Arrow Flight data plane (server/flight.py) — started by
        # run_server when P_FLIGHT_PORT > 0 on an ingest-capable mode,
        # BEFORE node registration so discovery metadata is accurate;
        # stopped in stop()
        self.flight = None
        # executor_tpu.device_summary() of the TPU engine's devices — set
        # by run_server on nodes that serve queries with it, else None
        self.query_device: dict | None = None

    def hot_tier(self):
        """Lazily-built hot tier manager, restored from persisted budgets."""
        if self._hot_tier is None:
            from parseable_tpu.storage.hottier import HotTierManager

            self._hot_tier = HotTierManager(self.p)
            self.p.hot_tier = self._hot_tier
            try:
                for doc in self.p.metastore.list_documents("hottier"):
                    if doc.get("stream") and doc.get("size"):
                        self._hot_tier.set_budget(doc["stream"], doc["size"])
            except Exception:
                logger.exception("failed restoring hot tier budgets")
        return self._hot_tier

    # ----- rbac persistence -------------------------------------------------
    def _load_rbac(self) -> RbacStore:
        doc = self.p.metastore.get_document("users", "rbac") if self._meta_ok() else None
        store = RbacStore.from_json(doc) if doc else RbacStore()
        bootstrap_admin(store, self.p.options.username, self.p.options.password)
        return store

    def _meta_ok(self) -> bool:
        try:
            self.p.metastore.get_parseable_metadata()
            return True
        except Exception:
            return False

    def save_rbac(self) -> None:
        self.p.metastore.put_document("users", "rbac", self.rbac.to_json())
        self._refresh_edge_auth()

    def reload_rbac(self) -> None:
        """Refresh users/roles from the metastore (cluster sync), keeping
        live sessions and the verified-credential cache where the password
        is unchanged."""
        fresh = self._load_rbac()
        fresh.sessions = self.rbac.sessions
        self.rbac = fresh
        self._refresh_edge_auth()

    def _refresh_edge_auth(self) -> None:
        """Re-snapshot the C-side edge auth tokens after any RBAC change —
        the acceptor must never honor a revoked session longer than the
        mutation that revoked it takes to return."""
        if self.edge is not None:
            try:
                self.edge.refresh_auth()
            except Exception:
                logger.exception("edge auth snapshot refresh failed")

    # ----- background sync (reference: src/sync.rs) -------------------------
    def start_sync_loops(self) -> None:
        def loop(interval: int, fn, name: str):
            def run():
                while not self._sync_stop.wait(interval):
                    # slow-task watchdog (reference: monitor_task_duration
                    # sync.rs:106-135): a tick overrunning its interval gets
                    # logged while still running, not just after the fact.
                    # Per-tick state binds as defaults — late-bound closure
                    # vars would let a stale watchdog latch onto the next
                    # tick's event.
                    started = time.monotonic()
                    done = threading.Event()

                    def watch(done=done, started=started):
                        while not done.wait(max(interval, 30)):
                            logger.warning(
                                "%s tick still running after %.0fs (interval %ds)",
                                name,
                                time.monotonic() - started,
                                interval,
                            )

                    w = threading.Thread(target=watch, name=f"{name}-watchdog", daemon=True)
                    w.start()
                    try:
                        # each tick is one trace: the flush/sync/storage
                        # spans it produces share a trace_id and parent
                        # correctly under /debug/spans + pmeta
                        with telemetry.trace_context():
                            fn()
                    except Exception:
                        # per-tick isolation: the loop itself never dies
                        # (reference: catch_unwind + respawn sync.rs:160-165)
                        logger.exception("%s tick failed", name)
                    finally:
                        done.set()
                        # the watchdog wakes immediately on set(); join so a
                        # tick can never strand its watchdog thread
                        w.join(timeout=5)

            t = threading.Thread(target=run, name=name, daemon=True)
            t.start()
            self._sync_threads.append(t)

        # self-observability: spans -> internal pmeta stream (every mode;
        # each node self-ingests its own telemetry), plus the opt-in CPU
        # stack sampler (reference: the hotpath profiling feature)
        telemetry.SPAN_SINK.attach(self.p)
        loop(10, telemetry.SPAN_SINK.flush, "span-flush")
        # conservation-law audit: every node balances its own books on a
        # timer; query/all nodes roll up peers (audit.py decides per mode)
        if self.p.options.audit_interval_secs > 0:
            from parseable_tpu import audit as _audit

            loop(
                self.p.options.audit_interval_secs,
                lambda: _audit.audit_tick(self.p),
                "audit",
            )
        if self.p.options.profile_mode == "cpu":
            from parseable_tpu.utils.profiler import get_profiler

            get_profiler().start()
            logger.info("P_PROFILE=cpu: global stack sampler started")

        if self.p.options.mode in (Mode.ALL, Mode.INGEST):
            # pipelined tick uploads each parquet as compaction finishes;
            # the upload tick still runs to retry leftovers (failed uploads
            # or snapshot commits keep staged parquet for the next cycle)
            local_tick = (
                self.p.sync_cycle if self.p.options.sync_pipeline else self.p.local_sync
            )
            loop(self.p.options.local_sync_interval_secs, local_tick, "local-sync")
            loop(self.p.options.upload_interval_secs, self.p.sync_all_streams, "object-sync")
            from parseable_tpu.storage.retention import retention_tick

            loop(3600, lambda: retention_tick(self.p), "retention")
            self.resources.start()
        if self.p.options.mode in (Mode.ALL, Mode.QUERY):
            from parseable_tpu.alerts import alert_tick

            loop(60, lambda: alert_tick(self), "alerts")
            self.hot_tier()  # restore budgets
            loop(60, lambda: self.hot_tier().tick(), "hot-tier")
            # scheduled cluster billing scrape -> internal pmeta stream
            # (reference: init_cluster_metrics_schedular cluster/mod.rs:1623)
            from parseable_tpu.server import cluster as _C

            loop(
                self.p.options.cluster_metrics_interval_secs,
                lambda: _C.ingest_cluster_metrics(self.p),
                "pmeta-scrape",
            )
        if self.p.options.send_analytics:
            from parseable_tpu.analytics import analytics_tick

            loop(3600, lambda: analytics_tick(self), "analytics")

    def stop(self) -> None:
        if self.shutting_down:
            return  # idempotent: tests and signal paths may both stop
        self.shutting_down = True
        self._sync_stop.set()
        # native ingest edge first: stop accepting + join dispatchers before
        # staging flushes, so every acked row is in staging when p.shutdown()
        # runs and edge_live() is 0 before the process exits
        if self.edge is not None:
            try:
                self.edge.stop()
            except Exception:
                logger.exception("edge stop failed")
            self.edge = None
        # flight data plane: shut the gRPC server down and join its serve
        # thread before staging flushes — in-flight DoGets drain first
        if self.flight is not None:
            try:
                self.flight.stop()
            except Exception:
                logger.exception("flight stop failed")
            self.flight = None
        self.resources.stop()
        # drain buffered spans into pmeta before the final staging flush so
        # the last requests' telemetry survives shutdown, then detach (no
        # further spans should buffer against a stopping instance)
        telemetry.SPAN_SINK.flush()
        telemetry.SPAN_SINK.detach()
        # join the (at most one) in-flight OTLP export and push leftovers —
        # an unjoined exporter at exit strands the final spans mid-POST
        telemetry.TRACER.drain()
        if self.p.options.profile_mode == "cpu":
            from parseable_tpu.utils.profiler import get_profiler

            get_profiler().stop()
        self.p.shutdown()
        # the encoded-block cache's write-behind thread (pool-lifecycle:
        # every thread we start has a deterministic stop)
        from parseable_tpu.ops.enccache import shutdown_enccache

        shutdown_enccache()
        # shared scan-scheduler workers (cross-query fair dispatch)
        from parseable_tpu.query.provider import shutdown_scan_scheduler

        shutdown_scan_scheduler()
        # intra-cluster client pools (staging fan-in, pushdown scatter,
        # control-plane sync): the worker pool, the keep-alive HTTP
        # connection pool, and the cached Flight channels
        from parseable_tpu.server.cluster import (
            shutdown_cluster_pool,
            shutdown_conn_pool,
            shutdown_flight_pool,
        )

        shutdown_cluster_pool(wait=False)
        shutdown_conn_pool()
        shutdown_flight_pool()
        # native sharded-parse worker pool (pool-lifecycle: the C++ side's
        # lock-id ppool::g_mu state drains queued shard jobs before joining;
        # the pool restarts lazily if anything parses after stop)
        from parseable_tpu.native import reset_telem_state, shutdown_parse_pool

        shutdown_parse_pool()
        # telemetry drain state: discard anything this thread never drained
        # and forget the pushed-enable cache so a restarted instance re-syncs
        reset_telem_state()
        self.query_workers.shutdown(wait=False)
        self.workers.shutdown(wait=False)
        # sync loop threads exit on the next _sync_stop.wait() wake; join so
        # stop() returns with no loop thread still ticking (a tick already
        # in flight bounds the wait — threads are daemons as the backstop)
        for t in self._sync_threads:
            t.join(timeout=5)
        self._sync_threads.clear()


# ---------------------------------------------------------------- middleware


def _run_traced(state: "ServerState", fn, *args):
    """run_in_executor with the caller's contextvars carried into the worker
    thread — the request's trace context must follow the work, or ingest/
    query spans detach from their HTTP root (run_in_executor does not copy
    context; task-level copying only covers coroutines)."""
    ctx = contextvars.copy_context()
    return asyncio.get_running_loop().run_in_executor(
        state.workers, lambda: ctx.run(fn, *args)
    )


def _run_query_traced(state: "ServerState", fn, *args):
    """Like _run_traced but on the dedicated query pool (P_QUERY_WORKERS):
    query CPU work must not occupy the general worker pool that ingest and
    metastore round trips depend on."""
    ctx = contextvars.copy_context()
    return asyncio.get_running_loop().run_in_executor(
        state.query_workers, lambda: ctx.run(fn, *args)
    )


async def _admit_query(state: "ServerState"):
    """Pass the admission gate. Returns (permit, None) when admitted —
    permit may be None when the gate is disabled — or (None, response)
    when the request was shed with 503 + Retry-After."""
    if state.query_gate is None:
        return None, None
    from parseable_tpu.server.admission import QueryShed

    try:
        return await state.query_gate.acquire(), None
    except QueryShed as e:
        return None, web.json_response(
            {"error": f"query load shed ({e.reason}); retry later"},
            status=503,
            headers={"Retry-After": str(e.retry_after_secs)},
        )


_TRACED_POST_PATHS = ("/api/v1/ingest", "/api/v1/query", "/api/v1/counts", "/v1/")


def _should_trace(request: web.Request) -> bool:
    path = request.path
    if request.method == "GET":
        # intra-cluster staging fan-in: the peer's serving span must join
        # the querier's propagated trace, not root a fresh per-node one
        return path.startswith("/api/v1/internal/staging/")
    if request.method != "POST":
        return False
    return (
        path.startswith(_TRACED_POST_PATHS)
        # partial-aggregate pushdown + control-plane sync hops
        or path.startswith("/api/v1/internal/")
        or (path.startswith("/api/v1/logstream/") and path.count("/") == 4)
    )


@web.middleware
async def trace_middleware(request: web.Request, handler):
    """One trace per ingest/query request (reference: telemetry.rs tracing
    layer around the actix handlers). Honors an incoming W3C `traceparent`
    so spans parent under the caller's trace; the assigned trace id is
    echoed back in X-P-Trace-Id for /api/v1/debug/spans lookups — on the
    error paths too, where trace lookup matters most: an HTTPException
    (aiohttp's 4xx/5xx idiom) gets the header and an errored span before
    it propagates, and an unexpected raise becomes a 500 that still
    carries the trace id."""
    if not _should_trace(request):
        return await handler(request)
    with telemetry.trace_context(request.headers.get("traceparent")) as trace_id:
        try:
            with telemetry.TRACER.span(
                "http.request", method=request.method, path=request.path
            ) as sp:
                try:
                    resp = await handler(request)
                except web.HTTPException as e:
                    sp["status_code"] = e.status
                    if e.status >= 400:
                        sp["status"] = "error"
                    e.headers["X-P-Trace-Id"] = trace_id
                    raise
                sp["status_code"] = resp.status
                if resp.status >= 500:
                    sp["status"] = "error"
        except web.HTTPException:
            raise  # already stamped above; aiohttp renders it as a response
        except Exception:
            # CancelledError is BaseException (py3.8+), so shutdown/client
            # aborts pass through untouched
            logger.exception("unhandled error in %s %s", request.method, request.path)
            return web.json_response(
                {"error": "internal server error"},
                status=500,
                headers={"X-P-Trace-Id": trace_id},
            )
        resp.headers["X-P-Trace-Id"] = trace_id
        return resp


def _unauthorized(reason: str = "Unauthorized") -> web.Response:
    return web.json_response({"error": reason}, status=401)


_INGEST_PATHS = ("/api/v1/ingest", "/v1/")


@web.middleware
async def auth_middleware(request: web.Request, handler):
    state: ServerState = request.app["state"]
    ui_enabled = state.p.options.ui_dir is not None
    if (
        request.path in ("/api/v1/liveness", "/api/v1/readiness")
        or request.path.startswith("/api/v1/o/")  # OIDC login flow
        or request.method == "OPTIONS"
        or (
            # the console shell + bundle are public (the app itself logs in
            # against the API); everything under /api//v1 still needs auth
            ui_enabled
            and request.method == "GET"
            and not request.path.startswith(("/api/", "/v1/"))
        )
    ):
        return await handler(request)
    # shed ingest under resource pressure (reference: resource_check.rs:120)
    if state.resources.overloaded and request.method == "POST":
        path = request.path
        if path.startswith(_INGEST_PATHS) or (
            path.startswith("/api/v1/logstream/") and path.count("/") == 4
        ):
            return web.json_response(
                {"error": f"node overloaded ({state.resources.reason}); retry later"},
                status=503,
            )
    username = None
    auth = request.headers.get("Authorization", "")
    if auth.startswith("Basic "):
        import base64

        try:
            user, _, pw = base64.b64decode(auth[6:]).decode().partition(":")
        except Exception:
            return _unauthorized("invalid basic auth")
        # cache hits answer inline (sha256); a miss needs scrypt, which is
        # ~10^2 ms BY DESIGN and head-of-line blocks every in-flight request
        # if run here — wrong-password probes never populate the cache, so
        # the slow path is also attacker-reachable on every attempt
        # (psan-loop-block finding: rbac/__init__.py hash_password blocked
        # the loop 58ms under the fan-out suite)
        authed, decided = state.rbac.try_cached_authenticate(user, pw)
        if not decided:
            authed = await asyncio.get_running_loop().run_in_executor(
                state.workers, state.rbac.authenticate, user, pw
            )
        if authed is None:
            return _unauthorized()
        username = user
    elif auth.startswith("Bearer "):
        username = state.rbac.session_user(auth[7:])
        if username is None:
            return _unauthorized("invalid or expired token")
    elif "X-P-API-Key" in request.headers:
        from parseable_tpu.apikeys import resolve_key_cached

        # off the event loop: resolution lists the metastore collection
        # (object-store I/O) on a miss; hits come from the TTL cache
        username = await asyncio.get_running_loop().run_in_executor(
            state.workers, resolve_key_cached, state.p.metastore, request.headers["X-P-API-Key"]
        )
        if username is None or username not in state.rbac.users:
            return _unauthorized("invalid or expired API key")
    elif "session" in request.cookies:
        username = state.rbac.session_user(request.cookies["session"])
        if username is None:
            return _unauthorized("invalid or expired session")
    else:
        return _unauthorized("missing credentials")
    request["username"] = username
    return await handler(request)


def require(action: Action, resource_param: str | None = None):
    """RBAC guard decorator (reference: RouteExt::authorize*)."""

    def deco(fn):
        async def wrapped(request: web.Request):
            state: ServerState = request.app["state"]
            resource = (
                request.match_info.get(resource_param)
                if resource_param
                else request.headers.get(STREAM_HEADER)
            )
            if not state.rbac.authorize(request["username"], action, resource):
                return web.json_response({"error": "Forbidden"}, status=403)
            return await fn(request)

        return wrapped

    return deco


# ------------------------------------------------------------------ handlers


async def liveness(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    if state.shutting_down:
        return web.Response(status=503)
    return web.Response(status=200)


async def readiness(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    try:
        # storage round trip off the event loop: a slow/unreachable backend
        # must fail THIS probe, not stall every in-flight request
        await asyncio.get_running_loop().run_in_executor(
            None, state.p.storage.list_dirs, ""
        )
        return web.Response(status=200)
    except Exception:
        return web.Response(status=503)


@require(Action.METRICS)
async def debug_profile(request: web.Request) -> web.Response:
    """GET /api/v1/debug/profile?seconds=N[&format=top]: sample every
    thread's Python stacks for a window and return collapsed flamegraph
    stacks (reference: the opt-in hotpath sampling profiler feature)."""
    state: ServerState = request.app["state"]
    try:
        seconds = float(request.query.get("seconds", "5"))
    except ValueError:
        return web.json_response({"error": "seconds must be a number"}, status=400)
    if not 0 < seconds <= 60:
        return web.json_response({"error": "seconds must be in (0, 60]"}, status=400)
    from parseable_tpu.utils.profiler import profile_window

    sampler = await asyncio.get_running_loop().run_in_executor(
        state.workers, profile_window, seconds
    )
    if request.query.get("format") == "top":
        return web.json_response(
            {
                "total_samples": sampler.total,
                "top": [
                    {"frame": f, "samples": c} for f, c in sampler.top_functions()
                ],
            }
        )
    return web.Response(
        text=sampler.collapsed(),
        content_type="text/plain",
        headers={"X-Total-Samples": str(sampler.total)},
    )


def _query_device(state: ServerState) -> dict | None:
    if state.query_device is None:
        return None
    from parseable_tpu.query import executor_tpu

    return {
        **state.query_device,
        "mesh_programs_built": executor_tpu.MESH_PROGRAMS_BUILT,
    }


@require(Action.GET_ABOUT)
async def about(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    return web.json_response(
        {
            "version": __version__,
            "uiVersion": "none",
            "commit": "",
            "deploymentId": state.p.node_id,
            "mode": state.p.options.mode.to_str(),
            "staging": str(state.p.options.local_staging_path),
            "store": {"type": state.p.storage.name, "path": state.p.provider.get_endpoint()},
            "queryEngine": state.p.options.query_engine,
            # what the engine runs on (None on nodes that run no TPU
            # engine): platform / device_kind / device_count / mesh, and
            # how many of its programs were built over that mesh so far
            "queryDevice": _query_device(state),
            "license": "AGPL-3.0",
        }
    )


@require(Action.METRICS)
async def metrics_handler(request: web.Request) -> web.Response:
    """Reference authorizes /metrics and /about with Action::Metrics and
    Action::GetAbout (server.rs:251,785) — without the guard any
    single-stream INGEST user can read global volumes and stream names.

    Content-Type must be prometheus_client.CONTENT_TYPE_LATEST (the
    text-format version + charset parameters), not bare text/plain —
    OpenMetrics-aware scrapers negotiate on it."""
    from parseable_tpu.ops.device import collect_device_gauges

    def _collect_and_render() -> bytes:
        # refresh accelerator gauges at scrape time (live HBM usage) and
        # the native pool gauges, then serialize the registry — all of it
        # off the event loop: device introspection and generate_latest over
        # a grown registry each take tens of ms, which would stall every
        # in-flight request for the duration of a scrape
        collect_device_gauges()
        _refresh_native_pool_gauges()
        return prom.render()

    body = await asyncio.get_running_loop().run_in_executor(None, _collect_and_render)
    return web.Response(
        body=body, headers={"Content-Type": prom.CONTENT_TYPE_LATEST}
    )


# previous (busy_ns, sample_ns) per pool worker slot: the busy counters are
# cumulative and monotonic across pool restarts, so the scrape-interval
# ratio is a pure delta — no reset coordination with the C side needed.
# The refresh runs on executor threads (metrics_handler keeps the render
# off the event loop), so concurrent scrapes must not interleave the
# read-prev/store-new sequence.
_POOL_BUSY_LAST: dict[int, tuple[int, int]] = {}  # guarded-by: _POOL_BUSY_MU
_POOL_BUSY_MU = threading.Lock()


def _refresh_native_pool_gauges() -> None:
    """Scrape-time refresh of the native parse-pool gauges (same pattern
    as the device gauges): live worker count, queued-not-running depth,
    cumulative telemetry ring drops, and per-worker busy fraction over the
    interval since the previous scrape."""
    from parseable_tpu import native

    size = native.parse_pool_size()
    prom.NATIVE_POOL_SIZE.set(size)
    prom.NATIVE_POOL_QUEUE_DEPTH.set(native.pool_queue_depth())
    prom.NATIVE_TELEM_DROPS.set(native.telem_drops())
    now = time.monotonic_ns()
    with _POOL_BUSY_MU:
        for w in range(size):
            busy = native.pool_busy_ns(w)
            prev = _POOL_BUSY_LAST.get(w)
            _POOL_BUSY_LAST[w] = (busy, now)
            if prev is None or now <= prev[1]:
                continue  # first scrape: no interval to compute a ratio over
            ratio = (busy - prev[0]) / (now - prev[1])
            prom.NATIVE_POOL_BUSY_RATIO.labels(str(w)).set(min(1.0, max(0.0, ratio)))


@require(Action.METRICS)
async def debug_spans(request: web.Request) -> web.Response:
    """GET /api/v1/debug/spans[?trace_id=...&limit=N]: the most recent
    finished spans from the in-memory ring — the low-latency view of what
    also lands in the `pmeta` stream. Pair with the X-P-Trace-Id response
    header to pull one request's full span tree."""
    trace_id = request.query.get("trace_id")
    if trace_id is not None:
        trace_id = trace_id.strip().lower()
        if len(trace_id) != 32 or any(c not in "0123456789abcdef" for c in trace_id):
            return web.json_response(
                {"error": "trace_id must be 32 hex characters"}, status=400
            )
    try:
        limit = int(request.query.get("limit", "1000"))
    except ValueError:
        return web.json_response({"error": "limit must be an integer"}, status=400)
    if limit <= 0:
        return web.json_response({"error": "limit must be positive"}, status=400)
    spans = telemetry.recent_spans(trace_id, min(limit, telemetry.SPAN_RING_SIZE))
    ident = telemetry.node_identity()
    # node_time: this node's wall clock mid-response, read by the cluster
    # trace assembler for its NTP-style per-peer clock-offset estimate
    return web.json_response(
        {
            "count": len(spans),
            "spans": spans,
            "node_time": time.time(),
            "node": ident["node"],
            "role": ident["role"],
        }
    )


async def login(request: web.Request) -> web.Response:
    """GET /api/v1/login: exchange basic auth (already verified by the
    middleware) for a session token — avoids per-request KDF costs
    (reference: session cookie flow, http/oidc.rs for the OAuth variant)."""
    state: ServerState = request.app["state"]
    token = state.rbac.new_session(request["username"])
    state._refresh_edge_auth()
    resp = web.json_response({"token": token})
    resp.set_cookie("session", token, httponly=True, max_age=7 * 24 * 3600)
    return resp


def _log_source_of(request: web.Request) -> LogSource:
    return LogSource.from_str(request.headers.get(LOG_SOURCE_HEADER, "json"))


def _custom_fields(request: web.Request) -> dict[str, str]:
    return {
        k[len(CUSTOM_FIELD_PREFIX) :]: v
        for k, v in request.headers.items()
        if k.lower().startswith(CUSTOM_FIELD_PREFIX)
    }


@require(Action.INGEST)
async def ingest(request: web.Request) -> web.Response:
    """POST /api/v1/ingest (reference: ingest.rs:69)."""
    state: ServerState = request.app["state"]
    stream_name = request.headers.get(STREAM_HEADER)
    if not stream_name:
        return web.json_response({"error": f"missing {STREAM_HEADER} header"}, status=400)
    log_source = _log_source_of(request)
    if log_source in (LogSource.OTEL_LOGS, LogSource.OTEL_METRICS, LogSource.OTEL_TRACES):
        return web.json_response(
            {"error": "use /v1/logs, /v1/metrics or /v1/traces for OTel data"}, status=400
        )
    return await _do_ingest(request, stream_name, log_source)


async def post_event(request: web.Request) -> web.Response:
    """POST /api/v1/logstream/{name} (reference: ingest.rs:393)."""
    state: ServerState = request.app["state"]
    stream_name = request.match_info["name"]
    if not state.rbac.authorize(request["username"], Action.INGEST, stream_name):
        return web.json_response({"error": "Forbidden"}, status=403)
    return await _do_ingest(request, stream_name, _log_source_of(request))


async def otel_ingest(request: web.Request) -> web.Response:
    """POST /v1/{logs,metrics,traces} (reference: ingest.rs:308-392)."""
    state: ServerState = request.app["state"]
    kind = request.match_info["kind"]
    source = {
        "logs": LogSource.OTEL_LOGS,
        "metrics": LogSource.OTEL_METRICS,
        "traces": LogSource.OTEL_TRACES,
    }.get(kind)
    if source is None:
        return web.json_response({"error": f"unknown OTel signal {kind}"}, status=404)
    stream_name = request.headers.get(STREAM_HEADER) or f"otel-{kind}"
    if not state.rbac.authorize(request["username"], Action.INGEST, stream_name):
        return web.json_response({"error": "Forbidden"}, status=403)
    return await _do_ingest(request, stream_name, source, telemetry_type=kind)


async def _read_body(request: web.Request) -> bytes | None:
    """Body read under the shared P_INGEST_MAX_BODY_BYTES transport cap
    (build_app's client_max_size). Returns None past the cap — callers
    answer with the same JSON 413 the native edge sends from C, so the
    limit and the error shape cannot diverge across tiers."""
    try:
        return await request.read()
    except web.HTTPRequestEntityTooLarge:
        return None


_BODY_TOO_LARGE = {"error": "payload too large"}


async def _do_ingest(
    request: web.Request, stream_name: str, log_source: LogSource, telemetry_type: str = "logs"
) -> web.Response:
    state: ServerState = request.app["state"]
    t_recv = time.time_ns()
    body = await _read_body(request)
    if body is None:
        return web.json_response(_BODY_TOO_LARGE, status=413)
    # recv: the waterfall's first stage — wire-to-memory time for the body
    prom.INGEST_STAGE_TIME.labels("recv", log_source.value).observe(
        (time.time_ns() - t_recv) / 1e9
    )
    if len(body) > state.p.options.max_event_payload_bytes:
        return web.json_response({"error": "payload too large"}, status=413)
    # json.loads is deferred: the native ingest lane parses the raw bytes
    # in C++ and the Python dict tree never materializes on clean payloads
    payload = None

    # tenant suspension/quota (reference: tenants/mod.rs:31-160; header
    # extraction utils/mod.rs:123) — the lookup hits the metastore, so it
    # runs on the worker pool, never the event loop
    tenant = request.headers.get("X-P-Tenant")
    if tenant:
        try:
            payload = json.loads(body)
        except json.JSONDecodeError as e:
            return web.json_response({"error": f"invalid JSON: {e}"}, status=400)
        approx_rows = len(payload) if isinstance(payload, list) else 1
        rejection = await asyncio.get_running_loop().run_in_executor(
            state.workers, state.tenants.check_ingest, tenant, approx_rows
        )
        if rejection is not None:
            status, reason = rejection
            return web.json_response({"error": reason}, status=status)
    custom_fields = _custom_fields(request)

    log_source_name = request.headers.get(LOG_SOURCE_HEADER, "json")

    def work() -> int:
        state.p.create_stream_if_not_exists(
            stream_name, log_source=log_source, telemetry_type=telemetry_type
        )
        # baseline BEFORE the push: the first tracked batch must not count
        # itself into its own conservation baseline (audit.py Ledger)
        state.p.audit.ensure_stream(state.p, stream_name)
        n = flatten_and_push_logs(
            state.p,
            stream_name,
            payload,
            log_source,
            custom_fields,
            origin_size=len(body),
            log_source_name=log_source_name,
            raw_body=body,
        )
        state.p.audit.record_acked(stream_name, n)
        return n

    try:
        count = await _run_traced(state, work)
    except (IngestError, StreamError, EventError) as e:
        return web.json_response({"error": str(e)}, status=400)
    t_ack = time.time_ns()
    resp = web.json_response({"message": f"ingested {count} records"}, status=200)
    prom.INGEST_STAGE_TIME.labels("ack", log_source.value).observe(
        (time.time_ns() - t_ack) / 1e9
    )
    return resp


@require(Action.QUERY)
async def query(request: web.Request) -> web.Response:
    """POST /api/v1/query (reference: handlers/http/query.rs:157)."""
    state: ServerState = request.app["state"]
    try:
        body = await request.json()
    except json.JSONDecodeError:
        return web.json_response({"error": "invalid JSON body"}, status=400)
    sql = body.get("query")
    if not sql:
        return web.json_response({"error": "missing 'query'"}, status=400)
    start, end = body.get("startTime"), body.get("endTime")
    send_fields = bool(body.get("fields", False))
    streaming = bool(body.get("streaming", False))
    # RBAC scope resolves against the parsed plan, pre-execution
    allowed = state.rbac.user_allowed_streams(request["username"])

    from parseable_tpu.query.executor import MemoryLimitExceeded, QueryTimeout

    permit, shed = await _admit_query(state)
    if shed is not None:
        return shed

    if streaming:
        # the streamed generator owns the permit from here: it releases on
        # exhaustion AND on close/abandonment (its release is idempotent,
        # and _query_streaming keeps a finally backstop for errors before
        # the generator ever starts)
        return await _query_streaming(
            request, state, sql, start, end, allowed, send_fields, permit
        )

    def work():
        sess = QuerySession(state.p)
        return sess.query(sql, start, end, allowed_streams=allowed)

    try:
        result = await _run_query_traced(state, work)
    except QueryTimeout as e:
        return web.json_response({"error": str(e)}, status=504)
    except MemoryLimitExceeded as e:
        return web.json_response({"error": str(e)}, status=413)
    except QueryError as e:
        if "unauthorized" in str(e):
            return web.json_response({"error": "Forbidden"}, status=403)
        return web.json_response({"error": str(e)}, status=400)
    except (SqlError, TimeParseError) as e:
        return web.json_response({"error": str(e)}, status=400)
    except Exception as e:
        logger.exception("query failed")
        return web.json_response({"error": str(e)}, status=500)
    finally:
        if permit is not None:
            permit.release()

    # rows -> JSON text: outside every stage of stats.stages, inside the request
    with telemetry.TRACER.span("response.encode") as sp:
        rows = result.to_json_rows()
        sp["rows"] = len(rows)
        if send_fields:
            return web.json_response({"fields": result.fields, "records": rows, "stats": result.stats})
        return web.json_response(rows)


async def _query_streaming(
    request, state, sql, start, end, allowed, send_fields=False, permit=None
):
    """Chunked NDJSON response (reference: query.rs:325-407): one line per
    scanned block, emitted as the scan progresses — a `SELECT *` over a big
    range streams without the server holding the full result.

    The admission permit rides the generator's close path: an abandoned
    response (client gone mid-stream) releases its concurrency slot the
    moment the generator closes, not when GC finds it. Release is
    idempotent, so the pre-generator error paths below double as backstop."""
    from parseable_tpu.query.session import QuerySession as QS
    from parseable_tpu.utils.arrowutil import record_batches_to_json

    loop = asyncio.get_running_loop()
    release = permit.release if permit is not None else (lambda: None)

    def start_stream():
        sess = QS(state.p)
        it = sess.query_stream(
            sql, start, end, allowed_streams=allowed, on_close=release
        )
        return iter(it)

    try:
        it = await loop.run_in_executor(state.query_workers, start_stream)
    except QueryError as e:
        release()
        if "unauthorized" in str(e):
            return web.json_response({"error": "Forbidden"}, status=403)
        return web.json_response({"error": str(e)}, status=400)
    except (SqlError, TimeParseError) as e:
        release()
        return web.json_response({"error": str(e)}, status=400)
    except BaseException:
        release()
        raise

    resp = web.StreamResponse(
        headers={"Content-Type": "application/x-ndjson", "Transfer-Encoding": "chunked"}
    )
    await resp.prepare(request)
    fields_sent = not send_fields
    try:
        try:
            while True:
                part = await loop.run_in_executor(state.query_workers, lambda: next(it, None))
                if part is None:
                    break
                if not fields_sent:
                    await resp.write(
                        json.dumps({"fields": part.column_names}).encode() + b"\n"
                    )
                    fields_sent = True
                rows = record_batches_to_json(part.to_batches())
                await resp.write(json.dumps({"records": rows}).encode() + b"\n")
            await resp.write_eof()
        except Exception as e:
            # headers are gone; surface the error in-band like the reference
            # — unless the connection itself is dead (client disconnect)
            try:
                await resp.write(json.dumps({"error": str(e)}).encode() + b"\n")
                await resp.write_eof()
            except (ConnectionError, ConnectionResetError):
                logger.debug("client disconnected mid-stream")
    finally:
        # close on a worker thread: if the handler was cancelled while a
        # next(it) is still executing in the pool, closing from here would
        # raise ValueError("generator already executing")
        def _close_quietly():
            import time as _tm

            for _ in range(40):
                try:
                    it.close()
                    return
                except ValueError:
                    _tm.sleep(0.05)
                except Exception:
                    return

        state.workers.submit(_close_quietly)
    return resp


@require(Action.QUERY)
async def counts(request: web.Request) -> web.Response:
    """POST /api/v1/counts — time-histogram fast path
    (reference: query/mod.rs:483-744 CountsRequest::get_bin_density)."""
    state: ServerState = request.app["state"]
    body = await request.json()
    stream = body.get("stream")
    start, end = body.get("startTime", "1h"), body.get("endTime", "now")
    num_bins = int(body.get("numBins", 10))
    if not stream:
        return web.json_response({"error": "missing 'stream'"}, status=400)

    allowed = state.rbac.user_allowed_streams(request["username"])

    permit, shed = await _admit_query(state)
    if shed is not None:
        return shed

    def work():
        from parseable_tpu.utils.timeutil import TimeRange, expected_time_bins

        tr = TimeRange.parse_human_time(start, end)
        bins = expected_time_bins(tr.start, tr.end, num_bins)
        sess = QuerySession(state.p)
        step_s = int((bins[0][1] - bins[0][0]).total_seconds()) if bins else 60
        # bins must align to the query start, not the epoch: pass the origin
        origin = bins[0][0].isoformat().replace("+00:00", "Z") if bins else None
        bin_expr = (
            f"date_bin(interval '{step_s}s', {DEFAULT_TIMESTAMP_KEY}, '{origin}')"
            if origin
            else f"date_bin(interval '{step_s}s', {DEFAULT_TIMESTAMP_KEY})"
        )
        res = sess.query(
            f"SELECT {bin_expr} AS start_time, "
            f"count(*) AS count FROM {stream} GROUP BY start_time ORDER BY start_time",
            start,
            end,
            allowed_streams=allowed,
        )
        counts_by_start = {r["start_time"]: r["count"] for r in res.to_json_rows()}
        out = []
        for lo, hi in bins:
            key = lo.replace(tzinfo=None).isoformat(timespec="milliseconds")
            out.append(
                {
                    "startTime": lo.isoformat().replace("+00:00", "Z"),
                    "endTime": hi.isoformat().replace("+00:00", "Z"),
                    "count": counts_by_start.get(key, 0),
                }
            )
        return out

    try:
        records = await _run_query_traced(state, work)
    except (SqlError, QueryError, TimeParseError, StreamNotFound) as e:
        return web.json_response({"error": str(e)}, status=400)
    finally:
        if permit is not None:
            permit.release()
    return web.json_response({"fields": ["startTime", "endTime", "count"], "records": records})


# ----- logstream management (reference: handlers/http/logstream.rs) --------


@require(Action.LIST_STREAM)
async def list_streams(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    # storage-backed discovery off the event loop (transitive-blocking)
    await _run_traced(state, state.p.load_streams_from_storage)
    allowed = state.rbac.user_allowed_streams(request["username"])
    names = state.p.streams.list_names()
    if allowed is not None:
        names = [n for n in names if n in allowed]
    return web.json_response([{"name": n} for n in names])


@require(Action.CREATE_STREAM, "name")
async def put_stream(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    update = request.headers.get(UPDATE_STREAM_HEADER, "").lower() == "true"
    time_partition = request.headers.get(TIME_PARTITION_HEADER)
    custom_partition = request.headers.get(CUSTOM_PARTITION_HEADER)
    static_schema_flag = request.headers.get(STATIC_SCHEMA_HEADER, "").lower() == "true"
    telemetry_type = request.headers.get(TELEMETRY_TYPE_HEADER, "logs")
    static_schema = None
    body = await _read_body(request)
    if body is None:
        return web.json_response(_BODY_TOO_LARGE, status=413)
    if static_schema_flag and body:
        from parseable_tpu.static_schema import convert_static_schema

        try:
            static_schema = convert_static_schema(json.loads(body), time_partition)
        except (ValueError, json.JSONDecodeError) as e:
            return web.json_response({"error": f"invalid static schema: {e}"}, status=400)
    try:
        validate_stream_name(name)
        exists = state.p.streams.contains(name)
        if exists and not update:
            return web.json_response({"error": f"stream {name} already exists"}, status=400)
        if exists and update:
            # apply header-driven changes to the existing stream
            # (reference: logstream_utils.rs update path)
            stream = state.p.get_stream(name)
            if custom_partition is not None:
                stream.metadata.custom_partition = custom_partition or None
            if time_partition is not None:
                return web.json_response(
                    {"error": "time partition cannot be changed after creation"}, status=400
                )
            def _persist() -> None:
                # executor thread: the lock may be held by the sync/retention
                # threads; never block the event loop waiting on it
                with state.p.stream_json_lock(name):
                    fmt = state.p.metastore.get_stream_json(name, state.p._node_suffix)
                    fmt.custom_partition = stream.metadata.custom_partition
                    state.p.metastore.put_stream_json(name, fmt, state.p._node_suffix)

            await asyncio.get_running_loop().run_in_executor(None, _persist)
            fanout_to_ingestors(state, "PUT", f"/api/v1/logstream/{name}", headers=_xp_headers(request))
            return web.json_response({"message": f"updated stream {name}"})
        def _create() -> None:
            # metastore round trips (stream json + schema) off the loop
            state.p.create_stream_if_not_exists(
                name,
                time_partition=time_partition,
                custom_partition=custom_partition,
                static_schema=static_schema,
                telemetry_type=telemetry_type,
            )

        await _run_traced(state, _create)
    except StreamError as e:
        return web.json_response({"error": str(e)}, status=400)
    fanout_to_ingestors(state, "PUT", f"/api/v1/logstream/{name}", headers=_xp_headers(request))
    return web.json_response({"message": f"created stream {name}"})


def _xp_headers(request: web.Request) -> dict[str, str]:
    return {k: v for k, v in request.headers.items() if k.lower().startswith("x-p-")}


@require(Action.DELETE_STREAM, "name")
async def delete_stream(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    if not state.p.streams.contains(name):
        return web.json_response({"error": f"stream {name} not found"}, status=404)

    def _delete() -> None:
        # staging rmtree + object-store prefix delete: both block
        state.p.streams.delete(name)
        state.p.metastore.delete_stream(name)

    await _run_traced(state, _delete)
    fanout_to_ingestors(state, "DELETE", f"/api/v1/logstream/{name}")
    return web.json_response({"message": f"deleted stream {name}"})


@require(Action.GET_SCHEMA, "name")
async def get_schema(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    try:
        stream = state.p.get_stream(name)
    except StreamNotFound:
        return web.json_response({"error": f"stream {name} not found"}, status=404)
    fields = [
        {"name": f.name, "data_type": str(f.type), "nullable": f.nullable}
        for f in stream.metadata.schema.values()
    ]
    return web.json_response({"fields": fields})


@require(Action.GET_STREAM_INFO, "name")
async def stream_info(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    try:
        stream = state.p.get_stream(name)
    except StreamNotFound:
        return web.json_response({"error": f"stream {name} not found"}, status=404)
    m = stream.metadata
    return web.json_response(
        {
            "created-at": m.created_at,
            "first-event-at": m.first_event_at,
            "time_partition": m.time_partition,
            "custom_partition": m.custom_partition,
            "static_schema_flag": m.static_schema_flag,
            "stream_type": m.stream_type,
            "log_source": [s.value for s in m.log_source],
            "telemetry_type": m.telemetry_type,
        }
    )


@require(Action.GET_STATS, "name")
async def stream_stats(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    try:
        fmts = await _run_traced(state, state.p.metastore.get_all_stream_jsons, name)
    except Exception:
        fmts = []
    if not fmts and not state.p.streams.contains(name):
        return web.json_response({"error": f"stream {name} not found"}, status=404)
    date = request.query.get("date")
    if date:
        # per-date stats from the day-partitioned manifest items — durable
        # across restarts, unlike the reference's in-memory per-date
        # counters (logstream.rs get_stats_date)
        events = ingestion = storage = 0
        for fmt in fmts:
            for item in fmt.snapshot.manifest_list:
                if item.time_lower_bound.date().isoformat() == date:
                    events += item.events_ingested
                    ingestion += item.ingestion_size
                    storage += item.storage_size
    else:
        events = sum(f.stats.events for f in fmts)
        ingestion = sum(f.stats.ingestion for f in fmts)
        storage = sum(f.stats.storage for f in fmts)
    return web.json_response(
        {
            "stream": name,
            "time": rfc3339_now(),
            "ingestion": {"count": events, "size": f"{ingestion} Bytes", "format": "json"},
            "storage": {"size": f"{storage} Bytes", "format": "parquet"},
        }
    )


@require(Action.PUT_RETENTION, "name")
async def put_retention(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    body = await request.json()
    from parseable_tpu.storage.retention import validate_retention_config

    try:
        validate_retention_config(body)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    try:
        stream = state.p.get_stream(name)
    except StreamNotFound:
        return web.json_response({"error": f"stream {name} not found"}, status=404)
    stream.metadata.retention = body
    def _persist() -> None:
        with state.p.stream_json_lock(name):
            fmt = state.p.metastore.get_stream_json(name, state.p._node_suffix)
            fmt.retention = body
            state.p.metastore.put_stream_json(name, fmt, state.p._node_suffix)

    try:
        await asyncio.get_running_loop().run_in_executor(None, _persist)
    except Exception:
        logger.exception("failed persisting retention")
    fanout_to_ingestors(state, "PUT", f"/api/v1/logstream/{name}/retention", json_body=body)
    return web.json_response({"message": "updated retention"})


@require(Action.PUT_HOT_TIER, "name")
async def put_hot_tier(request: web.Request) -> web.Response:
    """PUT /api/v1/logstream/{name}/hottier {"size": "10GiB"}
    (reference: hottier.rs + logstream hot-tier endpoints)."""
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    try:
        state.p.get_stream(name)
    except StreamNotFound:
        return web.json_response({"error": f"stream {name} not found"}, status=404)
    body = await request.json()

    def _enable() -> None:
        # hot_tier() lazily restores budgets from the metastore and the
        # reconcile downloads parquet: all of it belongs on a worker
        state.hot_tier().set_budget(name, body.get("size", ""))
        state.p.metastore.put_document(
            "hottier", name, {"stream": name, "size": body.get("size")}
        )
        # reconcile eagerly so the tier warms without waiting for the tick
        state.hot_tier().reconcile(name)

    try:
        await _run_traced(state, _enable)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    return web.json_response({"message": f"hot tier enabled for {name}"})


@require(Action.GET_HOT_TIER, "name")
async def get_hot_tier(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    # first call builds the manager from persisted metastore budgets
    ht = await _run_traced(state, state.hot_tier)
    budget = ht.get_budget(name)
    if budget is None:
        return web.json_response({"error": "hot tier not enabled"}, status=404)
    return web.json_response({"size": budget, "used_size": ht.used_bytes(name)})


@require(Action.DELETE_HOT_TIER, "name")
async def delete_hot_tier(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]

    def _disable() -> None:
        state.hot_tier().disable(name)
        state.p.metastore.delete_document("hottier", name)

    await _run_traced(state, _disable)
    return web.json_response({"message": f"hot tier disabled for {name}"})


@require(Action.GET_RETENTION, "name")
async def get_retention(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    try:
        stream = state.p.get_stream(request.match_info["name"])
    except StreamNotFound:
        return web.json_response({"error": "stream not found"}, status=404)
    return web.json_response(stream.metadata.retention or [])


# ----- livetail (SSE) -------------------------------------------------------


@require(Action.LIVE_TAIL, "name")
async def livetail_sse(request: web.Request) -> web.StreamResponse:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    pipe = LIVETAIL.subscribe(name)
    resp = web.StreamResponse(
        headers={"Content-Type": "text/event-stream", "Cache-Control": "no-cache"}
    )
    await resp.prepare(request)
    from parseable_tpu.utils.arrowutil import record_batches_to_json

    try:
        while not state.shutting_down:
            try:
                batch = await asyncio.get_running_loop().run_in_executor(
                    None, pipe.q.get, True, 5.0
                )
            except Exception:
                await resp.write(b": keepalive\n\n")
                continue
            for row in record_batches_to_json([batch]):
                await resp.write(b"data: " + json.dumps(row, default=str).encode() + b"\n\n")
    except (ConnectionResetError, asyncio.CancelledError):
        pass
    finally:
        LIVETAIL.unsubscribe(pipe)
    return resp


# ----- users & roles --------------------------------------------------------


@require(Action.PUT_USER)
async def put_user(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    username = request.match_info["username"]
    if username == state.p.options.username:
        return web.json_response({"error": "cannot modify root user"}, status=400)
    if username in state.rbac.users:
        return web.json_response({"error": f"user {username} already exists"}, status=400)
    body = {}
    raw = await _read_body(request)
    if raw is None:
        return web.json_response(_BODY_TOO_LARGE, status=413)
    if raw:
        body = json.loads(raw)
    roles = set(body.get("roles", []))
    # off the event loop: put_user runs the scrypt KDF (~10^2 ms by design —
    # the same head-of-line hazard as the auth slow path above)
    password = await _run_traced(state, state.rbac.put_user, username, None, roles)
    await _run_traced(state, state.save_rbac)
    fanout_to_ingestors(state, "POST", "/api/v1/internal/rbac/reload", kinds=("ingestor", "querier", "all"))
    return web.json_response(password)


@require(Action.LIST_USER)
async def list_users(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    return web.json_response(
        [
            {"id": u.username, "method": u.user_type, "roles": sorted(u.roles)}
            for u in state.rbac.users.values()
        ]
    )


@require(Action.DELETE_USER)
async def delete_user(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    username = request.match_info["username"]
    if username == state.p.options.username:
        return web.json_response({"error": "cannot delete root user"}, status=400)
    state.rbac.delete_user(username)
    await _run_traced(state, state.save_rbac)
    fanout_to_ingestors(state, "POST", "/api/v1/internal/rbac/reload", kinds=("ingestor", "querier", "all"))
    return web.json_response({"message": f"deleted user {username}"})


@require(Action.PUT_USER_ROLES)
async def put_user_roles(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    username = request.match_info["username"]
    roles = set(await request.json())
    u = state.rbac.users.get(username)
    if u is None:
        return web.json_response({"error": "user not found"}, status=404)
    missing = [r for r in roles if r not in state.rbac.roles]
    if missing:
        return web.json_response({"error": f"unknown roles {missing}"}, status=400)
    u.roles = roles
    await _run_traced(state, state.save_rbac)
    fanout_to_ingestors(state, "POST", "/api/v1/internal/rbac/reload", kinds=("ingestor", "querier", "all"))
    return web.json_response({"message": "updated roles"})


@require(Action.PUT_ROLE)
async def put_role(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    body = await request.json()
    perms = []
    try:
        for item in body:
            privilege = item.get("privilege")
            resource = (item.get("resource") or {}).get("stream") if isinstance(item.get("resource"), dict) else item.get("resource")
            perms.extend(role_privileges(privilege, resource))
    except (ValueError, AttributeError, TypeError) as e:
        return web.json_response({"error": f"invalid role body: {e}"}, status=400)
    state.rbac.put_role(name, perms)
    await _run_traced(state, state.save_rbac)
    fanout_to_ingestors(state, "POST", "/api/v1/internal/rbac/reload", kinds=("ingestor", "querier", "all"))
    return web.json_response({"message": f"updated role {name}"})


@require(Action.LIST_ROLE)
async def list_roles(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    return web.json_response(sorted(state.rbac.roles))


@require(Action.DELETE_ROLE)
async def delete_role(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    try:
        state.rbac.delete_role(request.match_info["name"])
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    await _run_traced(state, state.save_rbac)
    fanout_to_ingestors(state, "POST", "/api/v1/internal/rbac/reload", kinds=("ingestor", "querier", "all"))
    return web.json_response({"message": "deleted role"})


# ----- generic metastore-backed CRUD (alerts/targets/dashboards/filters) ----


def _validate_correlation(state: "ServerState", body: dict, username: str) -> None:
    """Correlation config sanity (reference: correlation.rs:280 validate):
    exactly two table configs over existing, authorized streams, and join
    conditions naming fields from those tables."""
    tables = body.get("tableConfigs") or []
    if len(tables) != 2:
        raise ValueError("correlation needs exactly two tableConfigs")
    allowed = state.rbac.user_allowed_streams(username)
    names = []
    for tc in tables:
        name = tc.get("tableName")
        if not name:
            raise ValueError("tableConfig missing tableName")
        if state.p.streams.get(name) is None:
            # fresh querier: the stream may exist in storage but not be
            # loaded yet (same fallback as QuerySession.resolve_stream)
            state.p.load_streams_from_storage()
        if state.p.streams.get(name) is None:
            raise ValueError(f"stream {name!r} does not exist")
        if allowed is not None and name not in allowed:
            raise ValueError(f"unauthorized for stream {name!r}")
        names.append(name)
    conds = (body.get("joinConfig") or {}).get("joinConditions") or []
    if not conds:
        raise ValueError("joinConfig.joinConditions must not be empty")
    for c in conds:
        if c.get("tableName") not in names or not c.get("field"):
            raise ValueError("joinCondition must name a configured table and field")


def crud_routes(collection: str, put_action: Action, get_action: Action, delete_action: Action):
    async def put_doc(request: web.Request):
        state: ServerState = request.app["state"]
        if not state.rbac.authorize(request["username"], put_action):
            return web.json_response({"error": "Forbidden"}, status=403)
        body = await request.json()
        doc_id = request.match_info.get("id") or body.get("id") or uuid.uuid4().hex
        body["id"] = doc_id
        body.setdefault("created", rfc3339_now())
        body["modified"] = rfc3339_now()
        if collection == "alerts":
            from parseable_tpu.alerts import validate_alert

            try:
                validate_alert(body)
            except ValueError as e:
                return web.json_response({"error": str(e)}, status=400)
        if collection == "targets":
            from parseable_tpu.alerts import validate_target

            try:
                validate_target(body)
            except ValueError as e:
                return web.json_response({"error": str(e)}, status=400)
        if collection == "correlations":
            # reference validates correlation configs against live streams
            # (correlation.rs:280); executable here via the JOIN SQL surface
            # — may fall back to a storage-backed stream listing, so it
            # runs on a worker like the put itself
            try:
                await _run_traced(
                    state, _validate_correlation, state, body, request["username"]
                )
            except ValueError as e:
                return web.json_response({"error": str(e)}, status=400)
        await _run_traced(state, state.p.metastore.put_document, collection, doc_id, body)
        return web.json_response(body)

    async def get_doc(request: web.Request):
        state: ServerState = request.app["state"]
        if not state.rbac.authorize(request["username"], get_action):
            return web.json_response({"error": "Forbidden"}, status=403)
        doc = await _run_traced(
            state, state.p.metastore.get_document, collection, request.match_info["id"]
        )
        if doc is None:
            return web.json_response({"error": "not found"}, status=404)
        return web.json_response(doc)

    async def list_docs(request: web.Request):
        state: ServerState = request.app["state"]
        if not state.rbac.authorize(request["username"], get_action):
            return web.json_response({"error": "Forbidden"}, status=403)
        return web.json_response(
            await _run_traced(state, state.p.metastore.list_documents, collection)
        )

    async def delete_doc(request: web.Request):
        state: ServerState = request.app["state"]
        if not state.rbac.authorize(request["username"], delete_action):
            return web.json_response({"error": "Forbidden"}, status=403)
        await _run_traced(
            state, state.p.metastore.delete_document, collection, request.match_info["id"]
        )
        return web.json_response({"message": "deleted"})

    return put_doc, get_doc, list_docs, delete_doc


# ----- intra-cluster data plane --------------------------------------------


def staging_window_table(stream, start, end, fields):
    """This node's staging window as ONE table, bounded to [start, end) and
    projected to `fields` (the timestamp column always rides along so the
    querier can re-filter). Shared verbatim by the HTTP staging handler and
    the Flight DoGet staging ticket (server/flight.py) so the two transport
    tiers cannot drift — byte-identical fallback is a data contract, not a
    convention. Returns None when the window is empty."""
    import pyarrow as pa
    import pyarrow.compute as pc

    batches = stream.staging_batches()
    # flushed-but-not-yet-uploaded parquet is part of this node's
    # staging window too — without it, rows are invisible to remote
    # queriers for a whole upload interval. Unclaimed == not yet
    # committed, so the querier's manifest scan can't double-count.
    batches.extend(stream.unclaimed_parquet_batches())
    if not batches:
        return None
    from parseable_tpu.utils.arrowutil import adapt_batch, merge_schemas

    schema = merge_schemas([b.schema for b in batches])
    table = pa.Table.from_batches([adapt_batch(schema, b) for b in batches])
    if (
        (start is not None or end is not None)
        and DEFAULT_TIMESTAMP_KEY in table.column_names
    ):
        col = table.column(DEFAULT_TIMESTAMP_KEY)
        mask = None
        if start is not None:
            mask = pc.greater_equal(
                col, pa.scalar(start.replace(tzinfo=None), type=col.type)
            )
        if end is not None:
            m2 = pc.less(col, pa.scalar(end.replace(tzinfo=None), type=col.type))
            mask = m2 if mask is None else pc.and_(mask, m2)
        table = table.filter(mask)
    if fields is not None:
        keep = [
            c
            for c in table.column_names
            if c in fields or c == DEFAULT_TIMESTAMP_KEY
        ]
        table = table.select(keep)
    if table.num_rows == 0:
        return None
    return table


@require(Action.QUERY, "name")
async def internal_staging(request: web.Request) -> web.Response:
    """GET /api/v1/internal/staging/{name}: this node's staging-window rows
    as Arrow IPC — the reference's querier->ingestor Flight do_get
    (airplane.rs:155-184) over HTTP. Guarded by stream-scoped QUERY
    permission (the reference uses an intra-cluster token; queriers here
    authenticate with the shared cluster credentials, which are admin).

    Bounded fan-in params (all optional; absent = the old full-window
    behavior, so older queriers keep working): `start`/`end` RFC3339
    instants filter rows to [start, end) on the event timestamp, and
    `fields` (comma-separated) projects columns before serialization —
    the timestamp column always rides along so the querier can re-filter.
    """
    from parseable_tpu.utils.timeutil import parse_rfc3339

    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    stream = state.p.streams.get(name)
    if stream is None:
        return web.Response(status=204)
    try:
        start = parse_rfc3339(request.query["start"]) if "start" in request.query else None
        end = parse_rfc3339(request.query["end"]) if "end" in request.query else None
    except TimeParseError as e:
        return web.json_response({"error": f"bad time bound: {e}"}, status=400)
    fields = None
    if "fields" in request.query:
        fields = {f for f in request.query["fields"].split(",") if f}

    def work() -> bytes:
        import io

        import pyarrow.ipc as ipc

        table = staging_window_table(stream, start, end, fields)
        if table is None:
            return b""
        sink = io.BytesIO()
        with ipc.new_stream(sink, table.schema) as w:
            w.write_table(table)
        return sink.getvalue()

    data = await asyncio.get_running_loop().run_in_executor(state.workers, work)
    if not data:
        return web.Response(status=204)
    return web.Response(body=data, content_type="application/vnd.apache.arrow.stream")


@require(Action.QUERY, "name")
async def internal_query_partial(request: web.Request) -> web.Response:
    """POST /api/v1/internal/query/partial/{name}: execute a pushed-down
    GROUP BY aggregate over this node's LOCAL slice (own staging window +
    manifest files it owns via the basename owner tag) and return one
    combined partial table as Arrow IPC (query/fanout.py documents the
    protocol). 204 = empty local slice; 400 = plan not partializable (the
    querier keeps that query on the central path); response headers carry
    scan accounting + this node's owner tag so the querier can verify the
    delegation matches the registry."""
    from parseable_tpu.query import fanout as FO

    state: ServerState = request.app["state"]
    name = request.match_info["name"]
    try:
        body = await request.json()
    except json.JSONDecodeError:
        return web.json_response({"error": "invalid JSON body"}, status=400)
    sql = body.get("query")
    if not sql:
        return web.json_response({"error": "missing 'query'"}, status=400)
    start, end = body.get("startTime"), body.get("endTime")

    def work():
        return FO.execute_local_partial(state.p, name, sql, start, end)

    try:
        out = await _run_query_traced(state, work)
    except FO.UnsupportedPartial as e:
        return web.json_response({"error": str(e)}, status=400)
    except (SqlError, QueryError, TimeParseError) as e:
        return web.json_response({"error": str(e)}, status=400)
    except Exception as e:
        logger.exception("partial pushdown failed")
        return web.json_response({"error": str(e)}, status=500)
    headers = {FO.H_TAG: state.p.owner_tag}
    if out is None:
        return web.Response(status=204, headers=headers)
    payload, meta = out
    headers[FO.H_ROWS] = str(meta["rows_scanned"])
    headers[FO.H_ERRORS] = str(meta["scan_errors"])
    if not payload:
        return web.Response(status=204, headers=headers)
    return web.Response(
        body=payload,
        content_type="application/vnd.apache.arrow.stream",
        headers=headers,
    )


async def logout(request: web.Request) -> web.Response:
    """GET /api/v1/logout — invalidate the presented session."""
    state: ServerState = request.app["state"]
    token = None
    auth = request.headers.get("Authorization", "")
    if auth.startswith("Bearer "):
        token = auth[7:]
    elif "session" in request.cookies:
        token = request.cookies["session"]
    if token:
        state.rbac.sessions.pop(token, None)
        state._refresh_edge_auth()
    resp = web.json_response({"message": "logged out"})
    resp.del_cookie("session")
    return resp


@require(Action.CREATE_STREAM)
async def schema_detect(request: web.Request) -> web.Response:
    """POST /api/v1/logstream/schema/detect — infer the Arrow schema a
    payload would produce, without creating anything (reference:
    logstream.rs detect_schema)."""
    from parseable_tpu.event.format import SchemaVersion, infer_json_schema
    from parseable_tpu.server.ingest_utils import flatten_json_records

    state: ServerState = request.app["state"]
    try:
        payload = await request.json()
    except json.JSONDecodeError as e:
        return web.json_response({"error": f"invalid JSON: {e}"}, status=400)
    records = payload if isinstance(payload, list) else [payload]
    if not all(isinstance(r, dict) for r in records):
        return web.json_response({"error": "expected JSON object(s)"}, status=400)
    try:
        # the same depth-guarded pipeline ingest runs (shared helper, so
        # detect and ingest can't diverge on nesting limits)
        rows = flatten_json_records(
            records,
            state.p.options.event_flatten_level,
            None,
            None,
            None,
            state.p.options.event_max_chunk_age,
        )
        schema = infer_json_schema(rows, SchemaVersion.V1, True)
    except Exception as e:
        return web.json_response({"error": str(e)}, status=400)
    return web.json_response(
        {
            "fields": [
                {"name": f.name, "data_type": str(f.type), "nullable": f.nullable}
                for f in schema
            ]
        }
    )


@require(Action.PUT_ALERT)
async def alert_set_enabled(request: web.Request) -> web.Response:
    """PUT /api/v1/alerts/{id}/{enable|disable} (reference: alert enable/
    disable routes)."""
    state: ServerState = request.app["state"]
    alert_id = request.match_info["id"]
    action = request.match_info["action"]

    def _toggle() -> dict | None:
        doc = state.p.metastore.get_document("alerts", alert_id)
        if doc is None:
            return None
        doc["state"] = "disabled" if action == "disable" else "enabled"
        state.p.metastore.put_document("alerts", alert_id, doc)
        return doc

    doc = await _run_traced(state, _toggle)
    if doc is None:
        return web.json_response({"error": "unknown alert"}, status=404)
    return web.json_response({"message": f"alert {action}d"})


@require(Action.PUT_ALERT)
async def alert_evaluate_now(request: web.Request) -> web.Response:
    """PUT /api/v1/alerts/{id}/evaluate_alert — run one evaluation
    immediately (reference: evaluate_alert route)."""
    from parseable_tpu.alerts import evaluate_alert, record_outcome

    state: ServerState = request.app["state"]
    alert_id = request.match_info["id"]
    doc = await _run_traced(state, state.p.metastore.get_document, "alerts", alert_id)
    if doc is None:
        return web.json_response({"error": "unknown alert"}, status=404)

    def work():
        outcome = evaluate_alert(state.p, doc)
        # a manual evaluation is a real one: state machine, MTTR, SSE,
        # and target notifications all apply (review finding)
        record_outcome(state.p, doc, outcome)
        return outcome

    try:
        outcome = await asyncio.get_running_loop().run_in_executor(state.workers, work)
    except Exception as e:
        return web.json_response({"error": f"evaluation failed: {e}"}, status=400)
    return web.json_response(
        {"id": alert_id, "state": outcome.state, "actual": outcome.actual, "message": outcome.message}
    )


@require(Action.PUT_ALERT)
async def alert_update_notification_state(request: web.Request) -> web.Response:
    """PUT /api/v1/alerts/{id}/update_notification_state
    {"state": "notify" | "indefinite" | "<rfc3339 until>"} (reference:
    NotificationState — mute/snooze alert notifications)."""
    state: ServerState = request.app["state"]
    alert_id = request.match_info["id"]
    doc = await _run_traced(state, state.p.metastore.get_document, "alerts", alert_id)
    if doc is None:
        return web.json_response({"error": "unknown alert"}, status=404)
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return web.json_response({"error": f"invalid JSON: {e}"}, status=400)
    new_state = str(body.get("state", "notify"))
    if new_state not in ("notify", "indefinite"):
        from parseable_tpu.utils.timeutil import parse_rfc3339

        try:
            parse_rfc3339(new_state)
        except (TimeParseError, ValueError):
            return web.json_response(
                {"error": "state must be notify, indefinite, or an RFC3339 instant"},
                status=400,
            )
    doc["notification_state"] = new_state
    await _run_traced(state, state.p.metastore.put_document, "alerts", alert_id, doc)
    return web.json_response({"message": "notification state updated", "state": new_state})


@require(Action.PUT_ALERT)
async def put_outbound_policy(request: web.Request) -> web.Response:
    """PUT /api/v1/alert-target-policy — domain/CIDR allow/deny lists for
    where notifications may POST (reference: outbound_http_policy.rs)."""
    state: ServerState = request.app["state"]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return web.json_response({"error": f"invalid JSON: {e}"}, status=400)
    import ipaddress

    for cidr in body.get("denied_cidrs") or []:
        try:
            ipaddress.ip_network(cidr, strict=False)
        except ValueError:
            return web.json_response({"error": f"invalid CIDR {cidr!r}"}, status=400)
    policy = {
        "allowed_domains": [str(d) for d in body.get("allowed_domains") or []],
        "denied_domains": [str(d) for d in body.get("denied_domains") or []],
        "denied_cidrs": [str(c) for c in body.get("denied_cidrs") or []],
    }
    await _run_traced(
        state, state.p.metastore.put_document, "policies", "outbound_policy", policy
    )
    return web.json_response(policy)


@require(Action.GET_ALERT)
async def get_outbound_policy(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    policy = (
        await _run_traced(
            state, state.p.metastore.get_document, "policies", "outbound_policy"
        )
        or {}
    )
    return web.json_response(policy)


@require(Action.GET_DASHBOARD)
async def dashboards_list_tags(request: web.Request) -> web.Response:
    """GET /api/v1/dashboards/list_tags (reference: users/dashboards.rs)."""
    state: ServerState = request.app["state"]
    tags: set[str] = set()
    docs = await _run_traced(state, state.p.metastore.list_documents, "dashboards")
    for doc in docs:
        for tag in doc.get("tags") or []:
            tags.add(str(tag))
    return web.json_response(sorted(tags))


@require(Action.CREATE_DASHBOARD)
async def dashboard_add_tile(request: web.Request) -> web.Response:
    """PUT /api/v1/dashboards/{id}/add_tile (reference: add_tile route)."""
    state: ServerState = request.app["state"]
    dash_id = request.match_info["id"]
    doc = await _run_traced(state, state.p.metastore.get_document, "dashboards", dash_id)
    if doc is None:
        return web.json_response({"error": "unknown dashboard"}, status=404)
    try:
        tile = await request.json()
    except json.JSONDecodeError as e:
        return web.json_response({"error": f"invalid JSON: {e}"}, status=400)
    if not isinstance(tile, dict) or not tile.get("title"):
        return web.json_response({"error": "tile needs a title"}, status=400)
    doc.setdefault("tiles", []).append(tile)
    doc["modified"] = rfc3339_now()
    await _run_traced(state, state.p.metastore.put_document, "dashboards", dash_id, doc)
    return web.json_response(doc)


@require(Action.GET_ALERT)
async def alert_state_handler(request: web.Request) -> web.Response:
    """GET /api/v1/alerts/{id}/state — current state incl. MTTR fields."""
    state: ServerState = request.app["state"]
    doc = await _run_traced(
        state, state.p.metastore.get_document, "alert_state", request.match_info["id"]
    )
    if doc is None:
        return web.json_response({"error": "no state yet"}, status=404)
    return web.json_response(doc)


@require(Action.GET_ALERT)
async def alerts_sse(request: web.Request) -> web.StreamResponse:
    """GET /api/v1/alerts/sse — alert state transitions as server-sent
    events (reference: src/sse/mod.rs Broadcaster push)."""
    import queue as _q

    from parseable_tpu.alerts import ALERT_EVENTS

    state: ServerState = request.app["state"]
    sid, events = ALERT_EVENTS.subscribe()
    resp = web.StreamResponse(
        headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
        }
    )
    await resp.prepare(request)
    # poll with get_nowait + sleep: holding a worker thread in a blocking
    # get() would let a handful of idle SSE clients starve the shared pool
    idle = 0.0
    try:
        while not state.shutting_down:
            try:
                event = events.get_nowait()
            except _q.Empty:
                await asyncio.sleep(0.5)
                idle += 0.5
                if idle >= 15:
                    await resp.write(b": keepalive\n\n")
                    idle = 0.0
                continue
            idle = 0.0
            await resp.write(f"data: {json.dumps(event)}\n\n".encode())
    except (ConnectionError, ConnectionResetError, asyncio.CancelledError):
        pass
    finally:
        ALERT_EVENTS.unsubscribe(sid)
    return resp


@require(Action.MANAGE_API_KEYS)
async def create_api_key(request: web.Request) -> web.Response:
    """POST /api/v1/apikeys (reference: handlers/http/apikeys.rs). The
    plaintext key appears only in this response."""
    from parseable_tpu.apikeys import create_key

    state: ServerState = request.app["state"]
    body = await request.json()
    name = body.get("name")
    if not name:
        return web.json_response({"error": "key needs a name"}, status=400)
    ttl = body.get("ttl_days")
    if ttl is not None:
        try:
            ttl = int(ttl)
        except (TypeError, ValueError):
            return web.json_response({"error": "ttl_days must be an integer"}, status=400)
        if ttl <= 0:
            return web.json_response({"error": "ttl_days must be positive"}, status=400)
    doc = create_key(state.p.metastore, request["username"], name, ttl)
    return web.json_response(doc)


@require(Action.MANAGE_API_KEYS)
async def list_api_keys(request: web.Request) -> web.Response:
    from parseable_tpu.apikeys import list_keys

    state: ServerState = request.app["state"]
    return web.json_response(list_keys(state.p.metastore))


@require(Action.MANAGE_API_KEYS)
async def delete_api_key(request: web.Request) -> web.Response:
    from parseable_tpu.apikeys import revoke_key

    state: ServerState = request.app["state"]
    if not revoke_key(state.p.metastore, request.match_info["id"]):
        return web.json_response({"error": "unknown key"}, status=404)
    return web.json_response({"message": "revoked"})


@require(Action.QUERY_LLM)
async def llm_sql(request: web.Request) -> web.Response:
    """POST /api/v1/llm — natural language -> SQL via an OpenAI-compatible
    completion API (reference: handlers/http/llm.rs:92-147). The prompt
    embeds the stream's schema; requires P_OPENAI_API_KEY."""
    state: ServerState = request.app["state"]
    api_key = state.p.options.openai_api_key
    if not api_key:
        return web.json_response(
            {"error": "LLM is not configured (set P_OPENAI_API_KEY)"}, status=400
        )
    body = await request.json()
    prompt = body.get("prompt")
    stream_name = body.get("stream")
    if not prompt or not stream_name:
        return web.json_response({"error": "need 'prompt' and 'stream'"}, status=400)
    try:
        stream = state.p.get_stream(stream_name)
    except StreamNotFound:
        return web.json_response({"error": f"stream {stream_name} not found"}, status=404)
    schema_desc = ", ".join(
        f"{f.name} {f.type}" for f in stream.metadata.schema.values()
    )

    def work():
        import urllib.request

        full_prompt = (
            f"I have a table named {stream_name} with columns: {schema_desc}. "
            f"Write a SQL query (no explanation, just SQL) for: {prompt}"
        )
        payload = json.dumps(
            {
                "model": body.get("model", "gpt-4o-mini"),
                "messages": [{"role": "user", "content": full_prompt}],
                "temperature": 0,
            }
        ).encode()
        req = urllib.request.Request(
            f"{state.p.options.openai_base_url.rstrip('/')}/chat/completions",
            data=payload,
            method="POST",
            headers={
                "Content-Type": "application/json",
                "Authorization": f"Bearer {api_key}",
            },
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            out = json.loads(resp.read())
        text = out["choices"][0]["message"]["content"]
        # strip a markdown code fence if the model added one
        if "```" in text:
            text = text.split("```")[1]
            if text.startswith("sql"):
                text = text[3:]
        return text.strip()

    try:
        sql = await asyncio.get_running_loop().run_in_executor(state.workers, work)
    except Exception as e:
        logger.warning("llm proxy failed: %s", e)
        return web.json_response({"error": f"LLM request failed: {e}"}, status=502)
    return web.json_response({"sql": sql})


@require(Action.MANAGE_TENANTS)
async def put_tenant(request: web.Request) -> web.Response:
    """PUT /api/v1/tenants/{id} — suspension flag + daily event quota
    (reference: tenants/mod.rs:31-160)."""
    state: ServerState = request.app["state"]
    body = await request.json() if request.can_read_body else {}
    try:
        doc = state.tenants.put(request.match_info["id"], body or {})
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    return web.json_response(doc)


@require(Action.MANAGE_TENANTS)
async def list_tenants(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    return web.json_response(state.tenants.list())


@require(Action.MANAGE_TENANTS)
async def delete_tenant(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    if not state.tenants.delete(request.match_info["id"]):
        return web.json_response({"error": "unknown tenant"}, status=404)
    return web.json_response({"message": "deleted"})


@require(Action.LIST_CLUSTER)
async def cluster_info(request: web.Request) -> web.Response:
    # array shape matches the reference's Vec<ClusterInfo>
    # (cluster/mod.rs:1001); each entry carries the latest pmeta scrape
    # state so billing collection is observable from the cluster plane
    state: ServerState = request.app["state"]
    from parseable_tpu.server import cluster as C

    nodes = await _run_traced(state, state.p.metastore.list_nodes)
    for n in nodes:
        n["pmeta_last_scrape"] = C.LAST_PMETA_SCRAPE
    return web.json_response(nodes)


def fanout_to_ingestors(
    state: "ServerState",
    method: str,
    path: str,
    json_body=None,
    headers=None,
    kinds: tuple[str, ...] = ("ingestor",),
) -> None:
    """Propagate a querier-side mutation to live peers
    (reference: cluster/mod.rs:391-840 sync_*_with_ingestors). Fire-and-
    forget on the worker pool — the metastore holds the durable state; the
    fan-out refreshes peer caches / per-node stream jsons. RBAC changes go
    to ALL peer kinds (other queriers also cache users/roles)."""
    from parseable_tpu.config import Mode as _Mode

    if state.p.options.mode != _Mode.QUERY:
        return
    from parseable_tpu.server import cluster as C

    def _fanout() -> None:
        # worker owns its errors: the Future is discarded, so an uncaught
        # raise (metastore listing, peer I/O) would otherwise vanish
        try:
            failed = C.sync_with_ingestors(state.p, method, path, json_body, headers, kinds)
            if failed:
                logger.warning("peer fan-out %s %s failed for: %s", method, path, failed)
        except Exception:
            logger.exception("peer fan-out %s %s failed", method, path)

    state.workers.submit(telemetry.propagate(_fanout))


async def internal_rbac_reload(request: web.Request) -> web.Response:
    """POST /api/v1/internal/rbac/reload: drop the in-memory RBAC cache and
    reload from the metastore (cache-invalidation flavor of the reference's
    user/role/password sync)."""
    state: ServerState = request.app["state"]
    if not state.rbac.authorize(request["username"], Action.PUT_USER):
        return web.json_response({"error": "Forbidden"}, status=403)
    await _run_traced(state, state.reload_rbac)
    return web.json_response({"message": "rbac reloaded"})


@require(Action.LIST_CLUSTER_METRICS)
async def cluster_metrics(request: web.Request) -> web.Response:
    """GET /api/v1/cluster/metrics: scrape every node's /metrics into a
    per-node rollup (reference: cluster/mod.rs:1147-1320)."""
    state: ServerState = request.app["state"]
    from parseable_tpu.server import cluster as C

    data = await asyncio.get_running_loop().run_in_executor(
        state.workers, C.collect_node_metrics, state.p
    )
    return web.json_response(data)


@require(Action.METRICS)
async def cluster_trace(request: web.Request) -> web.Response:
    """GET /api/v1/cluster/trace/{trace_id}: fan out to every live peer's
    span ring and return ONE stitched, skew-corrected span tree with
    critical-path attribution — the cluster-wide view of the trace id a
    query response echoed in X-P-Trace-Id."""
    state: ServerState = request.app["state"]
    trace_id = request.match_info["trace_id"].strip().lower()
    if len(trace_id) != 32 or any(c not in "0123456789abcdef" for c in trace_id):
        return web.json_response(
            {"error": "trace_id must be 32 hex characters"}, status=400
        )
    from parseable_tpu.server import cluster as C

    data = await _run_traced(state, C.assemble_cluster_trace, state.p, trace_id)
    return web.json_response(data)


@require(Action.LIST_CLUSTER_METRICS)
async def cluster_audit(request: web.Request) -> web.Response:
    """GET /api/v1/cluster/audit[?scope=local|cluster&quiesce=0|1]: run the
    conservation-law audit on demand (audit.py). Defaults assert quiesce —
    call it after draining to check the books balance; quiesce=0 applies
    only the at-rest/monotonicity checks safe under load."""
    state: ServerState = request.app["state"]
    scope = request.query.get("scope", "cluster")
    if scope not in ("local", "cluster"):
        return web.json_response(
            {"error": "scope must be 'local' or 'cluster'"}, status=400
        )
    quiesce = request.query.get("quiesce", "1") not in ("0", "false")
    from parseable_tpu import audit as A

    report = await _run_traced(state, A.run_audit, state.p, scope, quiesce)
    return web.json_response(report)


@require(Action.DELETE_NODE)
async def remove_node_handler(request: web.Request) -> web.Response:
    """DELETE /api/v1/cluster/{node_id}: deregister a dead node
    (reference: cluster/mod.rs:1185; live nodes are refused)."""
    state: ServerState = request.app["state"]
    node_id = request.match_info["node_id"]
    from parseable_tpu.server import cluster as C

    try:
        removed = await asyncio.get_running_loop().run_in_executor(
            state.workers, C.remove_node, state.p, node_id
        )
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    if not removed:
        return web.json_response({"error": f"unknown node {node_id}"}, status=404)
    return web.json_response({"message": f"removed node {node_id}"})


# -------------------------------------------------------------------- app


def build_app(state: ServerState) -> web.Application:
    from parseable_tpu.config import edge_options

    app = web.Application(
        middlewares=[trace_middleware, auth_middleware],
        # shared with the native edge acceptor's framing limit: both tiers
        # must agree on which bodies even get read (P_INGEST_MAX_BODY_BYTES)
        client_max_size=edge_options()["max_body"],
    )
    app["state"] = state
    mode = state.p.options.mode
    r = app.router

    # health (all modes)
    r.add_get("/api/v1/liveness", liveness)
    r.add_get("/api/v1/readiness", readiness)
    r.add_get("/api/v1/about", about)
    r.add_get("/api/v1/debug/profile", debug_profile)
    r.add_get("/api/v1/debug/spans", debug_spans)
    r.add_get("/api/v1/metrics", metrics_handler)
    r.add_get("/api/v1/login", login)

    if mode in (Mode.ALL, Mode.INGEST):
        r.add_post("/api/v1/ingest", ingest)
        r.add_post("/api/v1/logstream/{name}", post_event)
        r.add_post("/v1/{kind}", otel_ingest)
        r.add_get("/api/v1/internal/staging/{name}", internal_staging)
        # partial-aggregate pushdown: the querier scatters GROUP BY
        # aggregates here instead of pulling the raw staging window
        r.add_post("/api/v1/internal/query/partial/{name}", internal_query_partial)

    if mode in (Mode.ALL, Mode.QUERY):
        r.add_post("/api/v1/query", query)
        r.add_post("/api/v1/counts", counts)
        r.add_get("/api/v1/logstream/{name}/livetail", livetail_sse)

    # stream management on every mode (ingestors accept sync'd definitions)
    r.add_get("/api/v1/logstream", list_streams)
    r.add_put("/api/v1/logstream/{name}", put_stream)
    r.add_delete("/api/v1/logstream/{name}", delete_stream)
    r.add_get("/api/v1/logstream/{name}/schema", get_schema)
    r.add_get("/api/v1/logstream/{name}/info", stream_info)
    r.add_get("/api/v1/logstream/{name}/stats", stream_stats)
    r.add_put("/api/v1/logstream/{name}/retention", put_retention)
    r.add_get("/api/v1/logstream/{name}/retention", get_retention)
    r.add_put("/api/v1/logstream/{name}/hottier", put_hot_tier)
    r.add_get("/api/v1/logstream/{name}/hottier", get_hot_tier)
    r.add_delete("/api/v1/logstream/{name}/hottier", delete_hot_tier)

    # rbac
    r.add_post("/api/v1/user/{username}", put_user)
    r.add_get("/api/v1/user", list_users)
    r.add_delete("/api/v1/user/{username}", delete_user)
    r.add_put("/api/v1/user/{username}/role", put_user_roles)
    r.add_put("/api/v1/role/{name}", put_role)
    r.add_get("/api/v1/role", list_roles)
    r.add_delete("/api/v1/role/{name}", delete_role)

    # alert-state SSE + sub-resource routes must register before the
    # generic /alerts/{id} routes (aiohttp matches in registration order)
    r.add_get("/api/v1/alerts/sse", alerts_sse)
    r.add_get("/api/v1/alerts/{id}/state", alert_state_handler)
    r.add_put("/api/v1/alerts/{id}/{action:(enable|disable)}", alert_set_enabled)
    r.add_put("/api/v1/alerts/{id}/evaluate_alert", alert_evaluate_now)
    r.add_put("/api/v1/alerts/{id}/update_notification_state", alert_update_notification_state)
    r.add_put("/api/v1/alert-target-policy", put_outbound_policy)
    r.add_get("/api/v1/alert-target-policy", get_outbound_policy)
    r.add_get("/api/v1/dashboards/list_tags", dashboards_list_tags)
    r.add_put("/api/v1/dashboards/{id}/add_tile", dashboard_add_tile)
    r.add_get("/api/v1/logout", logout)
    r.add_post("/api/v1/logstream/schema/detect", schema_detect)

    # alerts / targets / dashboards / filters / correlations
    for coll, base, acts in (
        ("alerts", "/api/v1/alerts", (Action.PUT_ALERT, Action.GET_ALERT, Action.DELETE_ALERT)),
        ("targets", "/api/v1/targets", (Action.PUT_TARGET, Action.GET_TARGET, Action.DELETE_TARGET)),
        ("dashboards", "/api/v1/dashboards", (Action.CREATE_DASHBOARD, Action.GET_DASHBOARD, Action.DELETE_DASHBOARD)),
        ("filters", "/api/v1/filters", (Action.CREATE_FILTER, Action.GET_FILTER, Action.DELETE_FILTER)),
        ("correlations", "/api/v1/correlation", (Action.CREATE_CORRELATION, Action.GET_CORRELATION, Action.DELETE_CORRELATION)),
    ):
        put_doc, get_doc, list_docs, delete_doc = crud_routes(coll, *acts)
        r.add_post(base, put_doc)
        r.add_put(base + "/{id}", put_doc)
        r.add_get(base, list_docs)
        r.add_get(base + "/{id}", get_doc)
        r.add_delete(base + "/{id}", delete_doc)

    r.add_post("/api/v1/llm", llm_sql)
    r.add_put("/api/v1/tenants/{id}", put_tenant)
    r.add_get("/api/v1/tenants", list_tenants)
    r.add_delete("/api/v1/tenants/{id}", delete_tenant)
    r.add_post("/api/v1/apikeys", create_api_key)
    r.add_get("/api/v1/apikeys", list_api_keys)
    r.add_delete("/api/v1/apikeys/{id}", delete_api_key)
    from parseable_tpu.server import extras as _extras
    from parseable_tpu.server import oidc as _oidc

    _extras.register(r)
    _oidc.register(r)
    r.add_get("/api/v1/cluster/info", cluster_info)
    r.add_get("/api/v1/cluster/metrics", cluster_metrics)
    # sub-resources before the generic /cluster/{node_id} delete (aiohttp
    # matches in registration order); every mode serves both — an ingestor
    # answers scope=local audits and contributes spans to stitched traces
    r.add_get("/api/v1/cluster/trace/{trace_id}", cluster_trace)
    r.add_get("/api/v1/cluster/audit", cluster_audit)
    r.add_delete("/api/v1/cluster/{node_id}", remove_node_handler)
    r.add_post("/api/v1/internal/rbac/reload", internal_rbac_reload)

    # console UI (reference embeds the prebuilt bundle via build.rs;
    # here P_UI_DIR points at an unpacked console build, served at /)
    ui_dir = state.p.options.ui_dir
    if ui_dir and ui_dir.is_dir():
        if not (ui_dir / "index.html").is_file():
            logger.error("P_UI_DIR %s has no index.html; console disabled", ui_dir)
        else:
            async def ui_index(request: web.Request) -> web.FileResponse:
                return web.FileResponse(ui_dir / "index.html")

            r.add_get("/", ui_index)
            if (ui_dir / "assets").is_dir():
                r.add_static("/assets", ui_dir / "assets")
            # SPA fallback: browser refreshes on console routes (anything
            # that isn't the API) get the app shell back
            r.add_get("/{tail:(?!api/|v1/|assets/).*}", ui_index)
    return app


def run_server(opts: Options | None = None, storage: StorageOptions | None = None) -> None:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(levelname)s %(name)s %(message)s")
    p = Parseable(opts, storage)
    if p.options.otlp_endpoint:
        # Options may carry an endpoint the env didn't (programmatic boot)
        telemetry.TRACER.endpoint = p.options.otlp_endpoint
    # deployment reconcile + metadata migrations before anything registers
    # (reference: main.rs:73-79 resolve_parseable_metadata + migration runs)
    from parseable_tpu.migration import resolve_parseable_metadata, run_migrations

    resolve_parseable_metadata(p)
    upgraded = run_migrations(p)
    if upgraded:
        logger.info("migrated %d stream metadata documents", upgraded)
    state = ServerState(p)
    if p.options.query_engine == "tpu" and p.options.mode in (Mode.ALL, Mode.QUERY):
        # the process that serves queries takes its devices now, before it
        # listens: a backend that cannot start fails the boot, and what the
        # engine runs on is in the log and /api/v1/about, never a guess
        from parseable_tpu.query.executor_tpu import device_summary

        state.query_device = device_summary(p.options)
        logger.info(
            "query engine tpu on platform=%s device_kind=%s devices=%d mesh=%s",
            state.query_device["platform"],
            state.query_device["device_kind"],
            state.query_device["device_count"],
            state.query_device["mesh"] or "none (single chip)",
        )
    host, _, port = p.options.address.rpartition(":")
    # Arrow Flight data plane BEFORE registration: register_node advertises
    # the flight endpoint from options, and a failed start zeroes the port
    # so peers never discover a plane this node can't serve
    if p.options.flight_port > 0:
        try:
            from parseable_tpu.server.flight import maybe_start_flight

            state.flight = maybe_start_flight(state)
        except ImportError:
            logger.warning(
                "P_FLIGHT_PORT=%d set but pyarrow.flight is unavailable; "
                "staying on the HTTP data plane",
                p.options.flight_port,
            )
            p.options.flight_port = 0
    p.register_node(p.options.address)
    if p.options.check_update:
        from parseable_tpu.utils.update import check_for_update

        state.workers.submit(check_for_update, p.options)
    state.start_sync_loops()
    # native ingest edge: its own listener port, C++ HTTP framing + auth
    # snapshot, Python dispatchers staging straight off C-owned buffers;
    # every miss declines verbatim to the aiohttp app built below
    from parseable_tpu.native.edge import maybe_start_edge

    state.edge = maybe_start_edge(state)
    app = build_app(state)

    async def on_shutdown(app):
        state.stop()

    app.on_shutdown.append(on_shutdown)
    # TLS: both cert+key configured => https (reference: cli.rs:302-330;
    # modal/mod.rs:86-187 https branch of the server bootstrap)
    ssl_ctx = p.options.server_ssl_context()
    logger.info(
        "parseable-tpu %s starting in %s mode on %s://%s (store: %s)",
        __version__,
        p.options.mode.value,
        p.options.get_scheme(),
        p.options.address,
        p.provider.get_endpoint(),
    )
    web.run_app(
        app,
        host=host or "0.0.0.0",
        port=int(port or 8000),
        ssl_context=ssl_ctx,
        print=None,
    )


def main(argv: list[str] | None = None) -> None:
    from parseable_tpu.utils.compile_cache import configure_compile_cache

    opts, storage = parse_cli(argv)
    if opts.query_engine == "tpu":  # a CPU-engine node compiles nothing
        configure_compile_cache()
    run_server(opts, storage)


if __name__ == "__main__":
    main()
