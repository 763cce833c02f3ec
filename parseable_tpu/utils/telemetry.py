"""Self-telemetry: request tracing + the server's own spans, self-ingested.

Parity target (reference: src/telemetry.rs:55-149 init_tracing -> OTLP
exporter): spans recorded around the hot paths (ingest, staging flush,
object-store sync, query) batch in memory and POST to {endpoint}/v1/traces
as OTLP JSON when P_OTLP_ENDPOINT is set. No external SDK — the OTLP/HTTP
JSON shape is small and this process's needs are a handful of span kinds.

Beyond OTLP export, this build dogfoods the lake itself:

- A `contextvars`-based trace context (trace_id, current span_id) threads
  one request through ingest -> staging flush -> object sync -> query.
  HTTP ingress honors W3C `traceparent`; background sync ticks open their
  own root context so their child spans correlate per tick.
- Every finished span lands in a bounded in-memory ring (`recent_spans`,
  served by GET /api/v1/debug/spans) and — when a `SpanSink` is attached —
  is appended as a row to the internal `pmeta` stream, so
  `SELECT name, avg(duration_ms) FROM pmeta GROUP BY name` runs through
  the normal SQL path over the lake's own telemetry.

Recording is a no-op (zero row/export cost) unless at least one consumer
exists: an OTLP endpoint, an attached sink, or an active trace context.
"""

from __future__ import annotations

import contextvars
import json
import logging
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager

logger = logging.getLogger(__name__)

MAX_BUFFER = 2048
EXPORT_BATCH = 256
SPAN_RING_SIZE = 4096
SINK_MAX_ROWS = 8192

# (trace_id, current_span_id) for the executing logical request; span_id may
# be None at the root of a fresh trace (first span then has no parent).
_TRACE_CTX: contextvars.ContextVar[tuple[str, str | None] | None] = contextvars.ContextVar(
    "p_trace_ctx", default=None
)
# set while the sink itself writes into pmeta: the write path must not spawn
# spans of its own (unbounded self-observation recursion otherwise)
_SUPPRESS: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "p_trace_suppress", default=False
)


def new_trace_id() -> str:
    return f"{random.getrandbits(128):032x}"


def new_span_id() -> str:
    return f"{random.getrandbits(64):016x}"


def current_trace_id() -> str | None:
    ctx = _TRACE_CTX.get()
    return ctx[0] if ctx else None


def current_span_id() -> str | None:
    ctx = _TRACE_CTX.get()
    return ctx[1] if ctx else None


def current_traceparent() -> str | None:
    """Outgoing W3C traceparent for the executing context, or None when
    there is no ambient trace or no current span to parent under. Injected
    into every intra-cluster HTTP hop (server/cluster.py) so peer spans
    join the caller's trace instead of rooting fresh per-node traces."""
    ctx = _TRACE_CTX.get()
    if ctx is None or ctx[1] is None:
        return None
    return f"00-{ctx[0]}-{ctx[1]}-01"


# this process's cluster identity, stamped onto every finished span row so
# a stitched cross-node trace can attribute each span to the node that
# recorded it (node = the owner tag files/snapshots already carry)
_NODE_IDENTITY: dict[str, str] = {"node": "", "role": ""}


def set_node_identity(node: str, role: str) -> None:
    _NODE_IDENTITY["node"] = node
    _NODE_IDENTITY["role"] = role


def node_identity() -> dict[str, str]:
    return dict(_NODE_IDENTITY)


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
    """W3C traceparent `00-<32x trace>-<16x span>-<2x flags>` ->
    (trace_id, parent_span_id), or None when absent/malformed/all-zero."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id = parts[0], parts[1].lower(), parts[2].lower()
    if version == "ff" or len(version) != 2:
        return None
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(version, 16)
        t = int(trace_id, 16)
        s = int(span_id, 16)
    except ValueError:
        return None
    if t == 0 or s == 0:
        return None
    return trace_id, span_id


@contextmanager
def trace_context(traceparent: str | None = None):
    """Root trace context for one logical request (HTTP request, sync tick).

    Honors an incoming W3C traceparent (spans then parent under the remote
    caller's span); otherwise starts a fresh trace. Yields the trace id."""
    parsed = parse_traceparent(traceparent)
    if parsed is not None:
        trace_id, parent_span = parsed
    else:
        trace_id, parent_span = new_trace_id(), None
    token = _TRACE_CTX.set((trace_id, parent_span))
    try:
        yield trace_id
    finally:
        _TRACE_CTX.reset(token)


def propagate(fn):
    """Bind `fn` to a snapshot of the caller's context so trace parentage
    survives the hop onto a worker-pool thread (pool threads otherwise start
    with an empty Context and record orphaned or unrecorded spans). Used by
    the write-path pools (compaction, upload, per-stream sync coordinators)
    and the storage backends' part/chunk fan-outs; the scan pool does the
    equivalent with an explicit copy_context().

    Each invocation runs in its own copy of the snapshot: a Context object
    cannot be entered by two threads at once (RuntimeError), and one wrapped
    callable is routinely fanned out via `pool.map` across many workers."""
    ctx = contextvars.copy_context()

    def bound(*args, **kwargs):
        return ctx.copy().run(fn, *args, **kwargs)

    return bound


@contextmanager
def suppress_tracing():
    """Disable span recording in this context (pmeta self-writes)."""
    token = _SUPPRESS.set(True)
    try:
        yield
    finally:
        _SUPPRESS.reset(token)


class SpanSink:
    """Buffers finished spans as rows for the internal `pmeta` stream.

    The server attaches its Parseable instance at startup; `flush()` (a
    background loop + shutdown hook) writes buffered rows through the normal
    event pipeline, so the lake's own spans are queryable with its own SQL
    (reference analogue: cluster metrics ingested into pmeta,
    cluster/mod.rs:1623-1784). Detached (library/test use), rows are
    dropped at record time at zero cost."""

    def __init__(self):
        self._p = None
        self._rows: list[dict] = []  # guarded-by: self._lock
        self._lock = threading.Lock()

    @property
    def attached(self) -> bool:
        return self._p is not None

    def attach(self, parseable) -> None:
        self._p = parseable

    def detach(self) -> None:
        self._p = None
        with self._lock:
            self._rows.clear()

    def record(self, row: dict) -> None:
        if self._p is None:
            return
        with self._lock:
            self._rows.append(row)
            if len(self._rows) > SINK_MAX_ROWS:
                del self._rows[: len(self._rows) - SINK_MAX_ROWS]

    def flush(self) -> int:
        """Write buffered span rows into the internal pmeta stream.
        Returns the number of rows written."""
        p = self._p
        if p is None:
            return 0
        with self._lock:
            rows, self._rows = self._rows, []
        if not rows:
            return 0
        try:
            from parseable_tpu import INTERNAL_STREAM_NAME
            from parseable_tpu.event.json_format import JsonEvent

            with suppress_tracing():
                stream = p.create_stream_if_not_exists(
                    INTERNAL_STREAM_NAME, stream_type="Internal"
                )
                ev = JsonEvent(rows, INTERNAL_STREAM_NAME).into_event(stream.metadata)
                ev.process(stream, commit_schema=p.commit_schema)
            return len(rows)
        except Exception:
            logger.exception("pmeta span flush failed; %d spans dropped", len(rows))
            return 0


SPAN_SINK = SpanSink()

# last-N finished spans for GET /api/v1/debug/spans (deque appends are
# GIL-atomic; readers snapshot with list())
_SPAN_RING: deque[dict] = deque(maxlen=SPAN_RING_SIZE)


def recent_spans(trace_id: str | None = None, limit: int = 1000) -> list[dict]:
    spans = list(_SPAN_RING)
    if trace_id:
        spans = [s for s in spans if s["trace_id"] == trace_id]
    return spans[-limit:]


def clear_recent_spans() -> None:
    _SPAN_RING.clear()


class Tracer:
    def __init__(self, endpoint: str | None = None, service_name: str = "parseable-tpu"):
        from parseable_tpu.config import env_str

        self.endpoint = endpoint or env_str("P_OTLP_ENDPOINT") or None
        self.service_name = service_name
        self._spans: list[dict] = []  # guarded-by: self._lock
        # at most ONE in-flight background export, tracked so shutdown can
        # join it (an unjoined per-flush daemon thread is exactly the leak
        # psan's thread accounting flags)
        self._export_thread: threading.Thread | None = None  # guarded-by: self._lock
        self._lock = threading.Lock()
        # flush() holds the export serializer while _flush_locked swaps the
        # buffer under the span lock; the reverse nesting would deadlock a
        # recording thread against a slow exporter
        # lock-order: Tracer._flush_inflight < Tracer._lock
        self._flush_inflight = threading.Lock()

    @property
    def enabled(self) -> bool:
        return self.endpoint is not None

    def _recording(self) -> bool:
        """Spans cost something only when a consumer exists: an OTLP
        endpoint, an attached pmeta sink, or an active trace context
        (debug/spans + parentage)."""
        if _SUPPRESS.get():
            return False
        return (
            self.endpoint is not None
            or SPAN_SINK.attached
            or _TRACE_CTX.get() is not None
        )

    @contextmanager
    def span(self, name: str, **attrs):
        """Record one span; yields a mutable attr dict so callers can attach
        values discovered mid-span (stream, rows, bytes, status_code).
        No-op (zero allocation beyond the dict) when nothing consumes."""
        if not self._recording():
            yield attrs
            return
        ctx = _TRACE_CTX.get()
        if ctx is not None:
            trace_id, parent_id = ctx
        else:
            # no ambient context: one trace per top-level operation — a
            # process-wide id would collapse everything into a single
            # unbounded trace
            trace_id, parent_id = new_trace_id(), None
        span_id = new_span_id()
        token = _TRACE_CTX.set((trace_id, span_id))
        # one more sink, on the profiler's own clock: while a jax.profiler
        # session runs, the span lies in its trace beside the device
        # operations (an atomic flag test when none does). Never the
        # import: a node that has not imported JAX has no profiler either,
        # and one that is importing it on another thread has none yet
        annotate = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
        annotation = None
        if annotate is not None:
            annotation = annotate(name, trace_id=trace_id, span_id=span_id)
            annotation.__enter__()
        start_ns = time.time_ns()
        err = None
        try:
            yield attrs
        except BaseException as e:
            err = e
            raise
        finally:
            end_ns = time.time_ns()
            if annotation is not None:
                annotation.__exit__(None, None, None)
            _TRACE_CTX.reset(token)
            self._finish(
                name, trace_id, span_id, parent_id, start_ns, end_ns, err, attrs
            )

    def record_span(
        self,
        name: str,
        start_ns: int,
        end_ns: int,
        parent_span_id: str | None = None,
        **attrs,
    ) -> str | None:
        """Record an already-timed span: the native telemetry ring replays
        work that happened below the ctypes boundary with its own
        wall-clock start/duration, so these spans carry REAL timings, not
        re-measured ones. Parents under the current context span unless an
        explicit parent_span_id is given. Returns the new span id, or None
        when nothing consumes spans."""
        if not self._recording():
            return None
        ctx = _TRACE_CTX.get()
        if ctx is not None:
            trace_id, ctx_span = ctx
        else:
            trace_id, ctx_span = new_trace_id(), None
        span_id = new_span_id()
        self._finish(
            name,
            trace_id,
            span_id,
            parent_span_id or ctx_span,
            start_ns,
            end_ns,
            None,
            attrs,
        )
        return span_id

    def _finish(self, name, trace_id, span_id, parent_id, start_ns, end_ns, err, attrs):
        row = {
            "event_type": "span",
            "trace_id": trace_id,
            "span_id": span_id,
            "parent_span_id": parent_id or "",
            "name": name,
            "stream": str(attrs.get("stream", "")),
            "duration_ms": round((end_ns - start_ns) / 1e6, 3),
            "bytes": int(attrs.get("bytes", 0) or 0),
            "rows": int(attrs.get("rows", 0) or 0),
            "status": "error" if err else str(attrs.get("status", "ok")),
            "status_code": int(attrs.get("status_code", 0) or 0),
            "ts": _rfc3339_ns(start_ns),
            "node": _NODE_IDENTITY["node"],
            "role": _NODE_IDENTITY["role"],
        }
        # native-telemetry detail attrs ride along when present so the
        # stitched cluster trace shows WHICH shard/lane produced a span and
        # why it declined — the fixed fields above stay the stable schema;
        # `survivors` is execute.merge's (groups the device merge kept of
        # the entries counted under `rows`); the last three are
        # execute.blocks' (blocks by the route of their min / max fold and by
        # where an event-time bin off the block's origin was computed)
        for k in (
            "shard", "lane", "cause", "qwait_us", "survivors",
            "fold_minmax_scatter_blocks", "timebin_offorigin_device_blocks", "timebin_offorigin_host_blocks",
        ):
            if k in attrs:
                row[k] = attrs[k]
        _SPAN_RING.append(row)
        SPAN_SINK.record(row)
        if not self.enabled:
            return
        span = {
            "traceId": trace_id,
            "spanId": span_id,
            "name": name,
            "kind": 1,  # SPAN_KIND_INTERNAL
            "startTimeUnixNano": str(start_ns),
            "endTimeUnixNano": str(end_ns),
            "attributes": [
                {"key": k, "value": {"stringValue": str(v)}} for k, v in attrs.items()
            ],
            "status": {"code": 2 if err else 1},
        }
        if parent_id:
            span["parentSpanId"] = parent_id
        with self._lock:
            self._spans.append(span)
            if len(self._spans) > MAX_BUFFER:
                del self._spans[: len(self._spans) - MAX_BUFFER]
            should_flush = len(self._spans) >= EXPORT_BATCH
        if should_flush:
            # export off the request path: a slow collector must never
            # add latency to the ingest/query that tipped the batch
            self._spawn_export()

    def _spawn_export(self) -> None:
        """Start the background exporter unless one is already in flight
        (it will pick up the freshly tipped batch when it reruns or on
        drain). The thread is tracked, never fire-and-forget: drain()
        joins it, so process shutdown cannot strand an export mid-POST."""
        with self._lock:
            t = self._export_thread
            if t is not None and t.is_alive():
                return
            t = threading.Thread(target=self.flush, name="otlp-export", daemon=True)
            self._export_thread = t
        t.start()

    def drain(self, timeout: float = 10.0) -> None:
        """Join the in-flight export (at most one) and synchronously flush
        whatever is still buffered. Shutdown hook — after this returns no
        exporter thread is running on this tracer's behalf."""
        with self._lock:
            t, self._export_thread = self._export_thread, None
        if t is not None and t.is_alive():
            t.join(timeout)
        self.flush()

    def flush(self) -> bool:
        """Export buffered spans (OTLP/HTTP JSON); failures drop the batch.
        Serialized so concurrent exports don't interleave."""
        if not self.enabled:
            return False
        with self._flush_inflight:
            return self._flush_locked()

    def _flush_locked(self) -> bool:
        import urllib.request

        with self._lock:
            batch, self._spans = self._spans, []
        if not batch:
            return True
        payload = {
            "resourceSpans": [
                {
                    "resource": {
                        "attributes": [
                            {
                                "key": "service.name",
                                "value": {"stringValue": self.service_name},
                            }
                        ]
                    },
                    "scopeSpans": [
                        {"scope": {"name": "parseable_tpu"}, "spans": batch}
                    ],
                }
            ]
        }
        try:
            req = urllib.request.Request(
                self.endpoint.rstrip("/") + "/v1/traces",
                data=json.dumps(payload).encode(),
                method="POST",
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status < 300
        except Exception as e:
            logger.debug("otlp export failed: %s", e)
            return False


def _rfc3339_ns(ns: int) -> str:
    from datetime import UTC, datetime

    return (
        datetime.fromtimestamp(ns / 1e9, UTC)
        .isoformat(timespec="milliseconds")
        .replace("+00:00", "Z")
    )


# ------------------------------------------------- cross-node trace stitching
# Pure functions over span ROWS (the ring/pmeta shape) — the cluster trace
# endpoint (server/cluster.py assemble_cluster_trace) gathers rows from every
# peer, skew-corrects their timestamps, and stitches ONE tree here.


def span_window(span: dict) -> tuple[float, float]:
    """(start_epoch_s, end_epoch_s) of a span row, from its RFC3339 `ts`
    and `duration_ms`."""
    from datetime import datetime

    ts = str(span.get("ts", ""))
    start = datetime.fromisoformat(ts.replace("Z", "+00:00")).timestamp()
    return start, start + float(span.get("duration_ms", 0.0)) / 1000.0


def shift_span_ts(span: dict, offset_s: float) -> dict:
    """Copy of `span` with `ts` shifted by offset_s (peer clock-skew
    correction; positive offset = the peer's clock runs ahead of ours,
    so its timestamps move back)."""
    if not offset_s:
        return dict(span)
    from datetime import UTC, datetime

    out = dict(span)
    ts = str(span.get("ts", ""))
    start = datetime.fromisoformat(ts.replace("Z", "+00:00")).timestamp()
    out["ts"] = (
        datetime.fromtimestamp(start - offset_s, UTC)
        .isoformat(timespec="milliseconds")
        .replace("+00:00", "Z")
    )
    return out


def build_span_tree(spans: list[dict]) -> tuple[list[dict], int]:
    """Stitch span rows into nested trees: each node is a copy with a
    `children` list (ordered by start time). Returns (roots, orphans) —
    an orphan is a span claiming a parent that is not in the set (it is
    promoted to a root so nothing is dropped, but counted: a fully
    propagated trace has zero orphans)."""
    by_id: dict[str, dict] = {}
    for s in spans:
        sid = s.get("span_id", "")
        if sid and sid not in by_id:  # dedupe (a span is recorded on one node)
            by_id[sid] = dict(s, children=[])
    roots: list[dict] = []
    orphans = 0
    for node in by_id.values():
        parent = node.get("parent_span_id") or ""
        if parent and parent in by_id:
            by_id[parent]["children"].append(node)
        else:
            if parent:
                orphans += 1
            roots.append(node)
    for node in by_id.values():
        node["children"].sort(key=span_window)
    roots.sort(key=span_window)
    return roots, orphans


def critical_path(roots: list[dict]) -> list[dict]:
    """Latest-finisher walk from the latest-ending root: at each level,
    descend into the child that finishes last (the one the parent actually
    waited for). `self_ms` is the slice of each span not covered by the
    next hop — where the wall-clock time was actually spent."""
    if not roots:
        return []
    node = max(roots, key=lambda s: span_window(s)[1])
    path: list[dict] = []
    while node is not None:
        nxt = max(node["children"], key=lambda s: span_window(s)[1]) if node["children"] else None
        dur = float(node.get("duration_ms", 0.0))
        self_ms = max(0.0, dur - float(nxt.get("duration_ms", 0.0))) if nxt else dur
        path.append(
            {
                "name": node.get("name", ""),
                "node": node.get("node", ""),
                "span_id": node.get("span_id", ""),
                "duration_ms": round(dur, 3),
                "self_ms": round(self_ms, 3),
            }
        )
        node = nxt
    return path


TRACER = Tracer()
