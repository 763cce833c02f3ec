"""Arrow Flight gRPC data plane: zero-copy node-to-node columnar movement.

Parity target (reference: airplane.rs do_get + utils/arrow/flight.rs): the
reference moves querier<->ingestor columnar traffic over Arrow Flight gRPC
and keeps HTTP for the management plane. This build grows the same split as
a transport LADDER (the parse-ladder / edge-acceptor idiom): Flight is the
hot tier for the two internal data-plane calls, and ANY decline — peer
without a flight port in its discovery metadata, channel failure, auth or
ticket mismatch, mid-stream death — falls back to the existing
HTTP + Arrow IPC path byte-identically (cluster.py / query/fanout.py own
the client-side ladder).

DoGet tickets are JSON (documented in README "Cluster data plane"):

- ``{"kind": "staging", "stream", "start"?, "end"?, "fields"?}`` — the
  bounded staging window, mirroring ``GET /api/v1/internal/staging/{s}``:
  same ``staging_window_table`` helper the HTTP handler serializes, so the
  two tiers cannot drift.
- ``{"kind": "partial", "stream", "query", "startTime"?, "endTime"?}`` —
  the pushed-down partial aggregate, mirroring ``POST
  /api/v1/internal/query/partial/{s}``; the peer's accounting (owner tag,
  rows scanned, scan errors) rides as ``ptpu.*`` schema metadata instead
  of ``X-P-*`` response headers, stripped by the client before merging so
  the merged table is byte-identical to the HTTP tier's.

Auth + trace contract: the same Basic cluster credentials and W3C
``traceparent`` that ride HTTP headers arrive as gRPC call headers through
server middleware; handlers run inside the caller's trace context (spans
named ``flight.do_get``) so stitched cluster traces and the conservation
auditor keep working unchanged, and RBAC authorizes QUERY on the ticket's
stream exactly like the HTTP routes' ``@require`` decorator.
"""

from __future__ import annotations

import json
import logging
import threading

import pyarrow as pa
import pyarrow.flight as flight

from parseable_tpu.rbac import Action
from parseable_tpu.utils import telemetry

logger = logging.getLogger(__name__)

# partial-pushdown accounting rides as schema metadata on the streamed
# table (the Flight twin of fanout.py's X-P-* headers); the client strips
# exactly these keys so merged tables stay byte-identical across tiers
META_OWNER_TAG = b"ptpu.owner_tag"
META_ROWS = b"ptpu.rows_scanned"
META_ERRORS = b"ptpu.scan_errors"
META_EMPTY = b"ptpu.empty"
_META_KEYS = (META_OWNER_TAG, META_ROWS, META_ERRORS, META_EMPTY)


def strip_flight_meta(table: pa.Table) -> pa.Table:
    """Drop the ptpu.* accounting keys, preserving any metadata the table
    carried before the Flight hop (HTTP-tier parity)."""
    md = {
        k: v
        for k, v in (table.schema.metadata or {}).items()
        if k not in _META_KEYS
    }
    return table.replace_schema_metadata(md or None)


def _first_header(headers, name: str):
    """gRPC delivers headers as a lowercase-keyed mapping of lists; be
    liberal about both the casing and the list-ness."""
    for k, v in headers.items():
        if k.lower() == name:
            if isinstance(v, (list, tuple)):
                return v[0] if v else None
            return v
    return None


def _verify_basic(state, header) -> str | None:
    """Username for a valid Basic header, else None — the same credential
    funnel as app.py's auth_middleware (cached sha256 fast path, scrypt on
    a miss; Flight handlers run on gRPC worker threads, so the slow path
    never blocks an event loop)."""
    if not header:
        return None
    if isinstance(header, bytes):
        header = header.decode("latin-1")
    if not header.lower().startswith("basic "):
        return None
    import base64
    import binascii

    try:
        decoded = base64.b64decode(header.split(" ", 1)[1]).decode()
    except (binascii.Error, UnicodeDecodeError, ValueError):
        return None
    username, _, password = decoded.partition(":")
    user, decided = state.rbac.try_cached_authenticate(username, password)
    if not decided:
        user = state.rbac.authenticate(username, password)
    return username if user is not None else None


class _CallInfo(flight.ServerMiddleware):
    """Per-call identity + trace context captured by the factory."""

    def __init__(self, username: str, traceparent: str | None):
        self.username = username
        self.traceparent = traceparent


class _AuthMiddlewareFactory(flight.ServerMiddlewareFactory):
    """The gRPC twin of the HTTP tier's auth + trace middleware pair:
    reject bad cluster credentials before any handler runs, and carry the
    caller's W3C traceparent to the handler so its spans parent under the
    originating query's trace."""

    def __init__(self, state):
        self.state = state

    def start_call(self, info, headers):
        username = _verify_basic(self.state, _first_header(headers, "authorization"))
        if username is None:
            raise flight.FlightUnauthenticatedError("invalid cluster credentials")
        tp = _first_header(headers, "traceparent")
        if isinstance(tp, bytes):
            tp = tp.decode("latin-1")
        return _CallInfo(username, tp)


class FlightDataServer(flight.FlightServerBase):
    """DoGet server for the two internal data-plane calls, bound to
    ``grpc://{host}:{port}`` (port 0 = ephemeral, for tests). Arrow runs
    the handlers on its own C++ thread pool; ``start_background()`` parks
    ``serve()`` on one named Python thread with a deterministic ``stop()``
    joined by ``ServerState.stop`` (pool-lifecycle)."""

    def __init__(self, state, host: str, port: int):
        self.state = state
        self._thread: threading.Thread | None = None
        super().__init__(
            location=f"grpc://{host}:{port}",
            middleware={"ptpu-auth": _AuthMiddlewareFactory(state)},
        )

    # ------------------------------------------------------------ lifecycle

    def start_background(self) -> None:
        self._thread = threading.Thread(
            target=self.serve, name="flight-serve", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        thread, self._thread = self._thread, None
        self.shutdown()
        if thread is not None:
            thread.join(timeout=10)

    # ------------------------------------------------------------- handlers

    def do_get(self, context, ticket):
        call = context.get_middleware("ptpu-auth")
        try:
            req = json.loads(ticket.ticket.decode())
        except (ValueError, UnicodeDecodeError) as e:
            raise flight.FlightServerError(f"bad ticket: {e}") from e
        kind = req.get("kind")
        stream = str(req.get("stream") or "")
        if call is not None and not self.state.rbac.authorize(
            call.username, Action.QUERY, stream
        ):
            raise flight.FlightUnauthorizedError(
                f"user {call.username!r} may not query {stream!r}"
            )
        # the caller's traceparent rode the gRPC headers: run the handler
        # inside that context so the stitched cluster trace covers the hop
        with telemetry.trace_context(call.traceparent if call else None):
            with telemetry.TRACER.span(
                "flight.do_get", kind=str(kind), stream=stream
            ) as sp:
                if kind == "staging":
                    table = self._staging_table(req, stream)
                elif kind == "partial":
                    table = self._partial_table(req, stream)
                else:
                    raise flight.FlightServerError(f"unknown ticket kind {kind!r}")
                sp["rows"] = table.num_rows
                sp["bytes"] = table.nbytes
        # RecordBatchStream serializes straight from the table's Arrow
        # buffers in C++ — no BytesIO copy, no Python re-framing
        return flight.RecordBatchStream(table)

    def _staging_table(self, req: dict, name: str) -> pa.Table:
        """The bounded staging window — same helper as the HTTP handler, so
        both tiers serve identical rows. Empty window/unknown stream -> a
        zero-column table (the client maps it to the HTTP 204)."""
        from parseable_tpu.server.app import staging_window_table
        from parseable_tpu.utils.timeutil import TimeParseError, parse_rfc3339

        stream = self.state.p.streams.get(name)
        if stream is None:
            return pa.table({})
        try:
            start = parse_rfc3339(req["start"]) if req.get("start") else None
            end = parse_rfc3339(req["end"]) if req.get("end") else None
        except TimeParseError as e:
            raise flight.FlightServerError(f"bad time bound: {e}") from e
        fields = set(req["fields"]) if req.get("fields") is not None else None
        table = staging_window_table(stream, start, end, fields)
        return table if table is not None else pa.table({})

    def _partial_table(self, req: dict, name: str) -> pa.Table:
        """The pushed-down partial aggregate. Errors surface as Flight
        errors: the client treats any of them as a decline and retries the
        peer over HTTP, which classifies terminal (400: unsupported plan)
        vs retryable exactly as before — the ladder never invents a new
        classification of failures."""
        from parseable_tpu.query import fanout as FO

        sql = req.get("query")
        if not sql:
            raise flight.FlightServerError("missing 'query' in partial ticket")
        try:
            out = FO.execute_local_partial_table(
                self.state.p, name, sql, req.get("startTime"), req.get("endTime")
            )
        except FO.UnsupportedPartial as e:
            raise flight.FlightServerError(f"unsupported partial: {e}") from e
        except flight.FlightError:
            raise
        except Exception as e:
            logger.exception("flight partial pushdown failed")
            raise flight.FlightServerError(str(e)) from e
        meta = {"owner_tag": self.state.p.owner_tag, "rows_scanned": 0, "scan_errors": 0}
        table = None
        if out is not None:
            table, meta = out
        md = {
            META_OWNER_TAG: str(meta["owner_tag"]).encode(),
            META_ROWS: str(meta["rows_scanned"]).encode(),
            META_ERRORS: str(meta["scan_errors"]).encode(),
        }
        if table is None:
            # empty local slice / unknown stream: the HTTP tier's 204 with
            # accounting headers becomes an empty table with the marker key
            md[META_EMPTY] = b"1"
            table = pa.table({})
        full = dict(table.schema.metadata or {})
        full.update(md)
        return table.replace_schema_metadata(full)


def maybe_start_flight(state) -> FlightDataServer | None:
    """Start the Flight data plane for a serving process when configured:
    P_FLIGHT_PORT > 0 and an ingest-capable mode (the two DoGet calls serve
    node-local data, exactly like the HTTP internal routes registered only
    for ALL/INGEST). Returns None on any miss and zeroes the advertised
    port so ``register_node`` never publishes a plane this node won't
    serve — discovery metadata IS the client's ladder gate."""
    from parseable_tpu.config import Mode

    opts = state.p.options
    port = opts.flight_port
    if port <= 0:
        return None
    if opts.mode not in (Mode.ALL, Mode.INGEST):
        opts.flight_port = 0
        return None
    host, _, _ = opts.address.rpartition(":")
    host = host or "0.0.0.0"
    try:
        srv = FlightDataServer(state, host, port)
        srv.start_background()
    except Exception:
        logger.exception(
            "flight data plane failed to start on port %d; staying on HTTP", port
        )
        opts.flight_port = 0
        return None
    opts.flight_port = srv.port
    logger.info("flight data plane serving on grpc://%s:%d", host, srv.port)
    return srv
