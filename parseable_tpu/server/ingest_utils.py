"""Ingest dispatch: flatten by log source and push into staging.

Parity target (reference: handlers/http/modal/utils/ingest_utils.rs):
`flatten_and_push_logs` dispatches on the `X-P-Log-Source` header —
otel-logs/metrics/traces use the OTel flatteners, kinesis decodes Firehose
records, plain JSON goes through generic (cross-product) flattening with the
depth guard — then `push_logs` chunks records per custom-partition value and
builds/processes events.
"""

from __future__ import annotations

import base64
import json
from typing import Any

from parseable_tpu.core import Parseable
from parseable_tpu.event.format import LogSource
from parseable_tpu.event.json_format import JsonEvent
from parseable_tpu.livetail import LIVETAIL
from parseable_tpu.otel import (
    flatten_otel_logs,
    flatten_otel_metrics,
    flatten_otel_traces,
)
from parseable_tpu.utils.flatten import (
    JsonFlattenError,
    flatten,
    generic_flattening,
    has_more_than_max_allowed_levels,
)


class IngestError(ValueError):
    pass


def decode_kinesis(payload: dict) -> list[dict[str, Any]]:
    """Kinesis Firehose message -> rows (reference: handlers/http/kinesis.rs).

    {"requestId": ..., "timestamp": ..., "records": [{"data": base64-json}]}
    """
    rows = []
    request_id = payload.get("requestId")
    timestamp = payload.get("timestamp")
    for rec in payload.get("records", []):
        try:
            data = base64.b64decode(rec.get("data", ""))
            obj = json.loads(data) if data.strip() else {}
        except (ValueError, json.JSONDecodeError) as e:
            raise IngestError(f"invalid kinesis record data: {e}") from e
        if not isinstance(obj, dict):
            obj = {"message": obj}
        obj.setdefault("requestId", request_id)
        obj.setdefault("timestamp", timestamp)
        rows.append(obj)
    return rows


def flatten_json_records(
    payload: Any,
    max_flatten_level: int,
    time_partition: str | None,
    time_partition_limit_days: int | None,
    custom_partition: str | None,
    max_chunk_age_hours: int,
) -> list[dict[str, Any]]:
    """Plain-JSON path: depth guard -> cross-product expansion -> flatten."""
    if has_more_than_max_allowed_levels(payload, max_flatten_level):
        raise IngestError(
            f"JSON is deeper than the allowed {max_flatten_level} levels"
        )
    expanded = generic_flattening(payload)
    rows: list[dict[str, Any]] = []
    validation = time_partition is not None or custom_partition is not None
    for item in expanded:
        try:
            flat = flatten(
                item,
                "_",
                time_partition,
                time_partition_limit_days,
                custom_partition,
                validation_required=validation,
                max_chunk_age_hours=max_chunk_age_hours,
            )
        except JsonFlattenError as e:
            raise IngestError(str(e)) from e
        if isinstance(flat, list):
            rows.extend(flat)
        else:
            rows.append(flat)
    return rows


def flatten_and_push_logs(
    p: Parseable,
    stream_name: str,
    payload: Any,
    log_source: LogSource,
    custom_fields: dict[str, str] | None = None,
    origin_size: int = 0,
    log_source_name: str | None = None,
    raw_body: bytes | None = None,
) -> int:
    """Parse+flatten by source, then push into staging. Returns row count.

    `log_source_name` carries the raw X-P-Log-Source value: names matching a
    known format (event/known_schema.py) get regex field extraction applied
    to each record's raw line (reference: KNOWN_SCHEMA_LIST
    extract_from_inline_log, ingest.rs:114-122).

    `raw_body` (the undecoded HTTP payload) enables the native ingest lane:
    C++ parse+flatten straight to NDJSON -> pyarrow JSON reader -> columnar
    batch, with Python dicts never materializing. `payload` may then be
    None — it parses lazily only if the native lane declines."""
    from parseable_tpu.utils.telemetry import TRACER

    with TRACER.span(
        "ingest", stream=stream_name, source=log_source.value, bytes=origin_size
    ) as sp:
        count = _flatten_and_push(
            p, stream_name, payload, log_source, custom_fields, origin_size,
            log_source_name, raw_body, sp=sp,
        )
        sp["rows"] = count
        return count


def _lane_result(sp, lane: str, result: str | None) -> None:
    """Record which ingest lane served a request: a per-request `lane` tag
    on the ingest span (self-ingested into pmeta, so fallback rates are
    queryable in production) plus the ingest_native{lane,result} counter —
    columnar-hit / ndjson-hit / declined (result is None for requests the
    native lanes never attempt, e.g. kinesis or partitioned streams)."""
    if sp is not None:
        sp["lane"] = lane
    if result is not None:
        from parseable_tpu.utils.metrics import INGEST_NATIVE

        INGEST_NATIVE.labels(lane, result).inc()


def _emit_native_telem(sp, enabled: bool) -> None:
    """Drain the calling thread's native telemetry ring and replay the
    events into the request's trace + metrics.

    The drain is unconditional — ctypes releases the GIL, so this thread
    IS the thread whose thread-local ring the C++ parse just filled, and
    draining here (hit or decline, enabled or not) guarantees no event
    leaks across requests when executor threads are reused. With
    telemetry disabled the drain returns empty for one cheap call.

    Each parse/stitch event becomes a real child span under the current
    request context (`TRACER.record_span` — the C++ side stamped wall ns,
    so timings are real, not re-measured) and an `ingest_stage_seconds`
    observation; >1 parse event also refreshes the shard-imbalance gauge
    (max/mean shard ns — the signal that one shard got a pathological
    slice)."""
    from parseable_tpu import native

    events = native.telem_drain()
    if not events or not enabled:
        return
    from parseable_tpu.utils.metrics import (
        INGEST_SHARD_IMBALANCE,
        INGEST_STAGE_TIME,
    )
    from parseable_tpu.utils.telemetry import TRACER

    parse_durs: list[int] = []
    for kind, shard, lane, rc, nbytes, rows, start_ns, dur_ns, qwait_ns in events:
        lane_name = (
            native.TELEM_LANES[lane]
            if lane < len(native.TELEM_LANES)
            else str(lane)
        )
        if kind == native.TELEM_EV_PARSE:
            name, stage = "native.parse", "parse"
            parse_durs.append(dur_ns)
        elif kind == native.TELEM_EV_RECV:
            # stamped by the C++ edge acceptor at claim time: socket-read
            # wall time for this request (the waterfall's true recv span)
            name, stage = "edge.recv", "recv"
        else:
            name, stage = "native.stitch", "stitch"
        attrs = {
            "shard": shard,
            "lane": lane_name,
            "cause": native.TELEM_CAUSES.get(rc, str(rc)),
            "bytes": nbytes,
            "rows": rows,
        }
        if qwait_ns:
            # pool queue wait: job-start minus submit (0 for the inline
            # shard) — the waterfall's "waiting, not working" component
            attrs["qwait_us"] = qwait_ns // 1000
        TRACER.record_span(name, start_ns, start_ns + dur_ns, **attrs)
        INGEST_STAGE_TIME.labels(stage, lane_name).observe(dur_ns / 1e9)
    if len(parse_durs) > 1:
        mean = sum(parse_durs) / len(parse_durs)
        if mean > 0:
            INGEST_SHARD_IMBALANCE.set(max(parse_durs) / mean)
    if sp is not None and parse_durs:
        sp["native_spans"] = len(parse_durs)


def _parse_payload(payload: Any, raw_body: bytes | None) -> Any:
    if payload is not None or raw_body is None:
        return payload
    if hasattr(raw_body, "tobytes"):
        # edge-path CBuf (borrowed C memory): the native lanes consumed it
        # zero-copy, but json.loads needs real bytes — copy only on this
        # decline tier
        raw_body = raw_body.tobytes()
    try:
        return json.loads(raw_body)
    except json.JSONDecodeError as e:
        raise IngestError(f"invalid JSON: {e}") from e


def ingest_native_fast(
    p: Parseable,
    stream_name: str,
    raw_body: bytes,
    log_source: LogSource,
    custom_fields: dict[str, str] | None,
    lane_out: dict | None = None,
) -> int | None:
    """Native ingest lane, two tiers (VERDICT r4 #7: the flatten hot loop
    was ~75% of ingest time; BENCH r04/r05: the NDJSON round trip then
    left us at 0.47x of the raw pyarrow floor because every byte parsed
    twice):

    1. COLUMNAR — fastpath.cpp accumulates typed Arrow-layout buffers
       (float64/bool/string+validity) during the ONE JSON parse; they
       import zero-copy and feed the shared fast-path normalization
       directly. No second tokenization anywhere.
    2. NDJSON — the previous lane (C++ flatten -> NDJSON -> pyarrow
       read_json) for shapes the builders can't represent exactly
       (escaped keys, int64-range strings, lone surrogates).

    Returns the row count, or None whenever ANY stage prefers the exact
    Python semantics (arrays, sparse/duplicate keys, depth, mixed types,
    partial timestamp parses, static/partitioned streams) — behavior is
    identical either way because every decline falls through. `lane_out`
    receives {"lane": "columnar"|"ndjson"} on a hit."""
    from parseable_tpu import native

    stream = p.get_stream(stream_name)
    meta = stream.metadata
    if not _native_lane_eligible(meta):
        return None
    # C++ depth N == python-level N+1 (scalars sit one level below the
    # deepest dict), so the native limit is max_flatten_level - 1 exactly
    depth = p.options.event_flatten_level - 1
    r = native.flatten_columnar(raw_body, depth)
    if r is not None:
        names, arrays, nrows = r
        if lane_out is not None:
            lane_out["lane"] = "columnar"
        if nrows == 0:
            return 0
        count = _columns_to_event(
            p, stream, names, arrays, len(raw_body), log_source, custom_fields
        )
        if count is not None:
            p.audit.record_native(stream_name, parsed=nrows, staged=count)
            return count
        # normalization declined (mixed semantics the reader-level facts
        # can't prove clean): the Python path is authoritative — the NDJSON
        # tier would assemble the same columns and decline identically
        p.audit.record_native(stream_name, parsed=nrows, declined=nrows)
        if lane_out is not None:
            del lane_out["lane"]
        return None
    r = native.flatten_ndjson(raw_body, depth)
    if r is None:
        return None
    ndjson, nrows = r
    if nrows == 0:
        if lane_out is not None:
            lane_out["lane"] = "ndjson"
        return 0
    count = _ndjson_to_event(
        p, stream, ndjson, len(raw_body), log_source, custom_fields
    )
    if count is not None:
        p.audit.record_native(stream_name, parsed=nrows, staged=count)
        if lane_out is not None:
            lane_out["lane"] = "ndjson"
    else:
        p.audit.record_native(stream_name, parsed=nrows, declined=nrows)
    return count


def _native_lane_eligible(meta) -> bool:
    from parseable_tpu.event.format import SchemaVersion

    return (
        meta.time_partition is None
        and meta.custom_partition is None
        and not meta.static_schema_flag
        and meta.schema_version == SchemaVersion.V1
    )


def _columns_to_event(
    p: Parseable,
    stream,
    names: list[str],
    arrays,
    origin_size: int,
    log_source: LogSource,
    custom_fields: dict[str, str] | None,
) -> int | None:
    """Columnar-tier tail: the natively-built Arrow arrays (imported
    zero-copy from the C++ builders) assemble straight into a table for
    the shared normalization — no JSON reader, no second parse anywhere."""
    import pyarrow as pa

    tbl = pa.Table.from_arrays(arrays, names=names)
    # direct: the arrays are single-chunk contiguous native buffers, so the
    # staged batch can stream straight into the bucket's IPC file
    return _table_to_event(
        p, stream, tbl, origin_size, log_source, custom_fields, direct=True
    )


def _ndjson_to_event(
    p: Parseable,
    stream,
    ndjson: bytes,
    origin_size: int,
    log_source: LogSource,
    custom_fields: dict[str, str] | None,
    cast_ts_ms: tuple[str, ...] = (),
) -> int | None:
    """NDJSON-tier tail: pyarrow's C++ JSON reader builds the columns from
    natively-flattened NDJSON. Returns None when the reader prefers the
    exact Python path."""
    import time

    import pyarrow as pa
    import pyarrow.json as pj

    from parseable_tpu.utils.metrics import INGEST_STAGE_TIME
    from parseable_tpu.utils.telemetry import TRACER

    # the NDJSON tier's real parse happens here (pyarrow's C++ reader),
    # above the telemetry ring — timed Python-side under the same
    # stage/lane naming so the waterfall stays complete on this tier
    t0 = time.time_ns()
    try:
        # BufferReader wraps the bytes zero-copy (BytesIO copies them)
        tbl = pj.read_json(pa.BufferReader(ndjson))
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
        return None  # reader-level type conflict: Python path decides
    t1 = time.time_ns()
    INGEST_STAGE_TIME.labels("parse", "ndjson").observe((t1 - t0) / 1e9)
    TRACER.record_span(
        "native.parse", t0, t1, lane="ndjson", shard=0,
        rows=tbl.num_rows, bytes=len(ndjson),
    )
    for name in cast_ts_ms:
        # the NDJSON OTel lane emits these as integer epoch-ms; the int64
        # -> timestamp(ms) cast is value-preserving and parse-free (the
        # columnar tier exports timestamp(ms) buffers directly instead)
        if name in tbl.column_names:
            col = tbl.column(name)
            if pa.types.is_integer(col.type):
                tbl = tbl.set_column(
                    tbl.column_names.index(name),
                    name,
                    col.cast(pa.int64()).cast(pa.timestamp("ms")),
                )
    return _table_to_event(p, stream, tbl, origin_size, log_source, custom_fields)


def _table_to_event(
    p: Parseable,
    stream,
    tbl,
    origin_size: int,
    log_source: LogSource,
    custom_fields: dict[str, str] | None,
    direct: bool = False,
) -> int | None:
    """Shared tail of both native tiers: the fast-path normalization types
    the columns, then the event processes through the unchanged schema
    commit + staging path. Returns None when the normalizer prefers the
    exact Python path."""
    from datetime import UTC, datetime

    from parseable_tpu.event import Event
    from parseable_tpu.event.format import fast_columns_from_table
    from parseable_tpu.utils.arrowutil import add_parseable_fields

    meta = stream.metadata
    if len(tbl.column_names) > p.options.dataset_fields_allowed_limit:
        raise IngestError(
            f"fields ({len(tbl.column_names)}) exceed dataset limit "
            f"({p.options.dataset_fields_allowed_limit})"
        )
    fast = fast_columns_from_table(tbl, meta.schema or None, meta.infer_timestamp)
    if fast is None:
        return None
    batch, _schema = fast
    batch = add_parseable_fields(batch, datetime.now(UTC), custom_fields or {})
    ev = Event(
        stream_name=stream.name,
        rb=batch,
        origin_format="json",
        origin_size=origin_size,
        is_first_event=not meta.schema,
        log_source=log_source,
        stream_type=meta.stream_type,
        direct_staging=direct,
    )
    ev.process(stream, livetail=LIVETAIL.process, commit_schema=p.commit_schema)
    if ev.stage_ns:
        from parseable_tpu.utils.metrics import INGEST_STAGE_TIME

        for stage, ns in ev.stage_ns.items():
            INGEST_STAGE_TIME.labels(stage, log_source.value).observe(ns / 1e9)
    return batch.num_rows


def ingest_otel_native_fast(
    p: Parseable,
    stream_name: str,
    raw_body: bytes,
    custom_fields: dict[str, str] | None,
    lane_out: dict | None = None,
) -> int | None:
    """Native OTel-logs lane, two tiers (VERDICT r4 #3: the protobuf-JSON
    structure walk kept OTel ingest ~14x behind the plain-JSON lane):

    1. COLUMNAR — fastpath.cpp walks resourceLogs/scopeLogs/logRecords
       once and lands the flattened rows in typed Arrow buffers, with the
       time fields built as timestamp(ms) columns directly (no RFC3339
       format + re-parse round trip, no NDJSON re-tokenization).
    2. NDJSON — the previous lane (C++ walk -> NDJSON -> pyarrow
       read_json) for shapes the builders decline (escaped attr keys,
       lone surrogates). Reference: src/otel/logs.rs:298.

    Returns the row count, or None whenever any stage prefers the exact
    Python flattener — behavior is identical because every decline falls
    through to flatten_otel_logs. `lane_out` receives the winning lane."""
    from parseable_tpu import native

    stream = p.get_stream(stream_name)
    meta = stream.metadata
    if not _native_lane_eligible(meta):
        return None
    # with timestamp inference on, the time columns stage as timestamp(ms)
    # either way — so the native walk skips the RFC3339 string entirely
    ts_as_ms = bool(meta.infer_timestamp)
    r = native.otel_logs_columnar(raw_body, ts_as_ms=ts_as_ms)
    if r is not None:
        names, arrays, nrows = r
        if lane_out is not None:
            lane_out["lane"] = "columnar"
        if nrows == 0:
            return 0
        count = _columns_to_event(
            p, stream, names, arrays, len(raw_body), LogSource.OTEL_LOGS,
            custom_fields,
        )
        if count is not None:
            p.audit.record_native(stream_name, parsed=nrows, staged=count)
            return count
        p.audit.record_native(stream_name, parsed=nrows, declined=nrows)
        if lane_out is not None:
            del lane_out["lane"]
        return None  # normalization declined: Python flattener decides
    r = native.otel_logs_ndjson(raw_body, ts_as_ms=ts_as_ms)
    if r is None:
        return None
    ndjson, nrows = r
    if nrows == 0:
        if lane_out is not None:
            lane_out["lane"] = "ndjson"
        return 0
    cast_ts = ("time_unix_nano", "observed_time_unix_nano") if ts_as_ms else ()
    count = _ndjson_to_event(
        p, stream, ndjson, len(raw_body), LogSource.OTEL_LOGS, custom_fields,
        cast_ts_ms=cast_ts,
    )
    if count is not None:
        p.audit.record_native(stream_name, parsed=nrows, staged=count)
        if lane_out is not None:
            lane_out["lane"] = "ndjson"
    else:
        p.audit.record_native(stream_name, parsed=nrows, declined=nrows)
    return count


def ingest_otel_columnar_fast(
    p: Parseable,
    stream_name: str,
    raw_body: bytes,
    custom_fields: dict[str, str] | None,
    columnar_fn,
    log_source: LogSource,
    lane_out: dict | None = None,
) -> int | None:
    """Native columnar lane for the OTel metrics and traces sources.

    Unlike logs there is no NDJSON middle tier: these flatteners are pure
    structure walks (one row per data point / span), so the C++ builder
    either lands the exact rows in typed Arrow buffers or declines to the
    Python flattener — `columnar_fn` is native.otel_metrics_columnar or
    native.otel_traces_columnar. Returns the row count or None (decline),
    with identical behavior either way."""
    stream = p.get_stream(stream_name)
    meta = stream.metadata
    if not _native_lane_eligible(meta):
        return None
    ts_as_ms = bool(meta.infer_timestamp)
    r = columnar_fn(raw_body, ts_as_ms=ts_as_ms)
    if r is None:
        return None
    names, arrays, nrows = r
    if lane_out is not None:
        lane_out["lane"] = "columnar"
    if nrows == 0:
        return 0
    count = _columns_to_event(
        p, stream, names, arrays, len(raw_body), log_source, custom_fields
    )
    if count is not None:
        p.audit.record_native(stream_name, parsed=nrows, staged=count)
        return count
    p.audit.record_native(stream_name, parsed=nrows, declined=nrows)
    if lane_out is not None:
        del lane_out["lane"]
    return None  # normalization declined: Python flattener decides


def _flatten_and_push(
    p: Parseable,
    stream_name: str,
    payload: Any,
    log_source: LogSource,
    custom_fields: dict[str, str] | None = None,
    origin_size: int = 0,
    log_source_name: str | None = None,
    raw_body: bytes | None = None,
    sp=None,
) -> int:
    stream = p.get_stream(stream_name)
    meta = stream.metadata

    plain_json = log_source == LogSource.JSON or (
        log_source == LogSource.CUSTOM and not log_source_name
    )
    if not plain_json and log_source == LogSource.CUSTOM and log_source_name:
        from parseable_tpu.event.known_schema import KNOWN_FORMATS

        plain_json = log_source_name not in KNOWN_FORMATS
    native_attempted = False
    if raw_body is not None and plain_json:
        from parseable_tpu import native

        native_attempted = True
        telem = native.telem_sync()
        info: dict = {}
        try:
            count = ingest_native_fast(
                p, stream_name, raw_body, log_source, custom_fields,
                lane_out=info,
            )
        finally:
            _emit_native_telem(sp, telem)
        if count is not None:
            _lane_result(sp, info.get("lane", "columnar"), "hit")
            return count
    if raw_body is not None and log_source == LogSource.OTEL_LOGS:
        from parseable_tpu import native

        native_attempted = True
        telem = native.telem_sync()
        info = {}
        try:
            count = ingest_otel_native_fast(
                p, stream_name, raw_body, custom_fields, lane_out=info
            )
        finally:
            _emit_native_telem(sp, telem)
        if count is not None:
            _lane_result(sp, info.get("lane", "columnar"), "hit")
            return count
    if raw_body is not None and log_source in (
        LogSource.OTEL_METRICS,
        LogSource.OTEL_TRACES,
    ):
        from parseable_tpu import native

        native_attempted = True
        telem = native.telem_sync()
        info = {}
        columnar_fn = (
            native.otel_metrics_columnar
            if log_source == LogSource.OTEL_METRICS
            else native.otel_traces_columnar
        )
        try:
            count = ingest_otel_columnar_fast(
                p, stream_name, raw_body, custom_fields, columnar_fn,
                log_source, lane_out=info,
            )
        finally:
            _emit_native_telem(sp, telem)
        if count is not None:
            _lane_result(sp, info.get("lane", "columnar"), "hit")
            return count
    _lane_result(sp, "python", "declined" if native_attempted else None)
    payload = _parse_payload(payload, raw_body)

    if log_source == LogSource.OTEL_LOGS:
        rows = flatten_otel_logs(payload)
    elif log_source == LogSource.OTEL_METRICS:
        rows = flatten_otel_metrics(payload)
    elif log_source == LogSource.OTEL_TRACES:
        rows = flatten_otel_traces(payload)
    elif log_source == LogSource.KINESIS:
        rows = decode_kinesis(payload)
    else:
        rows = flatten_json_records(
            payload,
            p.options.event_flatten_level,
            meta.time_partition,
            meta.time_partition_limit_days,
            meta.custom_partition,
            p.options.event_max_chunk_age,
        )
        if log_source == LogSource.CUSTOM and log_source_name:
            from parseable_tpu.event.known_schema import KNOWN_FORMATS, KNOWN_SCHEMA_LIST

            if log_source_name in KNOWN_FORMATS:
                rows = [
                    KNOWN_SCHEMA_LIST.check_or_extract(r, log_source_name) for r in rows
                ]
    if not rows:
        return 0
    field_count = len({k for r in rows for k in r})
    if field_count > p.options.dataset_fields_allowed_limit:
        raise IngestError(
            f"fields ({field_count}) exceed dataset limit "
            f"({p.options.dataset_fields_allowed_limit})"
        )
    return push_logs(p, stream_name, rows, log_source, custom_fields, origin_size)


def push_logs(
    p: Parseable,
    stream_name: str,
    rows: list[dict[str, Any]],
    log_source: LogSource,
    custom_fields: dict[str, str] | None = None,
    origin_size: int = 0,
) -> int:
    """Chunk rows by custom-partition value and process each chunk
    (reference: ingest_utils.rs:291)."""
    from parseable_tpu.utils.metrics import INGEST_STAGE_TIME

    stream = p.get_stream(stream_name)
    meta = stream.metadata
    chunks: list[list[dict]]
    if meta.custom_partition:
        first_key = meta.custom_partition.split(",")[0].strip()
        grouped: dict[Any, list[dict]] = {}
        for r in rows:
            grouped.setdefault(r.get(first_key), []).append(r)
        chunks = list(grouped.values())
    elif meta.time_partition:
        chunks = [[r] for r in rows]  # per-record parsed timestamps
    else:
        chunks = [rows]
    total = 0
    # origin_size pro-rated by chunk rows (cumulative rounding, so the
    # per-chunk sizes always sum to exactly the payload size): recording
    # the full size on one chunk and 0 on the rest under-counted stream
    # stats for every custom/time-partitioned ingest
    total_rows = len(rows) or 1
    seen_rows = 0
    allocated = 0
    for chunk in chunks:
        seen_rows += len(chunk)
        chunk_size = origin_size * seen_rows // total_rows - allocated
        allocated += chunk_size
        ev = JsonEvent(
            chunk,
            stream_name,
            origin_size=chunk_size,
            log_source=log_source,
            custom_fields=custom_fields or {},
        ).into_event(meta, stream.metadata.stream_type)
        ev.process(stream, livetail=LIVETAIL.process, commit_schema=p.commit_schema)
        for stage, ns in ev.stage_ns.items():
            INGEST_STAGE_TIME.labels(stage, "python").observe(ns / 1e9)
        total += ev.rb.num_rows
    return total
