"""Prometheus metrics registry.

Parity target: src/metrics/mod.rs:32-873 (~35 families). The same metric
names/labels are kept so dashboards scrape identically.
"""

from __future__ import annotations

from prometheus_client import (
    CONTENT_TYPE_LATEST,
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

METRICS_NAMESPACE = "parseable"

REGISTRY = CollectorRegistry()


def _gauge(name: str, doc: str, labels: list[str]) -> Gauge:
    return Gauge(name, doc, labels, namespace=METRICS_NAMESPACE, registry=REGISTRY)


def _counter(name: str, doc: str, labels: list[str]) -> Counter:
    return Counter(name, doc, labels, namespace=METRICS_NAMESPACE, registry=REGISTRY)


# --- ingest --------------------------------------------------------------
EVENTS_INGESTED = _gauge("events_ingested", "Events ingested", ["stream", "format"])
EVENTS_INGESTED_SIZE = _gauge("events_ingested_size", "Events ingested size bytes", ["stream", "format"])
LIFETIME_EVENTS_INGESTED = _gauge("lifetime_events_ingested", "Lifetime events ingested", ["stream", "format"])
LIFETIME_EVENTS_INGESTED_SIZE = _gauge(
    "lifetime_events_ingested_size", "Lifetime events ingested size", ["stream", "format"]
)
EVENTS_INGESTED_DATE = _gauge(
    "events_ingested_date", "Events ingested on date", ["stream", "format", "date"]
)
EVENTS_INGESTED_SIZE_DATE = _gauge(
    "events_ingested_size_date", "Events ingested size on date", ["stream", "format", "date"]
)
# native ingest lane outcomes (server/ingest_utils.py): which tier served
# each request — columnar (single-pass C++ -> Arrow buffers), ndjson
# (C++ flatten -> pyarrow reader), or python (both native tiers declined).
# A rising declined rate means production payloads stopped matching the
# builders' shape assumptions — the fast path silently became the slow one.
INGEST_NATIVE = _counter(
    "ingest_native",
    "Native ingest lane outcomes (lane: columnar/ndjson/python; "
    "result: hit/declined)",
    ["lane", "result"],
)
# ingest stage waterfall (server/ingest_utils.py + event/__init__.py):
# per-request stage timings recv -> parse[shard] -> stitch -> schema-commit
# -> stage-ipc, fed by the native telemetry ring for the C++ stages and by
# Python timers for the rest. Lane matches INGEST_NATIVE's label values.
INGEST_STAGE_TIME = Histogram(
    "ingest_stage_seconds",
    "Ingest stage waterfall timings (recv/parse/stitch/schema-commit/"
    "stage-ipc) per lane",
    ["stage", "lane"],
    namespace=METRICS_NAMESPACE,
    registry=REGISTRY,
)
# shard balance of the most recent sharded native parse: max/mean shard ns
# (1.0 = perfectly balanced; a high ratio means one shard serializes the
# whole parse and the pool buys nothing)
INGEST_SHARD_IMBALANCE = _gauge(
    "ingest_shard_imbalance",
    "max/mean shard parse ns of the last sharded native parse",
    [],
)
# staging IPC write modes (staging/writer.py DiskWriter): direct = native
# columnar buffers streamed straight into the bucket file, buffered =
# through the pending regroup, adapted = schema-mismatch copy. A falling
# direct share means the zero-copy lane quietly stopped engaging.
STAGING_WRITES = _counter(
    "staging_writes",
    "Staging IPC batch writes by path (mode: direct/buffered/adapted)",
    ["mode"],
)
# native parse pool health (scrape-time refresh in server/app.py
# metrics_handler, same pattern as the device gauges): live workers,
# queued-not-running jobs, and per-worker busy ratio over the scrape
# interval (busy-ns delta / wall delta)
NATIVE_POOL_SIZE = _gauge("native_pool_size", "Native parse pool live workers", [])
NATIVE_POOL_QUEUE_DEPTH = _gauge(
    "native_pool_queue_depth", "Native parse pool jobs queued, not yet running", []
)
NATIVE_POOL_BUSY_RATIO = _gauge(
    "native_pool_busy_ratio",
    "Per-worker busy fraction since the previous /metrics scrape",
    ["worker"],
)
# telemetry ring overflow (cumulative, read from the native side at scrape
# time): nonzero means some requests' native spans were dropped rather
# than blocking their parse
NATIVE_TELEM_DROPS = _gauge(
    "native_telem_dropped_events",
    "Native telemetry events dropped on ring overflow (cumulative)",
    [],
)

# --- storage -------------------------------------------------------------
STORAGE_SIZE = _gauge("storage_size", "Storage size bytes", ["type", "stream", "format"])
EVENTS_DELETED = _gauge("events_deleted", "Events deleted", ["stream", "format"])
EVENTS_DELETED_SIZE = _gauge("events_deleted_size", "Events deleted size", ["stream", "format"])
DELETED_EVENTS_STORAGE_SIZE = _gauge(
    "deleted_events_storage_size", "Deleted events storage size", ["type", "stream", "format"]
)
LIFETIME_EVENTS_STORAGE_SIZE = _gauge(
    "lifetime_events_storage_size", "Lifetime events storage size", ["type", "stream", "format"]
)
EVENTS_STORAGE_SIZE_DATE = _gauge(
    "events_storage_size_date", "Parquet storage size on date", ["type", "stream", "format", "date"]
)
STAGING_FILES = _gauge("staging_files", "Staging files count", ["stream"])
# write-path health (core.py sync cycle): age of the oldest staged parquet
# not yet uploaded when the cycle sized its batch — a growing lag means the
# uploader is falling behind ingest — and enrichment tasks (enccache seed +
# field stats) queued behind the upload critical path
SYNC_LAG_SECONDS = _gauge(
    "sync_lag_seconds", "Oldest unuploaded staged parquet age (seconds)", ["stream"]
)
ENRICH_QUEUE_DEPTH = _gauge(
    "enrichment_queue_depth", "Post-upload enrichment tasks waiting", []
)

# --- query ---------------------------------------------------------------
QUERY_EXECUTE_TIME = Histogram(
    "query_execute_time",
    "Query execute time seconds",
    ["stream"],
    namespace=METRICS_NAMESPACE,
    registry=REGISTRY,
)
QUERY_CACHE_HIT = _counter("query_cache_hit", "Query cache hits", ["stream"])
# concurrent query serving (admission control + shared scan scheduler +
# plan/result caches): in-flight/queued gauges and the shed counter must
# reconcile (inflight <= max_concurrent, queued <= queue_depth, everything
# past that sheds 503); sched-wait is the per-task queue time between a
# scan task's enqueue and its dispatch on the shared pool
QUERY_INFLIGHT = _gauge("query_inflight", "Queries currently executing", [])
QUERY_QUEUED = _gauge("query_queued", "Queries waiting for an admission slot", [])
QUERY_SHED = _counter(
    "query_shed", "Queries shed by admission control", ["reason"]
)
QUERY_SCAN_SCHED_WAIT = Histogram(
    "query_scan_sched_wait_seconds",
    "Scan task wait between enqueue and dispatch on the shared scan pool",
    [],
    namespace=METRICS_NAMESPACE,
    registry=REGISTRY,
)
QUERY_PLAN_CACHE = _counter(
    "query_plan_cache", "Plan/parse cache lookups", ["result"]
)
QUERY_RESULT_CACHE = _counter(
    "query_result_cache", "Partial-aggregate result cache lookups", ["result"]
)
QUERY_RESULT_CACHE_BYTES = _gauge(
    "query_result_cache_bytes", "Bytes held by the partial-aggregate result cache", []
)
TOTAL_QUERY_BYTES_SCANNED_DATE = _gauge(
    "total_query_bytes_scanned_date", "Bytes scanned by queries on date", ["date"]
)
# parallel scan pipeline (query/provider.py): decoded tables waiting between
# the fetch+decode pool and the consumer, per-file read failures that dropped
# a file from the results (partial-result detector), and bytes the projected
# column-chunk range reads did NOT download vs whole-object GETs
SCAN_POOL_QUEUE_DEPTH = _gauge(
    "query_scan_pool_queue_depth", "Decoded tables queued ahead of the consumer", []
)
SCAN_ERRORS = _counter(
    "query_scan_errors", "Files dropped from a scan by read/decode failures", ["stream"]
)
SCAN_PROJECTION_BYTES_SAVED = _counter(
    "query_scan_projection_bytes_saved",
    "Bytes not fetched thanks to projected column-chunk range reads",
    ["stream"],
)
DEVICE_EXECUTE_TIME = Histogram(
    "tpu_execute_time",
    "Host wall seconds of one TPU-engine operator from its first block to "
    "its merged result: scan waits, dispatch, device waits, readbacks and "
    "the host merge (not device kernel time)",
    ["op"],
    namespace=METRICS_NAMESPACE,
    registry=REGISTRY,
)
# where those seconds go: the executor's phase clock (executor_tpu.PHASES),
# added once per query from its finished route_stats
DEVICE_PHASE_SECONDS = _counter(
    "tpu_execute_phase_seconds",
    "Host seconds the TPU executor spent in each phase of a query "
    "(encode, prepare, dispatch, device_wait, readback, partial, merge, finalize)",
    ["phase"],
)
DEVICE_BYTES_TO_DEVICE = _counter("tpu_bytes_to_device", "Bytes shipped host->device", ["op"])
# a block-local (high-cardinality) GROUP BY's cross-block merge: "device" when
# jit_executor_merge ranked the partials and the host merged only the
# survivors of a top-K, "host" when every partial row went through the host
DEVICE_MERGES = _counter(
    "tpu_device_merges",
    "Block-local GROUP BY merges by where the cross-block merge ran",
    ["path"],
)
# aggregates whose argument is an arithmetic expression (sum(price * (1 -
# discount))): folded inside the device program ("device") or evaluated by
# the CPU engine ("host": a plan-time rejection, or a block it folded)
DEVICE_EXPR_AGGREGATES = _counter(
    "tpu_expr_aggregates",
    "Aggregate outputs over an arithmetic expression by where the expression was evaluated",
    ["path"],
)
# blocks whose text bins (date_bin / date_trunc) a time column off the
# block's origin (an event time backfilled years from its ingest minute):
# binned inside the device program ("device"), or by host code ("host": a
# block the CPU engine folded, a bin the column's unit does not divide among
# them)
DEVICE_TIMEBIN_OFFORIGIN = _counter(
    "tpu_timebin_offorigin",
    "Blocks that bin a time column off the block's origin, by where the bin was computed",
    ["path"],
)
for _path in ("device", "host"):
    DEVICE_TIMEBIN_OFFORIGIN.labels(_path)  # a scrape reads 0, not nothing
# a column ops/device.py could not hold on the device, so every query that
# names it takes the CPU engine for that block: time_span (a timestamp
# column too wide for int32 in any whole unit), sub_ms, nested, other
ENCODE_DECLINED = _counter(
    "tpu_encode_declined",
    "Columns the device encoder declined, by reason",
    ["reason"],
)
for _reason in ("time_span", "sub_ms", "nested", "other"):
    ENCODE_DECLINED.labels(_reason)  # a scrape reads 0, not nothing, before the first decline
# JAX accelerator health next to the execute-time histogram: live HBM usage
# per local device (scrape-time collection, ops/device.py) and XLA programs
# compiled (a jit cache miss costs seconds — compile churn must be visible
# on a dashboard)
DEVICE_MEMORY_IN_USE = _gauge(
    "tpu_device_memory_in_use", "Accelerator memory in use (bytes)", ["device"]
)
DEVICE_MEMORY_PEAK = _gauge(
    "tpu_device_memory_peak", "Accelerator memory high-water mark (bytes)", ["device"]
)
DEVICE_JIT_PROGRAMS = _gauge(
    "tpu_jit_programs", "XLA programs compiled (jit cache misses)", []
)
DEVICE_RECOMPILES = _counter(
    "tpu_recompiles",
    "XLA program builds for a program-cache key that was already built once "
    "(0 in steady state; the dlint tripwire budgets these per shape class)",
    ["program"],
)
# --- tiering under memory pressure (ops/hotset.py, ops/enccache.py) ------
# first-class hot-set state: what's resident, how hard eviction is working,
# and entries rejected for exceeding the whole budget (previously a silent
# return). The enccache write-behind queue degrades deterministically under
# sustained ingest: depth gauge + a drop counter that must stay 0 in steady
# state. Prefetch results: shipped (background encode+ship done), hit
# (consumed by the query), wasted (shipped but never consumed before close).
HOTSET_RESIDENT_BYTES = _gauge(
    "tpu_hotset_resident_bytes", "Bytes of encoded blocks resident in the device hot set", []
)
HOTSET_EVICTIONS = _counter(
    "tpu_hotset_evictions", "Hot-set entries evicted under budget pressure", []
)
HOTSET_REJECTED_OVERSIZE = _counter(
    "tpu_hotset_rejected_oversize", "Hot-set puts rejected for exceeding the whole budget", []
)
ENCCACHE_QUEUE_DEPTH = _gauge(
    "tpu_enccache_queue_depth", "Write-behind encodes queued for the enccache writer", []
)
ENCCACHE_DROPS = _counter(
    "tpu_enccache_dropped_writes",
    "Write-behind enccache seeds dropped after the bounded backpressure wait",
    [],
)
PREFETCH_EVENTS = _counter(
    "tpu_prefetch", "Query-aware prefetch outcomes", ["result"]
)

# --- distributed query fan-out (server/cluster.py, query/fanout.py) ------
# fan-in = querier pulling raw staging windows over Arrow IPC (central
# pull); fan-out = querier scattering partial-aggregate pushdown requests.
# Peer label cardinality is bounded by cluster size. fanin_errors was the
# counted-swallow gap: staging fetch failures were logged but invisible to
# operators, so a flapping ingestor silently produced partial results.
CLUSTER_FANIN_ERRORS = _counter(
    "cluster_fanin_errors", "Staging fan-in fetch failures", ["peer"]
)
CLUSTER_FANIN_BYTES = _counter(
    "cluster_fanin_bytes", "Raw staging bytes pulled over the cluster data plane", ["peer"]
)
CLUSTER_FANOUT_REQUESTS = _counter(
    "cluster_fanout_requests",
    "Partial-aggregate pushdown requests by outcome (ok/error/timeout/"
    "fallback/hedged/retried/discarded)",
    ["peer", "result"],
)
CLUSTER_FANOUT_BYTES = _counter(
    "cluster_fanout_bytes", "Partial-aggregate result bytes received", ["peer"]
)
CLUSTER_FANOUT_LATENCY = Histogram(
    "cluster_fanout_seconds",
    "Per-peer partial-aggregate pushdown round-trip latency",
    ["peer"],
    namespace=METRICS_NAMESPACE,
    registry=REGISTRY,
)

# conservation-law auditor (parseable_tpu/audit.py): each detected
# invariant breach ticks once, labeled by invariant name (rows_conserved /
# snapshot_monotonic / gauges_zero / queryable_count /
# native_rows_conserved) — the soak battery's "did we lose or
# double-count rows" alarm
AUDIT_VIOLATIONS = _counter(
    "audit_violations",
    "Conservation-law audit violations by invariant",
    ["invariant"],
)

# errors a storage backend deliberately recovers from (credential-probe
# fallbacks, best-effort session cancels): recoverable by design, but a
# nonzero rate is the early signal of a flapping metadata server or a
# misbehaving endpoint — plint's silent-swallow rule requires every such
# handler to log and tick this
STORAGE_SWALLOWED_ERRORS = _counter(
    "storage_swallowed_errors",
    "Errors swallowed by deliberate storage-backend fallbacks",
    ["backend", "op"],
)

# --- storage layer calls (reference: storage/metrics_layer.rs) ----------
STORAGE_REQUEST_TIME = Histogram(
    "storage_request_response_time",
    "Storage request latency",
    ["backend", "method"],
    namespace=METRICS_NAMESPACE,
    registry=REGISTRY,
)

# --- hot tier ------------------------------------------------------------
HOT_TIER_DOWNLOAD_BYTES = _counter("hot_tier_download_bytes", "Hot tier bytes downloaded", ["stream"])
HOT_TIER_SIZE = _gauge("hot_tier_size", "Hot tier size bytes", ["stream"])

# --- alerts --------------------------------------------------------------
ALERTS_STATES = _counter("alerts_states", "Alert state transitions", ["name", "state"])

# --- kafka connector (reference: connectors/kafka/metrics.rs) -------------
KAFKA_RECORDS_CONSUMED = _counter(
    "kafka_records_consumed", "Kafka records consumed", ["topic"]
)
KAFKA_FLUSHED_ROWS = _counter(
    "kafka_flushed_rows", "Kafka rows flushed into staging", ["topic"]
)
KAFKA_STAT = _gauge(
    "kafka_stat",
    "librdkafka top-level statistic (stats_cb bridge)",
    ["client_id", "stat"],
)
KAFKA_BROKER_STAT = _gauge(
    "kafka_broker_stat",
    "librdkafka per-broker statistic (stats_cb bridge)",
    ["client_id", "broker", "stat"],
)
KAFKA_PARTITION_STAT = _gauge(
    "kafka_partition_stat",
    "librdkafka per-topic-partition statistic (stats_cb bridge)",
    ["client_id", "topic", "partition", "stat"],
)
KAFKA_REBALANCES = _counter(
    "kafka_rebalances", "Kafka consumer group rebalances", ["group"]
)


def render() -> bytes:
    return generate_latest(REGISTRY)
