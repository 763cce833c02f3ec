"""Scan provider: union of staging + hot tier + object-store parquet.

Parity target (reference: src/query/stream_schema_provider.rs:533-666 scan).
The scan resolves, in order:

1. **staging** — recent in-memory/disk arrows on this node, included when the
   query range touches the staging window (last ~LOCAL_SYNC_INTERVAL secs);
2. **hot tier** — parquet files already cached on local NVMe;
3. **object store** — manifest-pruned parquet (time overlap + column min/max
   stats), downloaded through the storage client.

Returns pyarrow Tables column-pruned to what the plan needs. All sources are
adapted to the merged stream schema so mixed-schema files union cleanly.

Object-store files flow through a shared parallel pipeline (the reference
gets the equivalent from DataFusion's ParquetExec): a bounded worker pool
(P_SCAN_WORKERS) fetches+decodes manifest files concurrently — Arrow's
parquet decode releases the GIL and object-store GETs are network-bound —
and yields tables to the consumer as they complete, holding at most
P_SCAN_INFLIGHT_BYTES of decoded data ahead of it. Closing the consumer
(LIMIT satisfied, timeout, error) cancels queued work and drains the pool:
no leaked threads, no storage calls issued after close.

On top of the pool, **projected column-chunk range reads**: for remote files
on a backend with a real ranged GET, the footer is read via a tail
`get_range` and only the byte ranges of the column chunks the plan projects
are fetched (adjacent ranges coalesced), instead of the whole object. The
whole-object GET remains for hot-tier files, `SELECT *`, backends whose
`get_range` is the whole-object default, and projections that cover most of
the file anyway.
"""

from __future__ import annotations

import contextvars
import io
import logging
import queue as _queue
import struct
import threading
import time as _time
from collections import deque
from dataclasses import dataclass, field
from datetime import UTC, datetime, timedelta
from pathlib import Path
from typing import Callable, Iterator

import pyarrow as pa
import pyarrow.parquet as pq

from parseable_tpu import DEFAULT_TIMESTAMP_KEY, LOCAL_SYNC_INTERVAL
from parseable_tpu.catalog import ManifestFile, Snapshot
from parseable_tpu.core import Parseable
from parseable_tpu.query.planner import LogicalPlan, prune_file
from parseable_tpu.utils.metrics import (
    QUERY_SCAN_SCHED_WAIT,
    SCAN_ERRORS,
    SCAN_POOL_QUEUE_DEPTH,
    SCAN_PROJECTION_BYTES_SAVED,
    TOTAL_QUERY_BYTES_SCANNED_DATE,
)

logger = logging.getLogger(__name__)

_PARQUET_MAGIC = b"PAR1"


@dataclass
class ScanStats:
    files_total: int = 0
    files_pruned: int = 0
    bytes_scanned: int = 0
    rows_scanned: int = 0
    staging_batches: int = 0
    # files dropped from the result set by read/decode failures — nonzero
    # means the response is PARTIAL (surfaced in stats + a Prometheus counter)
    scan_errors: int = 0
    # bytes the projected range reads did not download vs whole-object GETs
    bytes_saved_by_projection: int = 0
    range_read_files: int = 0
    # cumulative time this query's scan tasks waited for a shared-pool
    # worker (enqueue -> dispatch): THE cross-query contention signal
    sched_wait_seconds: float = 0.0
    # distributed data plane: raw staging bytes pulled from peers over
    # Arrow IPC (central pull / pushdown fallback) + failed peer fetches
    fanin_bytes: int = 0
    fanin_errors: int = 0
    # transport-ladder breakdown of the fan-in (http_bytes / flight_bytes /
    # flight_peers / flight_fallbacks), merged from cluster.py's stats dict
    fanin_transport: dict = field(default_factory=dict)
    # manifest files skipped because a live peer's pushdown scan owns them
    # (they are NOT pruned — another node is scanning them)
    files_delegated: int = 0


# --------------------------------------------------------------------------
# shared scan scheduler: per-query lanes, weighted round-robin dispatch


class ScanLane:
    """One query's slice of the shared scan pool.

    Holds the query's undispatched tasks, its in-flight byte budget, and
    the completion queue its consumer drains. All dispatch-side state is
    guarded by the owning scheduler's lock (dispatch decisions must see a
    consistent cross-lane picture); the results queue is its own sync."""

    def __init__(self, sched: "ScanScheduler", inflight_bytes: int, weight: int,
                 on_wait: Callable[[float], None] | None = None):
        self._sched = sched
        self.cap = max(1, inflight_bytes)
        self.weight = max(1, weight)
        self.credits = self.weight  # guarded-by: sched._cond
        self.tasks: "deque" = deque()  # guarded-by: sched._cond
        self.used = 0  # guarded-by: sched._cond - decoded bytes in flight
        self.running = 0  # guarded-by: sched._cond - tasks mid-execution
        self.closed = False  # guarded-by: sched._cond
        self.cancelled = threading.Event()
        self.results: _queue.Queue = _queue.Queue()
        self.on_wait = on_wait  # per-query sched-wait accounting (stats)

    def submit(self, fn: Callable[[], None], est: int) -> None:
        self._sched._submit(self, fn, min(max(1, est), self.cap))

    def release_bytes(self, est: int) -> None:
        """Consumer took a decoded table: free its budget, wake dispatch."""
        self._sched._release_bytes(self, min(max(1, est), self.cap))

    def close(self) -> None:
        """Drop undispatched tasks and wait for this lane's running tasks
        to finish — after close() returns, no storage call runs or will
        ever run on this lane's behalf."""
        self.cancelled.set()
        self._sched._close_lane(self)


class ScanScheduler:
    """Shared fetch+decode worker pool with per-query fairness.

    Replaces the per-query ThreadPoolExecutor + global FIFO contention: one
    process-wide set of P_SCAN_WORKERS threads serves every concurrent
    query through per-query *lanes*. Dispatch policy:

    - "fair" (default): weighted round-robin across lanes with queued work.
      Each lane spends `weight` credits per round, so a 10k-file scan and a
      3-file dashboard query alternate dispatches instead of the big scan
      occupying every worker until its backlog drains.
    - "fifo": strict global arrival order — the pre-scheduler behavior,
      kept for A/B measurement.

    A lane's task is only dispatched when its own inflight-byte budget has
    room, so a slow consumer parks its *lane*, never a worker thread.
    Queue-wait (enqueue -> dispatch) lands in the
    query_scan_sched_wait_seconds histogram and per-query ScanStats.
    """

    def __init__(self, workers: int, policy: str = "fair"):
        self.workers = max(1, workers)
        self.policy = policy if policy in ("fair", "fifo") else "fair"
        self._cond = threading.Condition()
        self._lanes: list[ScanLane] = []  # guarded-by: self._cond
        self._rr = 0  # guarded-by: self._cond - round-robin cursor
        self._seq = 0  # guarded-by: self._cond - global arrival order
        self._pending = 0  # guarded-by: self._cond - undispatched tasks
        self._stopped = False  # guarded-by: self._cond
        # NOT "scan-" prefixed: these are shared infrastructure threads that
        # outlive any one scan (per-scan thread-leak checks key on "scan*")
        self._threads = [
            threading.Thread(target=self._worker, name=f"qsched-{i}", daemon=True)
            for i in range(self.workers)
        ]
        for t in self._threads:
            t.start()

    # ---------------------------------------------------------------- lanes

    def lane(self, *, inflight_bytes: int, weight: int = 1,
             on_wait: Callable[[float], None] | None = None) -> ScanLane:
        ln = ScanLane(self, inflight_bytes, weight, on_wait)
        with self._cond:
            if self._stopped:
                raise RuntimeError("scan scheduler is stopped")
            self._lanes.append(ln)
        return ln

    def _submit(self, lane: ScanLane, fn: Callable[[], None], est: int) -> None:
        with self._cond:
            if lane.closed or self._stopped:
                # complete immediately: the task fn observes the cancelled
                # flag and posts its skip record, so consumers never hang
                lane.cancelled.set()
                fn()
                return
            lane.tasks.append((fn, est, self._seq, _time.monotonic()))
            self._seq += 1
            self._pending += 1
            SCAN_POOL_QUEUE_DEPTH.set(self._pending)
            self._cond.notify()

    def _release_bytes(self, lane: ScanLane, est: int) -> None:
        with self._cond:
            lane.used = max(0, lane.used - est)
            self._cond.notify_all()

    def _close_lane(self, lane: ScanLane) -> None:
        with self._cond:
            if lane.closed:
                return
            lane.closed = True
            self._pending -= len(lane.tasks)
            lane.tasks.clear()
            SCAN_POOL_QUEUE_DEPTH.set(self._pending)
            # synchronous drain: tasks already mid-fetch finish and their
            # results are dropped; nothing queued ever touches storage
            while lane.running:
                self._cond.wait()
            if lane in self._lanes:
                self._lanes.remove(lane)

    # ------------------------------------------------------------- dispatch

    def _fits(self, lane: ScanLane) -> bool:
        est = lane.tasks[0][1]
        # an item larger than the whole cap admits alone (the cap bounds
        # concurrent holdings, never deadlocks)
        return lane.used == 0 or lane.used + est <= lane.cap

    def _worker(self) -> None:
        while True:
            with self._cond:
                # wait until some lane has a dispatchable head task (queued
                # work whose inflight budget has room)
                while True:
                    if self._stopped:
                        return
                    eligible = [
                        ln for ln in self._lanes if ln.tasks and self._fits(ln)
                    ]
                    if eligible:
                        break
                    self._cond.wait()
                if self.policy == "fifo":
                    lane = min(eligible, key=lambda ln: ln.tasks[0][2])
                else:
                    lane = None
                    n = len(self._lanes)
                    for _pass in range(2):
                        for off in range(n):
                            cand = self._lanes[(self._rr + off) % n]
                            if cand.tasks and cand.credits > 0 and self._fits(cand):
                                lane = cand
                                self._rr = (self._rr + off + 1) % max(1, n)
                                cand.credits -= 1
                                break
                        if lane is not None:
                            break
                        # every eligible lane spent its credits: new round
                        for ln in self._lanes:
                            ln.credits = ln.weight
                    if lane is None:  # pragma: no cover - eligible non-empty
                        lane = eligible[0]
                fn, est, _seq, enq = lane.tasks.popleft()
                lane.used += est
                lane.running += 1
                self._pending -= 1
                SCAN_POOL_QUEUE_DEPTH.set(self._pending)
            wait = max(0.0, _time.monotonic() - enq)
            QUERY_SCAN_SCHED_WAIT.observe(wait)
            if lane.on_wait is not None:
                try:
                    lane.on_wait(wait)
                except Exception:  # pragma: no cover - stats cb must not kill
                    logger.exception("scan sched wait callback failed")
            try:
                fn()
            finally:
                with self._cond:
                    lane.running -= 1
                    self._cond.notify_all()

    def shutdown(self) -> None:
        """Stop the workers and error-complete whatever was still queued so
        no consumer hangs. Deterministic: joins every thread."""
        with self._cond:
            if self._stopped:
                return
            self._stopped = True
            self._cond.notify_all()
            leftovers = [(ln, list(ln.tasks)) for ln in self._lanes]
            for ln in self._lanes:
                ln.cancelled.set()
                ln.tasks.clear()
            self._pending = 0
            SCAN_POOL_QUEUE_DEPTH.set(0)
        for t in self._threads:
            t.join()
        for ln, tasks in leftovers:
            for fn, _est, _seq, _enq in tasks:
                fn()  # cancelled flag set: posts the skip record


_SCHED: ScanScheduler | None = None
_SCHED_LOCK = threading.Lock()


def get_scan_scheduler(options=None) -> ScanScheduler:
    """Process-wide scheduler, sized by P_SCAN_WORKERS / P_SCAN_SCHED.
    Re-roots (shutdown + rebuild) when the configuration changes — tests
    and the A/B bench flip policy between phases with no scans in flight."""
    global _SCHED
    import os as _os

    workers = max(1, getattr(options, "scan_workers", 0) or min(8, _os.cpu_count() or 1))
    policy = getattr(options, "scan_sched", "fair") or "fair"
    with _SCHED_LOCK:
        if _SCHED is not None and (_SCHED.workers != workers or _SCHED.policy != policy):
            old, _SCHED = _SCHED, None
            old.shutdown()
        if _SCHED is None:
            _SCHED = ScanScheduler(workers, policy)
        return _SCHED


def shutdown_scan_scheduler() -> None:
    global _SCHED
    with _SCHED_LOCK:
        if _SCHED is not None:
            _SCHED.shutdown()
            _SCHED = None


def lane_iter(
    lane: ScanLane,
    items: list,
    fetch: Callable,
    size_of: Callable[[object], int],
):
    """Run `fetch(item)` for every item through the lane's scheduler,
    yielding `(item, result)` pairs **as they complete** (completion order,
    not submission order — the engines merge blocks orderlessly, and
    head-of-line blocking would idle the device behind one slow GET).

    Contract (the scan pool's cancellation guarantees, unchanged from the
    per-query pool it replaced):
    - closing the generator cancels not-yet-dispatched tasks, so no storage
      call is issued after close; tasks already mid-fetch finish and their
      results are dropped; the drain is synchronous;
    - in-flight decoded bytes are bounded by the lane's budget (estimated
      by `size_of`); the trace context at submission is carried into every
      worker so per-file spans parent correctly.

    `fetch` errors propagate to the consumer (expected per-file read errors
    are already converted to `None` results by the caller's fetch fn).
    """
    for item in items:
        est = max(1, size_of(item))
        # each task enters its own copy of the submitter's context so spans
        # recorded during fetch/decode join the query's trace
        ctx = contextvars.copy_context()

        def task(item=item, est=est, ctx=ctx):
            # every code path MUST put exactly one record or the consumer hangs
            if lane.cancelled.is_set():
                lane.results.put((item, None, None, est))
                return
            try:
                out = ctx.run(fetch, item)
            except BaseException as e:  # noqa: BLE001 - re-raised in the consumer
                lane.results.put((item, None, e, est))
                return
            lane.results.put((item, out, None, est))

        lane.submit(task, est)

    received = 0
    try:
        while received < len(items):
            item, out, err, est = lane.results.get()
            received += 1
            lane.release_bytes(est)
            if err is not None:
                raise err
            if out is not None:
                yield item, out
    finally:
        lane.close()


def scan_pool_iter(
    items: list,
    fetch: Callable,
    *,
    workers: int,
    inflight_bytes: int,
    size_of: Callable[[object], int],
):
    """Single-query pool over a throwaway scheduler (compat shim for
    callers that want an isolated pool; production scans share the global
    scheduler via get_scan_scheduler + lane_iter). Threads are joined when
    the generator finishes or is closed."""
    sched = ScanScheduler(max(1, workers), "fair")
    lane = sched.lane(inflight_bytes=inflight_bytes)
    try:
        yield from lane_iter(lane, items, fetch, size_of)
    finally:
        sched.shutdown()


# --------------------------------------------------------------------------
# projected column-chunk range reads


class _RangeReadUncovered(Exception):
    """A read landed outside the fetched ranges (page-index probe, metadata
    the chunk map didn't predict) — the caller falls back to a full GET."""


class _SparseFile:
    """Seekable read-only file over fetched byte segments of a remote object.

    pyarrow's ParquetFile drives it like any file: seek to the footer, then
    seek/read each projected column chunk. Reads must land inside a fetched
    segment; anything else raises `_RangeReadUncovered`."""

    def __init__(self, size: int, segments: list[tuple[int, bytes]]):
        self._size = size
        self._segs = sorted(segments)
        self._pos = 0
        self.closed = False

    def readable(self) -> bool:
        return True

    def seekable(self) -> bool:
        return True

    def writable(self) -> bool:
        return False

    def close(self) -> None:
        self.closed = True

    def flush(self) -> None:
        pass

    def tell(self) -> int:
        return self._pos

    def seek(self, offset: int, whence: int = 0) -> int:
        if whence == 0:
            self._pos = offset
        elif whence == 1:
            self._pos += offset
        elif whence == 2:
            self._pos = self._size + offset
        return self._pos

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0:
            n = self._size - self._pos
        if n == 0:
            return b""
        for start, data in self._segs:
            if start <= self._pos and self._pos + n <= start + len(data):
                off = self._pos - start
                self._pos += n
                return data[off : off + n]
        raise _RangeReadUncovered(f"read [{self._pos}, +{n}) outside fetched ranges")


def coalesce_ranges(
    ranges: list[tuple[int, int]], gap: int
) -> list[tuple[int, int]]:
    """Merge inclusive [start, end] ranges whose gap is <= `gap` bytes —
    a handful of slightly-fat GETs beats many tiny round trips."""
    if not ranges:
        return []
    out: list[list[int]] = []
    for s, e in sorted(ranges):
        if out and s <= out[-1][1] + 1 + gap:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class StreamScan:
    """Materialize a stream's sources for one query."""

    def __init__(
        self,
        parseable: Parseable,
        plan: LogicalPlan,
        hot_tier_dir: Path | None = None,
        use_hot_stubs: bool = False,
        file_filter: Callable[[str], bool] | None = None,
        local_staging: bool = True,
        staging_parquet: bool = True,
        fetch_remote_staging: bool = True,
    ):
        self.p = parseable
        self.plan = plan
        self.hot_tier_dir = hot_tier_dir
        # device-resident blocks skip the parquet read entirely: the scan
        # yields a stub the TPU executor resolves from the hot set
        self.use_hot_stubs = use_hot_stubs
        # distributed pushdown scoping (query/fanout.py): predicate over a
        # manifest file's BASENAME partitioning the scan by owner tag — a
        # peer keeps only its own files, the querier skips files a live
        # peer will scan, the fallback pass keeps only a failed peer's.
        # Files it rejects count as files_delegated, not pruned.
        self.file_filter = file_filter
        # staging sources: this node's in-memory/arrow window, this node's
        # staged-but-uncommitted parquet, and (queriers) the peers' windows
        # over the cluster data plane — individually switchable because the
        # peer partial scan and the fallback scan each cover a subset
        self.local_staging = local_staging
        self.staging_parquet = staging_parquet
        self.fetch_remote_staging = fetch_remote_staging
        self._sources: dict[bytes, ManifestFile] = {}
        self._manifest_files: list[ManifestFile] | None = None
        # ordered source ids the scan stubbed (hot-set or enccache
        # resident): the TPU executor's prefetcher walks this list to ship
        # block i+1 while block i aggregates. Complete before the first
        # stub is yielded (the partition loop runs eagerly).
        self.prefetchable: list[bytes] = []
        # pool workers update the same ScanStats concurrently with the
        # consumer thread's own bookkeeping
        self.stats = ScanStats()  # guarded-by: self._stats_lock
        self._stats_lock = threading.Lock()

    # ---------------------------------------------------------------- helpers

    def merged_snapshot(self) -> Snapshot:
        """Union of all nodes' snapshots for this stream
        (reference: stream_schema_provider.rs:566-585)."""
        merged = Snapshot()
        for fmt in self.p.metastore.get_all_stream_jsons(self.plan.stream):
            merged.manifest_list.extend(fmt.snapshot.manifest_list)
        return merged

    def _within_staging_window(self) -> bool:
        """Does the query range touch data still in staging?
        (reference: stream_schema_provider.rs:849-871)."""
        high = self.plan.time_bounds.high
        if high is None:
            return True
        window_start = datetime.now(UTC) - timedelta(seconds=2 * LOCAL_SYNC_INTERVAL)
        return high >= window_start

    def _columns_for_read(self, available: list[str]) -> list[str] | None:
        needed = self.plan.needed_columns
        if needed is None:
            return None
        cols = [c for c in available if c in needed]
        # carry the timestamp column for time filtering — unless the plan
        # dropped it (no bounds, no expression touches it)
        tb = self.plan.time_bounds
        wants_ts = (
            DEFAULT_TIMESTAMP_KEY in needed or tb.low is not None or tb.high is not None
        )
        if wants_ts and DEFAULT_TIMESTAMP_KEY in available and DEFAULT_TIMESTAMP_KEY not in cols:
            cols.append(DEFAULT_TIMESTAMP_KEY)
        return cols

    # ---------------------------------------------------------------- sources

    def legacy_listing_files(self) -> list[ManifestFile]:
        """Prefix-listing fallback for pre-manifest data (reference:
        query/listing_table_builder.rs:41-147): when a stream has NO
        snapshot manifests at all, parquet uploaded by older deployments is
        discovered by listing `{stream}/date=.../` prefixes bounded by the
        query's time range."""
        tb = self.plan.time_bounds
        if tb.low is not None and tb.high is not None:
            from parseable_tpu.utils.timeutil import TimeRange

            prefixes = [
                f"{self.plan.stream}/{p}"
                for p in TimeRange(tb.low, tb.high).generate_prefixes()
            ]
            # too many minute prefixes -> one stream-wide listing wins
            if len(prefixes) > 256:
                prefixes = [f"{self.plan.stream}/date="]
        else:
            prefixes = [f"{self.plan.stream}/date="]
        out: list[ManifestFile] = []
        seen: set[str] = set()
        errors = 0
        for prefix in prefixes:
            try:
                metas = list(self.p.storage.list_prefix(prefix))
            except Exception:
                logger.warning("legacy listing failed for %s", prefix, exc_info=True)
                errors += 1
                continue
            for m in metas:
                if not m.key.endswith(".parquet") or m.key in seen:
                    continue
                seen.add(m.key)
                with self._stats_lock:
                    self.stats.files_total += 1
                if self.file_filter is not None and not self.file_filter(
                    m.key.rsplit("/", 1)[-1]
                ):
                    with self._stats_lock:
                        self.stats.files_delegated += 1
                    continue
                out.append(ManifestFile(file_path=m.key, num_rows=0, file_size=m.size))
        if errors == len(prefixes) and errors:
            # storage down must error, not masquerade as an empty stream
            raise RuntimeError("legacy listing failed for every prefix (storage unavailable?)")
        return out

    def manifest_files(self) -> list[ManifestFile]:
        """Manifest entries after time + stats pruning; falls back to
        prefix listing when the stream predates manifests. Memoized for
        the scan's lifetime — the session consults it up to three times
        per query (time hint, count fast path, the scan itself)."""
        if self._manifest_files is not None:
            return self._manifest_files
        self._manifest_files = self._manifest_files_uncached()
        return self._manifest_files

    def _manifest_files_uncached(self) -> list[ManifestFile]:
        snapshot = self.merged_snapshot()
        if not snapshot.manifest_list:
            return self.legacy_listing_files()
        items = snapshot.manifests_for_range(self.plan.time_bounds.low, self.plan.time_bounds.high)
        files: list[ManifestFile] = []
        seen: set[str] = set()
        for item in items:
            prefix = item.manifest_path[: -len("/manifest.json")]
            manifest = self.p.metastore.get_manifest(prefix)
            if manifest is None:
                continue
            for f in manifest.files:
                if f.file_path in seen:
                    continue
                seen.add(f.file_path)
                with self._stats_lock:
                    self.stats.files_total += 1
                if self.file_filter is not None and not self.file_filter(
                    f.file_path.rsplit("/", 1)[-1]
                ):
                    with self._stats_lock:
                        self.stats.files_delegated += 1
                    continue
                if not self._file_overlaps_time(f):
                    with self._stats_lock:
                        self.stats.files_pruned += 1
                    continue
                if not prune_file(f, self.plan.constraints):
                    with self._stats_lock:
                        self.stats.files_pruned += 1
                    continue
                files.append(f)
        return files

    def _file_overlaps_time(self, f: ManifestFile) -> bool:
        tb = self.plan.time_bounds
        if tb.low is None and tb.high is None:
            return True
        for col in f.columns:
            if col.name == DEFAULT_TIMESTAMP_KEY and col.stats is not None:
                lo = datetime.fromtimestamp(col.stats.min / 1000, UTC)
                hi = datetime.fromtimestamp(col.stats.max / 1000, UTC)
                if tb.low is not None and hi < tb.low:
                    return False
                if tb.high is not None and lo >= tb.high:
                    return False
        return True

    # ---------------------------------------------------- parquet read paths

    def _record_error(self) -> None:
        with self._stats_lock:
            self.stats.scan_errors += 1
        SCAN_ERRORS.labels(self.plan.stream).inc()

    def _read_parquet(
        self, f: ManifestFile, use_threads: bool = True
    ) -> pa.Table | None:
        """Read a manifest entry: hot tier first, then projected range
        reads, else a whole-object GET. Errors drop the file from the
        results but are COUNTED (stats.scan_errors + Prometheus) so a
        partial response is detectable, not silent.

        `use_threads=False` when called from the scan pool: file-level
        parallelism replaces Arrow's intra-file thread pool — stacking
        both oversubscribes the host and measurably slows the cold path."""
        from parseable_tpu.utils import telemetry

        local: Path | None = None
        if self.hot_tier_dir is not None:
            cand = self.hot_tier_dir / f.file_path
            if cand.is_file():
                local = cand
        try:
            if local is None:
                try:
                    table = self._read_projected_remote(f, use_threads)
                    if table is not None:
                        return table
                except Exception:
                    # any range-read surprise (uncovered read, footer probe
                    # mismatch, flaky ranged GET) falls back to the full GET
                    logger.debug(
                        "range read fell back for %s", f.file_path, exc_info=True
                    )
                with telemetry.TRACER.span(
                    "scan.fetch", file=f.file_path, stream=self.plan.stream
                ) as sp:
                    data = self.p.storage.get_object(f.file_path)
                    sp["bytes"] = len(data)
                with self._stats_lock:
                    self.stats.bytes_scanned += len(data)
                src = io.BytesIO(data)
            else:
                with self._stats_lock:
                    self.stats.bytes_scanned += local.stat().st_size
                src = local
            with telemetry.TRACER.span(
                "scan.decode", file=f.file_path, stream=self.plan.stream
            ):
                with pq.ParquetFile(src) as pf:
                    cols = self._columns_for_read(pf.schema_arrow.names)
                    table = pf.read(columns=cols, use_threads=use_threads)
            with self._stats_lock:
                self.stats.rows_scanned += table.num_rows
            return table
        except Exception:
            logger.exception("failed reading parquet %s", f.file_path)
            self._record_error()
            return None

    def _read_projected_remote(
        self, f: ManifestFile, use_threads: bool = True
    ) -> pa.Table | None:
        """Projected column-chunk range read; None means 'use the full GET'
        (no projection, no real ranged backend, projection covers most of
        the file, tiny file). Raises on surprises — caller falls back."""
        from parseable_tpu.utils import telemetry

        opts = getattr(self.p, "options", None)
        if opts is None or not getattr(opts, "scan_range_reads", False):
            return None
        if self.plan.needed_columns is None:
            return None
        storage = self.p.storage
        if not storage.supports_range_reads():
            return None
        size = f.file_size
        # pyarrow's ParquetFile.open probes the file with one 64 KiB tail
        # read regardless of the real footer size, so the fetched tail must
        # cover at least that much or the sparse file can't serve the probe
        footer_hint = max(64 * 1024, getattr(opts, "scan_footer_bytes", 64 * 1024))
        if not size or size <= 2 * footer_hint:
            return None  # tiny object: one GET is strictly cheaper

        fetched = 0
        table = None
        try:
            with telemetry.TRACER.span(
                "scan.fetch", file=f.file_path, ranged=True, stream=self.plan.stream
            ) as fetch_sp:
                tail = storage.get_range(
                    f.file_path, size - min(size, footer_hint), size - 1
                )
                fetched += len(tail)
                if len(tail) < 8 or tail[-4:] != _PARQUET_MAGIC:
                    raise ValueError(f"not a parquet object: {f.file_path}")
                footer_total = struct.unpack("<I", tail[-8:-4])[0] + 8
                if footer_total > size:
                    raise ValueError(f"corrupt parquet footer length in {f.file_path}")
                if footer_total > len(tail):
                    more = storage.get_range(
                        f.file_path, size - footer_total, size - len(tail) - 1
                    )
                    fetched += len(more)
                    tail = more + tail
                md = pq.read_metadata(io.BytesIO(tail[-footer_total:]))
                cols = self._columns_for_read(md.schema.to_arrow_schema().names)
                if cols is None:
                    return None
                colset = set(cols)
                ranges: list[tuple[int, int]] = []
                projected = 0
                for rg in range(md.num_row_groups):
                    group = md.row_group(rg)
                    for ci in range(group.num_columns):
                        chunk = group.column(ci)
                        if chunk.path_in_schema.split(".", 1)[0] not in colset:
                            continue
                        start = chunk.data_page_offset
                        dict_off = chunk.dictionary_page_offset
                        if dict_off is not None and 0 <= dict_off < start:
                            start = dict_off
                        length = chunk.total_compressed_size
                        if start < 0 or length <= 0 or start + length > size:
                            raise ValueError(
                                f"chunk range out of bounds in {f.file_path}"
                            )
                        ranges.append((start, start + length - 1))
                        projected += length
                if not ranges:
                    return None  # zero physical columns projected (count-only)
                max_cov = getattr(opts, "scan_range_max_coverage", 0.8)
                if projected + footer_total >= max_cov * size:
                    return None  # near-full coverage: one GET beats k round trips
                gap = max(0, getattr(opts, "scan_range_coalesce_bytes", 1024 * 1024))
                segments: list[tuple[int, bytes]] = []
                for s, e in coalesce_ranges(ranges, gap):
                    data = storage.get_range(f.file_path, s, e)
                    if len(data) != e - s + 1:
                        raise ValueError(f"short ranged GET on {f.file_path}")
                    fetched += len(data)
                    segments.append((s, data))
                segments.append((size - len(tail), tail))
                fetch_sp["bytes"] = fetched

            with telemetry.TRACER.span(
                "scan.decode",
                file=f.file_path,
                ranged=True,
                bytes=fetched,
                stream=self.plan.stream,
            ):
                with pq.ParquetFile(_SparseFile(size, segments)) as pf:
                    table = pf.read(columns=cols, use_threads=use_threads)
            return table
        finally:
            # every byte actually pulled counts — including the footer probe
            # when this path bails out to (or falls back on) the full GET
            with self._stats_lock:
                self.stats.bytes_scanned += fetched
                if table is not None:
                    saved = max(0, size - fetched)
                    self.stats.bytes_saved_by_projection += saved
                    self.stats.range_read_files += 1
                    self.stats.rows_scanned += table.num_rows
            if table is not None:
                SCAN_PROJECTION_BYTES_SAVED.labels(self.plan.stream).inc(
                    max(0, size - fetched)
                )

    def staging_tables(self) -> Iterator[pa.Table]:
        """Staging-window data: this node's unconverted arrows + unuploaded
        parquet, and — on a dedicated querier — every live ingestor's staging
        window fetched over the cluster data plane (reference:
        airplane.rs:155-184 recent-data fan-in)."""
        from parseable_tpu.config import Mode

        stream = self.p.streams.get(self.plan.stream)
        if stream is None:
            return
        if self.p.options.mode == Mode.QUERY and self.fetch_remote_staging:
            from parseable_tpu.server.cluster import fetch_staging_batches

            # bounded fan-in: the peer filters to the plan's time range and
            # projects to the needed columns before serializing — a narrow
            # dashboard query stops shipping every peer's full window
            fanin: dict = {}
            remote = fetch_staging_batches(
                self.p,
                self.plan.stream,
                time_bounds=self.plan.time_bounds,
                columns=self.plan.needed_columns,
                stats=fanin,
            )
            with self._stats_lock:
                self.stats.fanin_bytes += fanin.get("bytes", 0)
                self.stats.fanin_errors += fanin.get("errors", 0)
                for k in (
                    "http_bytes", "flight_bytes", "flight_peers", "flight_fallbacks"
                ):
                    if fanin.get(k):
                        self.stats.fanin_transport[k] = (
                            self.stats.fanin_transport.get(k, 0) + fanin[k]
                        )
            if remote:
                from parseable_tpu.utils.arrowutil import adapt_batch, merge_schemas

                with self._stats_lock:
                    self.stats.staging_batches += len(remote)
                schema = merge_schemas([b.schema for b in remote])
                table = pa.Table.from_batches([adapt_batch(schema, b) for b in remote])
                cols = self._columns_for_read(table.column_names)
                if cols is not None:
                    table = table.select(cols)
                yield table
        if not self.local_staging:
            return
        batches = stream.staging_batches()
        if batches:
            with self._stats_lock:
                self.stats.staging_batches += len(batches)
            table = pa.Table.from_batches(batches)
            cols = self._columns_for_read(table.column_names)
            if cols is not None:
                table = table.select(cols)
            yield table
        if not self.staging_parquet:
            return
        # a staged parquet that has already been uploaded and committed is
        # served by the manifest scan — reading the lingering local copy
        # (commit -> unlink is not atomic) would double-count its rows.
        # The memoized manifest list gives one consistent committed set for
        # both sides of the dedupe.
        staged = stream.parquet_files()
        committed = (
            {m.file_path.rsplit("/", 1)[-1] for m in self.manifest_files()}
            if staged
            else set()
        )
        for f in staged:
            if f.name in committed:
                continue
            try:
                with pq.ParquetFile(f) as pf:
                    cols = self._columns_for_read(pf.schema_arrow.names)
                    t = pf.read(columns=cols)
                with self._stats_lock:
                    self.stats.rows_scanned += t.num_rows
                yield t
            except FileNotFoundError:
                # committed + unlinked between listing and read; its rows
                # are (or are about to be) visible via the manifest
                logger.debug("staged parquet %s vanished (uploaded)", f)
            except Exception:
                logger.exception("failed reading staged parquet %s", f)
                self._record_error()

    # ------------------------------------------------------------------ scan

    def _stamp(self, table: pa.Table, source_id: bytes) -> pa.Table:
        meta = dict(table.schema.metadata or {})
        meta[b"ptpu_source_id"] = source_id
        return table.replace_schema_metadata(meta)

    def tables(self) -> Iterator[pa.Table]:
        """All sources.

        Staging tables are row-filtered here (they're query-local and never
        cached). Parquet tables yield *unfiltered* but stamped with a source
        id so their device encodings are query-independent and hot-set
        cacheable — the engines apply the row-level time filter themselves
        (host filter on CPU, device mask on TPU).

        Hot-set / enccache stubs resolve synchronously before any I/O;
        everything else goes through the parallel fetch+decode pool and
        yields in completion order. The bytes-scanned gauge lands in a
        `finally` so early exits (LIMIT, timeout, generator close) still
        account for what was actually fetched.
        """
        try:
            yield from self._tables_inner()
        finally:
            with self._stats_lock:
                scanned = self.stats.bytes_scanned
            TOTAL_QUERY_BYTES_SCANNED_DATE.labels(
                datetime.now(UTC).date().isoformat()
            ).inc(scanned)

    def _tables_inner(self) -> Iterator[pa.Table]:
        if self._within_staging_window():
            for t in self.staging_tables():
                t = self._apply_time_filter(t)
                if t.num_rows:
                    yield t
        hotset = key_fn = enccache = None
        dict_cols: set[str] = set()
        if self.use_hot_stubs:
            from parseable_tpu.ops.enccache import get_enccache
            from parseable_tpu.ops.hotset import get_hotset
            from parseable_tpu.query.executor_tpu import (
                dict_group_columns,
                hot_key,
                make_stub,
            )

            hotset = get_hotset()
            enccache = get_enccache(self.p.options)
            dict_cols = dict_group_columns(self.plan.select)
            key_fn = lambda sid: hot_key(sid, self.plan.needed_columns, dict_cols)
            make_stub_fn = make_stub
        to_fetch: list[tuple[ManifestFile, bytes]] = []
        stubs: list[tuple[bytes, int]] = []
        for f in self.manifest_files():
            # size + row count make the id content-sensitive: a rewritten
            # object at the same path must not serve a stale cached block
            source_id = f"{f.file_path}|{f.file_size}|{f.num_rows}".encode()
            self._sources[source_id] = f
            if hotset is not None:
                entry = hotset.get(key_fn(source_id))
                if entry is not None:
                    stubs.append((source_id, entry.meta.num_rows))
                    continue
                # encoded-block disk cache: the executor loads device-ready
                # columns; skip the parquet read entirely
                if enccache is not None and enccache.can_serve(
                    source_id, self.plan.needed_columns, dict_cols
                ):
                    stubs.append((source_id, f.num_rows))
                    continue
            to_fetch.append((f, source_id))
        # publish the ordered stub list BEFORE the first stub yield: the
        # executor's prefetcher ships block i+1 from the enccache while
        # block i aggregates (hot-now entries are included too — under
        # eviction pressure they may be gone by the time the engine gets
        # there, and the prefetcher skips anything still resident)
        self.prefetchable = [sid for sid, _rows in stubs]
        for source_id, rows in stubs:
            with self._stats_lock:
                self.stats.rows_scanned += rows
            yield make_stub_fn(source_id, rows)

        opts = getattr(self.p, "options", None)
        workers = min(len(to_fetch), max(1, getattr(opts, "scan_workers", 1)))
        if workers <= 1:
            for f, source_id in to_fetch:
                t = self._read_parquet(f)
                if t is None or t.num_rows == 0:
                    continue
                yield self._stamp(t, source_id)
            return
        inflight = max(1, getattr(opts, "scan_inflight_bytes", 256 * 1024 * 1024))

        def on_wait(seconds: float) -> None:
            with self._stats_lock:
                self.stats.sched_wait_seconds += seconds

        # shared cross-query scheduler: this query's files ride one lane,
        # dispatched fairly against every other in-flight query's lanes
        lane = get_scan_scheduler(opts).lane(
            inflight_bytes=inflight, on_wait=on_wait
        )
        pooled = lane_iter(
            lane,
            to_fetch,
            lambda pair: self._read_parquet(pair[0], use_threads=False),
            lambda pair: pair[0].file_size or 1,
        )
        try:
            for (f, source_id), t in pooled:
                if t.num_rows == 0:
                    continue
                yield self._stamp(t, source_id)
        finally:
            # explicit, synchronous lane drain when the consumer closes us
            # (a for-loop does not close its source generator on its own)
            pooled.close()

    def read_source(self, source_id: bytes) -> pa.Table:
        """Re-read a stubbed source (hot-set eviction race / CPU fallback)."""
        f = self._sources.get(source_id)
        if f is None:
            raise KeyError(f"unknown scan source {source_id!r}")
        t = self._read_parquet(f)
        if t is None:
            raise OSError(f"failed to re-read {f.file_path}")
        return self._stamp(t, source_id)

    def _apply_time_filter(self, table: pa.Table) -> pa.Table:
        tb = self.plan.time_bounds
        if (tb.low is None and tb.high is None) or DEFAULT_TIMESTAMP_KEY not in table.column_names:
            return table
        import pyarrow.compute as pc

        col = table.column(DEFAULT_TIMESTAMP_KEY)
        mask = None
        if tb.low is not None:
            mask = pc.greater_equal(col, pa.scalar(tb.low.replace(tzinfo=None), type=col.type))
        if tb.high is not None:
            m2 = pc.less(col, pa.scalar(tb.high.replace(tzinfo=None), type=col.type))
            mask = m2 if mask is None else pc.and_(mask, m2)
        return table.filter(mask)
