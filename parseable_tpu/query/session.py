"""Query session: SQL + time range -> plan -> scan -> execute -> JSON rows.

Parity target (reference: src/query/mod.rs QUERY_SESSION / Query::execute,
handlers/http/query.rs::query): API callers pass SQL plus startTime/endTime;
time filters are injected into the plan exactly like the reference's
`final_logical_plan`, the count(*) fast path is served from manifest row
counts, and everything else runs on the selected engine (tpu|cpu).
"""

from __future__ import annotations

import copy as _copy
import logging
import threading
import time as _time
from collections import OrderedDict
from dataclasses import dataclass, field
from datetime import UTC
from typing import Any

import pyarrow as pa

from parseable_tpu.core import Parseable
from parseable_tpu.query import sql as S
from parseable_tpu.query.executor import QueryExecutor
from parseable_tpu.query.planner import LogicalPlan, TimeBounds, plan as build_plan
from parseable_tpu.query.provider import StreamScan
from parseable_tpu.utils.arrowutil import record_batches_to_json
from parseable_tpu.utils.metrics import (
    QUERY_CACHE_HIT,
    QUERY_EXECUTE_TIME,
    QUERY_PLAN_CACHE,
)
from parseable_tpu.utils.timeutil import TimeRange

logger = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# plan/parse cache


class PlanCache:
    """Thread-safe LRU over parsed ASTs and logical plans.

    Two entry kinds share the store: ("ast", sql) -> pristine parsed
    Select, and ("plan", sql, stream, schema_fp) -> the LogicalPlan as
    built by build_plan, before any per-request state (API time bounds,
    deadline, schema hint) is applied. Entries are stored AND returned as
    deepcopies — planning and execution mutate both structures freely, so
    the cached originals must never be reachable from a running query.

    Invalidation: the schema fingerprint in the key makes a schema change
    miss naturally; commit_schema additionally calls invalidate_stream so
    superseded plans don't squat on LRU slots."""

    def __init__(self, max_entries: int):
        self.max_entries = max(1, max_entries)
        self._lock = threading.Lock()
        self._entries: OrderedDict[tuple, Any] = OrderedDict()  # guarded-by: self._lock
        self.hits = 0  # guarded-by: self._lock
        self.misses = 0  # guarded-by: self._lock

    def get(self, key: tuple):
        with self._lock:
            val = self._entries.get(key)
            if val is None:
                self.misses += 1
                return None
            self.hits += 1
            self._entries.move_to_end(key)
        return _copy.deepcopy(val)

    def put(self, key: tuple, val) -> None:
        val = _copy.deepcopy(val)
        with self._lock:
            self._entries[key] = val
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)

    def invalidate_stream(self, stream: str) -> int:
        with self._lock:
            doomed = [
                k for k in self._entries if k[0] == "plan" and k[2] == stream
            ]
            for k in doomed:
                del self._entries[k]
        return len(doomed)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


_PLAN_CACHE: PlanCache | None = None
_PLAN_CACHE_LOCK = threading.Lock()


def get_plan_cache(options=None) -> PlanCache | None:
    """Process-wide plan/parse cache sized by P_QUERY_PLAN_CACHE
    (0 disables). Re-roots when the configured capacity changes."""
    global _PLAN_CACHE
    entries = getattr(options, "query_plan_cache_entries", 256)
    if entries <= 0:
        return None
    with _PLAN_CACHE_LOCK:
        if _PLAN_CACHE is None or _PLAN_CACHE.max_entries != entries:
            _PLAN_CACHE = PlanCache(entries)
        return _PLAN_CACHE


def invalidate_plan_cache(stream: str) -> int:
    """Schema-change hook (core.commit_schema): evict the stream's plans.
    The parsed-AST entries stay — SQL text doesn't depend on schema."""
    with _PLAN_CACHE_LOCK:
        cache = _PLAN_CACHE
    return cache.invalidate_stream(stream) if cache is not None else 0


def _is_composite(select: S.Select) -> bool:
    """Joins/CTEs/unions/subqueries need the multi-table planner (and full
    materialization before streaming)."""
    return bool(select.ctes or select.set_ops or select.joins) or any(
        S.contains_subquery(x)
        for x in [select.where, select.having, *(i.expr for i in select.items)]
    )


def _referenced_streams(select: S.Select) -> set[str]:
    """Every physical stream the statement touches (CTE names excluded):
    main table, joins, union branches, CTE bodies, subqueries."""
    out: set[str] = set()
    cte_names: set[str] = set()

    def walk_expr(e) -> None:
        if e is None:
            return
        if isinstance(e, S.Subquery):
            walk(e.select)
            return
        for child in getattr(e, "__dict__", {}).values():
            if isinstance(child, S.Expr):
                walk_expr(child)
            elif isinstance(child, list):
                for c in child:
                    if isinstance(c, S.Expr):
                        walk_expr(c)
                    elif isinstance(c, S.OrderItem):
                        walk_expr(c.expr)
                    elif isinstance(c, tuple):
                        for cc in c:
                            if isinstance(cc, S.Expr):
                                walk_expr(cc)

    def walk(s: S.Select) -> None:
        for name, sub in s.ctes.items():
            cte_names.add(name)
            walk(sub)
        if s.table:
            out.add(s.table)
        for j in s.joins:
            out.add(j.table)
            walk_expr(j.on)
        for _, branch in s.set_ops:
            walk(branch)
        for x in [s.where, s.having, *(i.expr for i in s.items)]:
            walk_expr(x)

    walk(select)
    return out - cte_names


class QueryError(ValueError):
    pass


def collect_streams(select: S.Select) -> set[str]:
    """Every stream a query touches: FROM, JOINs, and subqueries."""
    out: set[str] = set()
    if select.table:
        out.add(select.table)
    for j in select.joins:
        out.add(j.table)

    def walk(e: S.Expr | None) -> None:
        if e is None:
            return
        if isinstance(e, S.Subquery):
            out.update(collect_streams(e.select))
            return
        for attr in ("left", "right", "operand", "expr", "low", "high", "else_expr"):
            v = getattr(e, attr, None)
            if isinstance(v, S.Expr):
                walk(v)
        for lst_attr in ("items", "args"):
            for v in getattr(e, lst_attr, []) or []:
                if isinstance(v, S.Expr):
                    walk(v)
        for w, t in getattr(e, "whens", []) or []:
            walk(w)
            walk(t)

    walk(select.where)
    walk(select.having)
    for i in select.items:
        walk(i.expr)
    for _, branch in select.set_ops:
        out.update(collect_streams(branch))
    cte_names = set(select.ctes)
    for cte_sel in select.ctes.values():
        out.update(collect_streams(cte_sel))
    return out - cte_names


def _qualified_refs(e: S.Expr | None) -> list[S.Column]:
    """All Column nodes (qualified or not) in an expression tree."""
    out: list[S.Column] = []
    if e is None:
        return out

    def walk(x) -> None:
        if isinstance(x, S.Column):
            out.append(x)
            return
        if isinstance(x, S.Subquery):
            return
        for attr in ("left", "right", "operand", "expr", "low", "high", "else_expr"):
            v = getattr(x, attr, None)
            if isinstance(v, S.Expr):
                walk(v)
        for lst_attr in ("items", "args"):
            for v in getattr(x, lst_attr, []) or []:
                if isinstance(v, S.Expr):
                    walk(v)
        for w, t in getattr(x, "whens", []) or []:
            walk(w)
            walk(t)

    walk(e)
    return out


@dataclass
class QueryResult:
    table: pa.Table
    fields: list[str]
    stats: dict[str, Any] = field(default_factory=dict)

    def to_json_rows(self) -> list[dict]:
        return record_batches_to_json(self.table.to_batches())


class _TimedIter:
    """Wraps a scan iterator, accumulating the wall time spent producing
    blocks — the scan share of the EXPLAIN ANALYZE stage breakdown (the
    executor pulls lazily, so scan and execute interleave; time inside
    next() is scan/decode, the remainder is operator work)."""

    def __init__(self, it):
        self._it = iter(it)
        self.seconds = 0.0
        self.blocks = 0

    def __iter__(self):
        return self

    def __next__(self):
        t0 = _time.perf_counter()
        try:
            return next(self._it)
        finally:
            self.seconds += _time.perf_counter() - t0
            self.blocks += 1

    def close(self) -> None:
        """Close the wrapped scan generator — cancels the parallel fetch
        pool deterministically (LIMIT early-exit, timeout, error) instead
        of waiting for GC to finalize the suspended generator."""
        close = getattr(self._it, "close", None)
        if close is not None:
            close()


class QuerySession:
    """One engine-backed session over a Parseable instance."""

    def __init__(self, parseable: Parseable, engine: str | None = None):
        self.p = parseable
        self.engine = engine or parseable.options.query_engine

    def resolve_stream(self, name: str) -> None:
        """Make sure the stream exists locally, loading from storage when a
        querier sees it for the first time (query.rs:558-618)."""
        if self.p.streams.get(name) is None:
            self.p.load_streams_from_storage()
        if self.p.streams.get(name) is None:
            raise QueryError(f"stream {name!r} does not exist")

    def query(
        self,
        sql_text: str,
        start_time: str | None = None,
        end_time: str | None = None,
        allowed_streams: set[str] | None = None,
    ) -> QueryResult:
        """Run SQL. `allowed_streams` (None = unrestricted) is the caller's
        RBAC scope, enforced on the *resolved* plan before any execution so
        unauthorized streams neither run nor leak through error messages."""
        t0 = _time.monotonic()
        from parseable_tpu.utils.telemetry import TRACER

        with TRACER.span("query", engine=self.engine) as sp:
            self._plan_cache_state = None
            self._result_cache_state = None
            tp = _time.perf_counter()
            with TRACER.span("query.parse"):
                select = self._parse_cached(sql_text)
            self._parse_ms = round((_time.perf_counter() - tp) * 1000, 3)
            self._sql_text = sql_text
            result = self._query_ast(
                select, start_time, end_time, allowed_streams, t0, sql_key=sql_text
            )
            sp["stream"] = ",".join(sorted(_referenced_streams(select))) or "?"
            sp["rows"] = result.table.num_rows
            return result

    def _parse_cached(self, sql_text: str) -> S.Select:
        """parse_sql through the plan/parse cache: the cached AST is
        pristine (stored before any planning mutation) and handed out as a
        deepcopy, so repeated dashboard statements skip the parser."""
        cache = get_plan_cache(self.p.options)
        if cache is None:
            return S.parse_sql(sql_text)
        cached = cache.get(("ast", sql_text))
        if cached is not None:
            return cached
        select = S.parse_sql(sql_text)
        cache.put(("ast", sql_text), select)
        return select

    def _schema_fingerprint(self, stream: str) -> int | None:
        """Fingerprint of the stream's committed schema — part of every
        plan-cache key so a schema change can never serve a stale plan."""
        s = self.p.streams.get(stream)
        if s is None or not s.metadata.schema:
            return None
        return hash(tuple((n, str(f.type)) for n, f in s.metadata.schema.items()))

    def _query_ast(
        self,
        select: S.Select,
        start_time: str | None,
        end_time: str | None,
        allowed_streams: set[str] | None,
        t0: float | None = None,
        sql_key: str | None = None,
    ) -> QueryResult:
        t0 = t0 if t0 is not None else _time.monotonic()
        if select.explain:
            return self._explain(select, start_time, end_time, allowed_streams, t0)
        if select.ctes:
            return self._query_with_ctes(select, start_time, end_time, allowed_streams, t0)
        if select.set_ops:
            return self._query_union(select, start_time, end_time, allowed_streams, t0)
        has_sub = any(
            S.contains_subquery(x)
            for x in [select.where, select.having, *(i.expr for i in select.items)]
        )
        if select.joins or has_sub:
            return self._query_multi(select, start_time, end_time, allowed_streams, t0)
        cte_tables = getattr(self, "_cte_tables", None)
        if cte_tables is not None and select.table in cte_tables:
            return self._query_cte_table(select, cte_tables[select.table], t0)
        from parseable_tpu.utils.telemetry import TRACER

        tplan = _time.perf_counter()
        with TRACER.span("query.plan"):
            lp = self._plan_ast(
                select, start_time, end_time, allowed_streams, t0, sql_key=sql_key
            )
        plan_ms = round((_time.perf_counter() - tplan) * 1000, 3)

        scan = StreamScan(
            self.p,
            lp,
            hot_tier_dir=self._hot_dir(lp.stream),
            use_hot_stubs=self.engine == "tpu" and lp.is_aggregate,
        )
        texec = _time.perf_counter()
        self._fanout_stats = None
        self._execute_clock = None
        # pushdown ships the ORIGINAL statement text to peers (they re-plan
        # it locally); only the top-level single-statement path has it —
        # CTE bodies / resolved-subquery selects executed through here are
        # derived statements with no faithful text, so they stay central
        self._exec_sql = sql_key
        with TRACER.span("query.execute", stream=lp.stream) as sp:
            result, timer = self._execute(lp, scan)
            sp["rows"] = result.table.num_rows
        tdone = _time.perf_counter()
        exec_s = tdone - texec
        elapsed = _time.monotonic() - t0
        QUERY_EXECUTE_TIME.labels(lp.stream).observe(elapsed)
        result.stats.update(
            {
                "elapsed_secs": round(elapsed, 6),
                "engine": self.engine,
                "files_total": scan.stats.files_total,
                "files_pruned": scan.stats.files_pruned,
                "bytes_scanned": scan.stats.bytes_scanned,
                "rows_scanned": scan.stats.rows_scanned,
                # nonzero = files dropped by read failures (partial result)
                "scan_errors": scan.stats.scan_errors,
                "bytes_saved_by_projection": scan.stats.bytes_saved_by_projection,
                # EXPLAIN ANALYZE-style per-stage wall-time breakdown;
                # scan = time inside the block iterator, execute = the rest
                "stages": {
                    "parse_ms": getattr(self, "_parse_ms", None),
                    "plan_ms": plan_ms,
                    "scan_ms": round(timer.seconds * 1000, 3),
                    "execute_ms": round(max(exec_s - timer.seconds, 0.0) * 1000, 3),
                    # execute_ms split where the work happens: the TPU
                    # executor's phase clock (None where none ran: the CPU
                    # engine, a result-cache hit, the manifest fast path)
                    "execute": self._execute_stage(self._execute_clock, texec, tdone),
                    "total_ms": round(elapsed * 1000, 3),
                    "bytes_saved_by_projection": scan.stats.bytes_saved_by_projection,
                    # cross-query contention: time this query's scan tasks
                    # spent queued behind other queries on the shared pool
                    "sched_wait_ms": round(scan.stats.sched_wait_seconds * 1000, 3),
                    "plan_cache": getattr(self, "_plan_cache_state", None),
                    "result_cache": getattr(self, "_result_cache_state", None),
                    # distributed data plane: pushdown scatter-gather
                    # breakdown (per-peer latency/bytes, hedges, fallbacks)
                    # or the central pull's raw fan-in accounting
                    "fanout": self._fanout_stage(scan),
                    # tiering state for this process + this query's prefetch
                    # outcome (None on the CPU engine — no device tier)
                    "hotset": self._hotset_stage(result.stats.get("device_routes")),
                    # program-cache traffic: XLA builds vs cache hits this
                    # query, plus rebuilds of an already-built key — the
                    # dlint tripwire's budget holds "recompiles" at 0
                    # (None on the CPU engine — nothing jits)
                    "programs": self._programs_stage(
                        result.stats.get("device_routes")
                    ),
                },
            }
        )
        self._maybe_log_slow(select, elapsed, result.stats)
        return result

    def _fanout_stage(self, scan: StreamScan) -> dict | None:
        """stats.stages.fanout: the distributed data plane's share of the
        query — pushdown scatter-gather stats when it ran, otherwise the
        central pull's raw staging fan-in bytes/errors (None on non-querier
        nodes with nothing fetched)."""
        dist = getattr(self, "_fanout_stats", None)
        if dist is not None:
            snap = dict(dist)
            with scan._stats_lock:
                snap["fanin_bytes"] = scan.stats.fanin_bytes
                snap["fanin_errors"] = scan.stats.fanin_errors
                snap["files_delegated"] = scan.stats.files_delegated
                # fallback fan-in's share of the transport ladder; the
                # scatter's own flight/http split is already in "transport"
                if scan.stats.fanin_transport:
                    snap["fanin_transport"] = dict(scan.stats.fanin_transport)
            return snap
        with scan._stats_lock:
            fanin_bytes = scan.stats.fanin_bytes
            fanin_errors = scan.stats.fanin_errors
            fanin_transport = dict(scan.stats.fanin_transport)
        from parseable_tpu.config import Mode as _Mode

        if self.p.options.mode != _Mode.QUERY and not fanin_bytes and not fanin_errors:
            return None
        out = {
            "mode": "central",
            "fanin_bytes": fanin_bytes,
            "fanin_errors": fanin_errors,
        }
        if fanin_transport:
            out["transport"] = fanin_transport
        return out

    def _hotset_stage(self, routes: dict | None) -> dict | None:
        """stats.stages.hotset: first-class tier state (budget, residency,
        evictions, oversize rejections) plus this query's prefetch counters
        — previously these lived only as Python attrs on the singleton."""
        if self.engine != "tpu":
            return None
        from parseable_tpu.ops.hotset import get_hotset

        snap = get_hotset().stats_snapshot()
        for k in ("prefetch_issued", "prefetch_hits", "prefetch_wasted"):
            if routes and k in routes:
                snap[k] = routes[k]
        return snap

    @staticmethod
    def _execute_stage(clock, t_begin: float, t_end: float) -> dict | None:
        """stats.stages.execute: the finished phase clock of the TPU
        executor's `route_stats` (executor_tpu.RouteStats), in ms. The
        eight phases never overlap and bracket host code only, so their
        sum is at most `execute_ms`; what is left is the executor's own
        bookkeeping between them, the session's work around it and any
        block the CPU folded. `head_ms` and `tail_ms` are no phases but
        the request's two edges with nothing of it on the device, on the
        wall clock: execute's start to the first program call's return
        (the scan's waits before it included), and the last readback's end
        to execute's end; None where no program ran or nothing was read.
        `blocks`, `readbacks` and the three `merge_*` are counts."""
        if clock is None:
            return None
        ns = clock.ns
        t0_ns, t1_ns = t_begin * 1e9, t_end * 1e9
        return {
            "encode_ms": round(ns["encode"] / 1e6, 3),
            "prepare_ms": round(ns["prepare"] / 1e6, 3),
            "dispatch_ms": round(ns["dispatch"] / 1e6, 3),
            "device_wait_ms": round(ns["device_wait"] / 1e6, 3),
            "readback_ms": round(ns["readback"] / 1e6, 3),
            "partial_ms": round(ns["partial"] / 1e6, 3),
            "merge_ms": round(ns["merge"] / 1e6, 3),
            "finalize_ms": round(ns["finalize"] / 1e6, 3),
            "head_ms": (
                round((clock.first_dispatch_ns - t0_ns) / 1e6, 3)
                if clock.first_dispatch_ns
                else None
            ),
            "tail_ms": (
                round((t1_ns - clock.last_readback_ns) / 1e6, 3)
                if clock.last_readback_ns
                else None
            ),
            "blocks": clock.blocks,
            "readbacks": clock.readbacks,
            # arithmetic nodes traced a row for the aggregates over
            # expressions (after sharing): device time beside work asked for
            "expr_nodes": clock.expr_nodes,
            # the block-local merge's counters beside the phases they
            # explain (device_routes holds them too): whether it ran on
            # the device, entries it sorted there, groups that survived
            "merge_device": clock["merge_device"],
            "merge_entries": clock["merge_entries"],
            "merge_survivors": clock["merge_survivors"],
        }

    def _programs_stage(self, routes: dict | None) -> dict | None:
        """stats.stages.programs: this query's program-cache traffic —
        warm queries should read built == 0 and recompiles == 0; a nonzero
        recompile means a cache key was rebuilt (eviction or key churn),
        the condition the dlint tripwire turns red on."""
        if self.engine != "tpu" or routes is None:
            return None
        return {
            "built": int(routes.get("programs_built", 0)),
            "reused": int(routes.get("programs_reused", 0)),
            "recompiles": int(routes.get("recompiles", 0)),
        }

    def _maybe_log_slow(self, select: S.Select, elapsed: float, stats: dict) -> None:
        """Slow-query log (gated by P_SLOW_QUERY_MS; 0 disables): one
        structured warning with the statement, stage breakdown, and the
        trace id so the full span tree is one /debug/spans call away."""
        threshold = getattr(self.p.options, "slow_query_ms", 0)
        if not threshold or elapsed * 1000 < threshold:
            return
        from parseable_tpu.utils.telemetry import current_trace_id

        sql_text = getattr(self, "_sql_text", None) or S.format_statement(select)
        logger.warning(
            "slow query (%.0f ms > %d ms) trace_id=%s engine=%s stages=%s sql=%s",
            elapsed * 1000,
            threshold,
            current_trace_id() or "-",
            stats.get("engine", self.engine),
            stats.get("stages"),
            sql_text,
        )

    def _explain(
        self,
        select: S.Select,
        start_time: str | None,
        end_time: str | None,
        allowed_streams: set[str] | None,
        t0: float,
    ) -> QueryResult:
        """EXPLAIN [ANALYZE]: (plan_type, plan) rows — DataFusion's explain
        shape (reference: src/query/mod.rs:212-276 exposes EXPLAIN through
        the DataFusion session)."""
        mode = select.explain
        select.explain = None
        # RBAC before anything renders: composite statements don't reach
        # _plan_ast's per-stream check, so enforce over every referenced
        # stream here (same contract as execution)
        if allowed_streams is not None:
            for stream in sorted(_referenced_streams(select)):
                if stream not in allowed_streams:
                    raise QueryError(f"unauthorized for stream {stream!r}")
        plan_types = ["logical_plan"]
        plans = [S.format_statement(select)]

        if _is_composite(select):
            plans.append(
                "CompositeExec: joins/CTEs/unions/subqueries run through the "
                "multi-table planner (query/multi.py); branch scans prune and "
                "execute like single-stream plans"
            )
            plan_types.append("physical_plan")
        else:
            try:
                lp = self._plan_ast(select, start_time, end_time, allowed_streams, t0)
                proj = (
                    ", ".join(sorted(lp.needed_columns))
                    if lp.needed_columns is not None
                    else "*"
                )
                phys = [
                    f"engine={self.engine}",
                    f"scan: stream={lp.stream} projection=[{proj}] "
                    f"time_bounds=[{lp.time_bounds.low}, {lp.time_bounds.high}]",
                ]
                if lp.is_aggregate:
                    from parseable_tpu.query.partials import specs_partializable
                    from parseable_tpu.query.executor import QueryExecutor

                    agg, _, _ = QueryExecutor(lp).build_aggregator()
                    if self.engine == "tpu":
                        phys.append(
                            "aggregate: device fused one-hot fold (dense pow2 "
                            "group space; block-local two-phase past "
                            "DENSE_G_MAX)"
                        )
                    elif specs_partializable(agg.specs):
                        phys.append(
                            "aggregate: two-phase partial/merge "
                            "(dictionary-coded keys, single int64 group code)"
                        )
                    else:
                        phys.append("aggregate: streaming hash aggregate")
                    if select.order_by and select.limit is not None:
                        phys.append(
                            f"top-k: ORDER BY/LIMIT pushdown (k={ (select.offset or 0) + select.limit })"
                        )
                plan_types.append("physical_plan")
                plans.append("\n".join(phys))
            except QueryError:
                raise
            except Exception as e:  # noqa: BLE001
                plan_types.append("physical_plan")
                plans.append(f"(plan unavailable: {e})")

        if mode == "analyze":
            # sql_key: single non-composite statements have faithful text,
            # so the analyzed run is pushdown-eligible exactly like the
            # real query it profiles (without it _exec_sql stays None and
            # EXPLAIN ANALYZE silently measured the central path only)
            res = self._query_ast(
                select,
                start_time,
                end_time,
                allowed_streams,
                sql_key=None if _is_composite(select) else S.format_statement(select),
            )
            st = res.stats
            plan_types.append("analyze")
            parts = [f"rows_out={res.table.num_rows}"]
            for k in (
                "rows_scanned",
                "files_total",
                "files_pruned",
                "bytes_scanned",
                "bytes_saved_by_projection",
                "scan_errors",
                "elapsed_secs",
                "engine",
            ):
                if st.get(k) is not None:  # composite paths carry no scan stats
                    parts.append(f"{k}={st[k]}")
            plans.append(" ".join(parts))
            stages = st.get("stages")
            if stages:
                # per-stage wall-time split (parse/plan/scan/execute);
                # nested stage dicts (fanout/hotset) get their own rows
                plan_types.append("stage_timing")
                plans.append(
                    " ".join(
                        f"{k}={v}"
                        for k, v in stages.items()
                        if v is not None and not isinstance(v, dict)
                    )
                )
            fanout = (stages or {}).get("fanout")
            if fanout:
                # distributed data plane: scatter totals + one line per peer
                plan_types.append("fanout")

                def _fv(v):
                    # transport breakdowns are dicts: render flight:2,http:1
                    if isinstance(v, dict):
                        return ",".join(f"{k}:{v[k]}" for k in sorted(v))
                    return v

                lines = [
                    " ".join(
                        f"{k}={_fv(fanout[k])}"
                        for k in (
                            "mode",
                            "peers",
                            "ok",
                            "fallback",
                            "hedged",
                            "retries",
                            "bytes",
                            "transport",
                            "fanin_bytes",
                            "fanin_errors",
                            "fanin_transport",
                        )
                        if fanout.get(k) not in (None, {})
                    )
                ]
                for domain, pp in sorted((fanout.get("per_peer") or {}).items()):
                    lines.append(
                        f"peer {domain}: " + " ".join(
                            f"{k}={pp.get(k)}"
                            for k in (
                                "result", "ms", "rows", "bytes",
                                "attempts", "hedged", "transport",
                            )
                        )
                    )
                plans.append("\n".join(lines))
            routes = st.get("device_routes")
            if routes is not None:
                # observable without a profiler (VERDICT r3 #10): where
                # each block ran and what the link actually carried
                plan_types.append("device_routes")
                plans.append(
                    " ".join(f"{k}={v}" for k, v in sorted(routes.items()))
                )

        table = pa.table({"plan_type": plan_types, "plan": plans})
        return QueryResult(
            table,
            ["plan_type", "plan"],
            stats={"elapsed_secs": round(_time.monotonic() - t0, 6), "explain": mode},
        )

    def _plan(
        self,
        sql_text: str,
        start_time: str | None,
        end_time: str | None,
        allowed_streams: set[str] | None,
        t0: float,
    ) -> LogicalPlan:
        return self._plan_ast(
            S.parse_sql(sql_text), start_time, end_time, allowed_streams, t0
        )

    def _plan_ast(
        self,
        select: S.Select,
        start_time: str | None,
        end_time: str | None,
        allowed_streams: set[str] | None,
        t0: float,
        sql_key: str | None = None,
    ) -> LogicalPlan:
        # plan cache: keyed on (sql, stream, schema fingerprint), storing
        # the plan as built — RBAC, stream resolution, API time bounds and
        # the safety rails are per-request and re-applied below on a copy
        lp = None
        cache_key = None
        cache = get_plan_cache(self.p.options) if sql_key is not None else None
        if cache is not None and select.table:
            fp = self._schema_fingerprint(select.table)
            if fp is not None:
                cache_key = ("plan", sql_key, select.table, fp)
                lp = cache.get(cache_key)
        if cache_key is not None:
            state = "hit" if lp is not None else "miss"
            QUERY_PLAN_CACHE.labels(state).inc()
            self._plan_cache_state = state
        if lp is None:
            lp = build_plan(select)
            if cache_key is not None:
                cache.put(cache_key, lp)
        if allowed_streams is not None and lp.stream not in allowed_streams:
            raise QueryError(f"unauthorized for stream {lp.stream!r}")
        self.resolve_stream(lp.stream)
        stream = self.p.streams.get(lp.stream)
        if stream is not None and stream.metadata.schema:
            lp.schema_hint = pa.schema(list(stream.metadata.schema.values()))

        if start_time and end_time:
            tr = TimeRange.parse_human_time(start_time, end_time)
            api_bounds = TimeBounds(low=tr.start, high=tr.end)
            lp.time_bounds = lp.time_bounds.intersect(api_bounds)

        # safety rails (reference: query/mod.rs:92,152-165 + :216-226)
        timeout = self.p.options.query_timeout_secs
        if timeout:
            lp.deadline = t0 + timeout
        lp.memory_limit_bytes = self.p.options.query_memory_limit_bytes
        lp.execution_batch_size = self.p.options.execution_batch_size
        return lp

    def query_stream(
        self,
        sql_text: str,
        start_time: str | None = None,
        end_time: str | None = None,
        allowed_streams: set[str] | None = None,
        on_close=None,
    ):
        """Streaming variant (reference: handlers/http/query.rs:325-407):
        returns an iterator of pyarrow Tables, emitted as the scan
        progresses, so `SELECT *` over a huge range never materializes in
        full. Row export is IO-bound, so it always runs the CPU engine —
        the device path exists for aggregation.

        `on_close` fires exactly once when the returned generator finishes
        OR is closed/abandoned mid-stream — the admission-control hook: an
        abandoned HTTP export must hand its concurrency permit back, not
        hold it until GC. (If the generator is never started, on_close
        never fires — callers keep their own idempotent backstop.)"""
        t0 = _time.monotonic()
        select = self._parse_cached(sql_text)
        if _is_composite(select) or select.explain:
            # set operations / CTEs / joins need the full result before the
            # first row can stream (and EXPLAIN emits plan rows, never a
            # scan); materialize through the normal path, one chunk out
            result = self._query_ast(select, start_time, end_time, allowed_streams, t0)

            def single():
                try:
                    yield result.table
                finally:
                    if on_close is not None:
                        on_close()

            return single()
        lp = self._plan_ast(
            select, start_time, end_time, allowed_streams, t0, sql_key=sql_text
        )
        # streaming exports are paced by the client (resp.write backpressure
        # counts as wall time); the SQL timeout would truncate every large
        # download, so it doesn't apply here — memory stays bounded by the
        # per-block emission instead
        lp.deadline = None
        scan = StreamScan(self.p, lp, hot_tier_dir=self._hot_dir(lp.stream))
        executor = QueryExecutor(lp)
        tables = scan.tables()

        def streamed():
            # explicit close so an abandoned HTTP export cancels the scan
            # pool deterministically instead of waiting for GC — and
            # releases the admission slot on the same close path
            try:
                yield from executor.execute_select_stream(tables)
            finally:
                tables.close()
                if on_close is not None:
                    on_close()

        return streamed()

    # ------------------------------------------------------- CTE / UNION

    def _query_with_ctes(
        self,
        select: S.Select,
        start_time: str | None,
        end_time: str | None,
        allowed_streams: set[str] | None,
        t0: float,
    ) -> QueryResult:
        """WITH bindings: materialize each CTE in declaration order (later
        CTEs and the main body see earlier ones), then run the body.
        Reference parity: DataFusion CTE inlining (src/query/mod.rs)."""
        import copy

        prev = getattr(self, "_cte_tables", None)
        tables = dict(prev or {})
        self._cte_tables = tables
        try:
            for name, cte_sel in select.ctes.items():
                sub = copy.deepcopy(cte_sel)
                # RBAC applies to the CTE's underlying streams, not its name
                tables[name] = self._query_ast(
                    sub, start_time, end_time, allowed_streams, t0
                ).table
            body = copy.copy(select)
            body.ctes = {}
            return self._query_ast(body, start_time, end_time, allowed_streams, t0)
        finally:
            if prev is None:
                del self._cte_tables
            else:
                self._cte_tables = prev

    def _query_union(
        self,
        select: S.Select,
        start_time: str | None,
        end_time: str | None,
        allowed_streams: set[str] | None,
        t0: float,
    ) -> QueryResult:
        """UNION [ALL]: branches execute independently (RBAC/time range per
        branch), match by position, fold left with distinct at each non-ALL
        step (standard SQL associativity); the hoisted ORDER BY/LIMIT apply
        to the combined result."""
        import copy

        from parseable_tpu.query.executor import QueryExecutor as _QE
        from parseable_tpu.utils.arrowutil import adapt_batch, merge_schemas

        head = copy.copy(select)
        head.set_ops = []
        head.order_by = []
        head.limit = None
        head.offset = None
        acc = self._query_ast(head, start_time, end_time, allowed_streams, t0).table
        n_cols = acc.num_columns
        out_names = acc.column_names

        def distinct(t: pa.Table) -> pa.Table:
            return t.group_by(t.column_names, use_threads=False).aggregate([])

        for is_all, branch in select.set_ops:
            bt = self._query_ast(
                copy.copy(branch), start_time, end_time, allowed_streams, t0
            ).table
            if bt.num_columns != n_cols:
                raise QueryError(
                    f"UNION branches have {n_cols} vs {bt.num_columns} columns"
                )
            bt = bt.rename_columns(out_names)
            schema = merge_schemas([acc.schema, bt.schema])
            batches = [adapt_batch(schema, b) for t in (acc, bt) for b in t.to_batches()]
            acc = pa.Table.from_batches(batches, schema=schema)
            if not is_all:
                acc = distinct(acc)

        if select.order_by or select.limit is not None or select.offset is not None:
            from parseable_tpu.query.planner import LogicalPlan, TimeBounds

            shim = S.Select(
                items=[S.SelectItem(S.Star())],
                table="__union",
                order_by=select.order_by,
                limit=select.limit,
                offset=select.offset,
            )
            lp = LogicalPlan(
                select=shim, stream="__union", time_bounds=TimeBounds(),
                constraints=[], needed_columns=None,
            )
            acc = _QE(lp)._order_limit(acc)
        elapsed = _time.monotonic() - t0
        return QueryResult(
            acc,
            acc.column_names,
            {"elapsed_secs": round(elapsed, 6), "engine": self.engine, "set_op": "union"},
        )

    def _query_cte_table(self, select: S.Select, table: pa.Table, t0: float) -> QueryResult:
        """FROM <cte>: run the remaining SELECT over the materialized CTE
        output with the CPU executor (time bounds were applied when the CTE
        scanned its streams; they do not re-apply to derived rows)."""
        import copy

        from parseable_tpu.query.planner import TimeBounds, plan as build_plan

        sel = copy.deepcopy(select)  # joins/subqueries were routed to _query_multi already
        lp = build_plan(sel)
        lp.time_bounds = TimeBounds()
        timeout = self.p.options.query_timeout_secs
        if timeout:
            lp.deadline = t0 + timeout
        lp.memory_limit_bytes = self.p.options.query_memory_limit_bytes
        executor = QueryExecutor(lp)
        out = executor.execute(iter([table]))
        elapsed = _time.monotonic() - t0
        return QueryResult(
            out,
            out.column_names,
            {"elapsed_secs": round(elapsed, 6), "engine": "cpu", "cte": select.table},
        )

    # ------------------------------------------------------- multi-stream

    def _query_multi(
        self,
        select: S.Select,
        start_time: str | None,
        end_time: str | None,
        allowed_streams: set[str] | None,
        t0: float,
    ) -> QueryResult:
        """Joins + subqueries (reference gets these from DataFusion;
        query/multi.py documents the design). The API time range applies to
        every stream scan; the WHERE tree applies post-join."""
        import copy

        from parseable_tpu.query import multi as M

        sel = copy.deepcopy(select)

        # bounded nesting: run_select re-enters this method for nested
        # subqueries, so the depth lives on the session, not the recursion
        depth = getattr(self, "_multi_depth", 0)
        if depth > 4:
            raise QueryError("subqueries nested too deeply")
        self._multi_depth = depth + 1
        try:
            return self._query_multi_inner(
                sel, start_time, end_time, allowed_streams, t0, M
            )
        finally:
            self._multi_depth = depth

    def _query_multi_inner(
        self,
        sel: S.Select,
        start_time: str | None,
        end_time: str | None,
        allowed_streams: set[str] | None,
        t0: float,
        M,
    ) -> QueryResult:
        # RBAC over every referenced stream, before anything executes
        # (CTE names are session-local bindings, not streams)
        cte_tables = getattr(self, "_cte_tables", None) or {}
        streams = collect_streams(sel) - set(cte_tables)
        if allowed_streams is not None:
            for name in streams:
                if name not in allowed_streams:
                    raise QueryError(f"unauthorized for stream {name!r}")

        def run_select(sub: S.Select) -> pa.Table:
            # share the outer query's t0 so all subqueries burn the SAME
            # timeout window, not a fresh one each
            return self._query_ast(sub, start_time, end_time, allowed_streams, t0).table

        sel.where = M.resolve_subqueries(sel.where, run_select)
        sel.having = M.resolve_subqueries(sel.having, run_select)
        sel.items = [
            S.SelectItem(M.resolve_subqueries(i.expr, run_select), i.alias)
            for i in sel.items
        ]

        if not sel.joins:
            # subqueries resolved; the remainder is a single-stream query
            return self._query_ast(sel, start_time, end_time, allowed_streams, t0)

        # --- materialize each side through the normal single-stream scan ---
        refs = [(sel.table, sel.table_alias or sel.table)] + [
            (j.table, j.alias or j.table) for j in sel.joins
        ]
        exprs = [sel.where, sel.having, *(i.expr for i in sel.items)]
        exprs += [g for g in sel.group_by] + [o.expr for o in sel.order_by]
        exprs += [j.on for j in sel.joins]
        needed_all = set()
        needed_by_alias: dict[str, set[str]] = {a: set() for _, a in refs}
        star = any(isinstance(i.expr, S.Star) for i in sel.items)
        for e in exprs:
            for col in _qualified_refs(e):
                if col.table is not None and col.table in needed_by_alias:
                    needed_by_alias[col.table].add(col.name)
                elif col.table is None:
                    needed_all.add(col.name)

        # ownership from the stream SCHEMAS, not materialized columns — an
        # empty scan fabricates needed columns (_empty_like) and would make
        # ambiguity detection data-dependent
        owner_of: dict[str, str] = {}
        sides: list[tuple[str, pa.Table]] = []
        for name, alias in refs:
            needed = None if star else (needed_by_alias[alias] | needed_all)
            if name in cte_tables:
                t = cte_tables[name]
                if needed is not None:
                    keep = [c for c in t.column_names if c in needed]
                    t = t.select(keep)
                sides.append((alias, t))
                for c in t.column_names:
                    owner_of[c] = "__ambiguous__" if c in owner_of else alias
                continue
            self.resolve_stream(name)
            t = self._materialize_stream(name, needed, start_time, end_time, t0)
            sides.append((alias, t))
            stream = self.p.streams.get(name)
            schema_cols = (
                set(stream.metadata.schema.keys())
                if stream is not None and stream.metadata.schema
                else set(t.column_names)
            )
            for c in schema_cols:
                owner_of[c] = "__ambiguous__" if c in owner_of else alias

        # residual ON conditions evaluate against the alias-qualified join
        # output — bare columns in them must be qualified first
        sel.joins = [
            S.Join(j.table, j.alias, j.kind, M.qualify_unqualified(j.on, owner_of))
            for j in sel.joins
        ]
        joined = M.execute_join(
            sides[0],
            list(zip(sel.joins, [t for _, t in sides[1:]])),
            memory_limit=self.p.options.query_memory_limit_bytes,
        )

        # bare columns resolve by schema ownership; then run the remaining
        # SELECT over the joined table with the standard executor
        sel.where = M.qualify_unqualified(sel.where, owner_of)
        sel.having = M.qualify_unqualified(sel.having, owner_of)
        sel.items = [
            S.SelectItem(M.qualify_unqualified(i.expr, owner_of), i.alias) for i in sel.items
        ]
        sel.group_by = [M.qualify_unqualified(g, owner_of) for g in sel.group_by]
        sel.order_by = [
            S.OrderItem(M.qualify_unqualified(o.expr, owner_of), o.desc) for o in sel.order_by
        ]
        sel.joins = []
        sel.table = "__joined"
        lp = build_plan(sel)
        lp.time_bounds = TimeBounds()  # already applied per stream scan
        timeout = self.p.options.query_timeout_secs
        if timeout:
            lp.deadline = t0 + timeout
        lp.memory_limit_bytes = self.p.options.query_memory_limit_bytes
        executor = QueryExecutor(lp)
        table = executor.execute(iter([joined]))
        elapsed = _time.monotonic() - t0
        QUERY_EXECUTE_TIME.labels(",".join(sorted(streams))).observe(elapsed)
        stats = {
            "elapsed_secs": round(elapsed, 6),
            "engine": "cpu",
            "joined_streams": sorted(streams),
        }
        self._maybe_log_slow(sel, elapsed, stats)
        return QueryResult(table, table.column_names, stats)

    def _materialize_stream(
        self,
        name: str,
        needed: set[str] | None,
        start_time: str | None,
        end_time: str | None,
        t0: float,
    ) -> pa.Table:
        """One join side: full scan of a stream within the API time range,
        column-pruned, bounded by the memory cap."""
        from parseable_tpu import DEFAULT_TIMESTAMP_KEY

        sub = S.Select(items=[S.SelectItem(S.Star())], table=name)
        lp = self._plan_ast(sub, start_time, end_time, None, t0)
        if needed is not None:
            lp.needed_columns = needed | {DEFAULT_TIMESTAMP_KEY}
        scan = StreamScan(self.p, lp, hot_tier_dir=self._hot_dir(name))
        tables = scan.tables()
        try:
            return QueryExecutor(lp).execute(tables)
        finally:
            tables.close()

    def _hot_dir(self, stream: str):
        return (
            self.p.hot_tier.local_dir_for_scan(stream)
            if getattr(self.p, "hot_tier", None) is not None
            else self.p.options.hot_tier_storage_path
        )

    def _execute(self, lp: LogicalPlan, scan: StreamScan) -> tuple[QueryResult, _TimedIter]:
        timer = _TimedIter(iter(()))
        # count(*) fast path off manifest row counts, only when every
        # overlapping file lies fully inside the time bounds
        if lp.count_star_only:
            fast = self._try_manifest_count(lp, scan)
            if fast is not None:
                name = lp.select.items[0].alias or "count(*)"
                table = pa.table({name: pa.array([fast], pa.int64())})
                return QueryResult(table, [name], {"fast_path": "manifest_count"}), timer

        # partial-aggregate result cache: a repeated aggregate over an
        # unchanged manifest set skips the scan — only HAVING/projection/
        # ORDER BY re-run over the cached interim. Eligibility requires the
        # query range to stay clear of the staging window (staging rows are
        # invisible to the manifest fingerprint, and concurrent ingest
        # would make a cached answer stale the moment it was stored).
        from parseable_tpu.query.partials import (
            get_result_cache,
            manifest_fingerprint,
            plan_fingerprint,
        )

        self._result_cache_state = None
        result_cache = get_result_cache(self.p.options)
        result_key = None
        if (
            result_cache is not None
            and lp.is_aggregate
            and not scan._within_staging_window()
        ):
            result_key = (
                lp.stream,
                manifest_fingerprint(scan.manifest_files()),
                plan_fingerprint(lp, self.engine),
            )
            interim = result_cache.get(result_key)
            if interim is not None:
                self._result_cache_state = "hit"
                QUERY_CACHE_HIT.labels(lp.stream).inc()
                ex = QueryExecutor(lp)
                _agg, rewritten, _names = ex.build_aggregator()
                table = ex.finalize_from_interim(interim, rewritten)
                return (
                    QueryResult(table, table.column_names, {"result_cache": "hit"}),
                    timer,
                )
            self._result_cache_state = "miss"

        # distributed partial-aggregate pushdown (query/fanout.py): on a
        # dedicated querier, scatter partializable GROUP BY aggregates to
        # live ingestors — each scans its own staging + owned manifests and
        # answers with one partial table — instead of pulling raw staging
        # windows and scanning everything here. prepare() launches the
        # fan-out (overlapping the local scan) and re-scopes `scan` to
        # unowned/historical files; collection happens inside the
        # executor's merge via partials_source. Falls through to the
        # central path when ineligible (non-aggregate plans, no tagged
        # live peers, knob off).
        dist = None
        from parseable_tpu.config import Mode as _Mode

        exec_sql = getattr(self, "_exec_sql", None)
        if (
            self.p.options.mode == _Mode.QUERY
            and self.p.options.query_pushdown
            and lp.is_aggregate
            and exec_sql is not None
        ):
            from parseable_tpu.query import fanout as FO

            dist = FO.prepare(self.p, lp, scan, exec_sql)
        if dist is not None:
            # the distributed merge is host-side regardless of the session
            # engine: peer partials fold into the CPU two-phase funnel
            executor = QueryExecutor(lp)
            executor.partials_source = dist.collect
            if result_key is not None:
                def _dist_sink(interim, _key=result_key, _cache=result_cache, _scan=scan):
                    with _scan._stats_lock:
                        errors = _scan.stats.scan_errors
                    if errors == 0:
                        _cache.put(_key, interim)

                executor.interim_sink = _dist_sink
            timer = _TimedIter(scan.tables())
            try:
                table = executor.execute(timer)
            finally:
                timer.close()
            self._fanout_stats = dist.stats
            return QueryResult(table, table.column_names, {}), timer

        if self.engine == "tpu":
            from parseable_tpu.query.executor_tpu import TpuQueryExecutor

            if (
                lp.ts_artificial
                and lp.time_bounds.low is None
                and lp.time_bounds.high is None
                and lp.needed_columns is not None
            ):
                # no bounds and no expression touches the timestamp: skip
                # encoding/shipping it (the column is ~a third of a typical
                # scan's transfer bytes)
                from parseable_tpu import DEFAULT_TIMESTAMP_KEY

                lp.needed_columns.discard(DEFAULT_TIMESTAMP_KEY)
            self._set_scan_time_hint(lp, scan)
            executor: QueryExecutor = TpuQueryExecutor(lp, self.p.options)
            executor.source_loader = scan.read_source
            # the scan's ordered stub list drives query-aware prefetch:
            # block i+1 ships from the enccache while block i aggregates
            executor.prefetch_scan = scan
        else:
            executor = QueryExecutor(lp)
        if result_key is not None:
            # store the merged interim the moment the engine produces it —
            # but never a partial one (scan_errors means files were dropped)
            def _sink(interim, _key=result_key, _cache=result_cache, _scan=scan):
                with _scan._stats_lock:
                    errors = _scan.stats.scan_errors
                if errors == 0:
                    _cache.put(_key, interim)

            executor.interim_sink = _sink
        # both engines consume the scan's parallel fetch+decode pipeline
        # (provider.py): the pool overlaps object-store GETs and parquet
        # decode with engine compute, bounded by P_SCAN_INFLIGHT_BYTES —
        # this replaced the TPU path's single-worker depth-3 prefetcher
        timer = _TimedIter(scan.tables())
        try:
            table = executor.execute(timer)
        finally:
            timer.close()
        stats = {}
        routes = getattr(executor, "route_stats", None)
        if routes is not None:
            # route observability (EXPLAIN ANALYZE surfaces this): where
            # each block ran + actual transfer bytes
            stats["device_routes"] = dict(routes)
            # the phase clock beside the counters (stages.execute reads it)
            self._execute_clock = routes
        return QueryResult(table, table.column_names, stats), timer

    @staticmethod
    def _set_scan_time_hint(lp: LogicalPlan, scan: StreamScan) -> None:
        """Overall scan time range from per-file p_timestamp stats — lets the
        TPU engine pre-size time-bin group capacities exactly (a loose hint
        inflates the dense group space and with it the scatter cost)."""
        from datetime import datetime

        from parseable_tpu import DEFAULT_TIMESTAMP_KEY

        lo_ms = hi_ms = None
        for f in scan.manifest_files():
            for col in f.columns:
                if col.name == DEFAULT_TIMESTAMP_KEY and col.stats is not None:
                    lo_ms = col.stats.min if lo_ms is None else min(lo_ms, col.stats.min)
                    hi_ms = col.stats.max if hi_ms is None else max(hi_ms, col.stats.max)
        if lo_ms is None:
            return
        lo = datetime.fromtimestamp(lo_ms / 1000, UTC)
        hi = datetime.fromtimestamp(hi_ms / 1000, UTC)
        if lp.time_bounds.low is not None:
            lo = max(lo, lp.time_bounds.low)
        if lp.time_bounds.high is not None:
            hi = min(hi, lp.time_bounds.high)
        if lo <= hi:
            lp.scan_time_hint = (lo, hi)

    def _try_manifest_count(self, lp: LogicalPlan, scan: StreamScan) -> int | None:
        from datetime import datetime

        from parseable_tpu import DEFAULT_TIMESTAMP_KEY

        tb = lp.time_bounds
        total = 0
        partial = False
        for f in scan.manifest_files():
            lo = hi = None
            for col in f.columns:
                if col.name == DEFAULT_TIMESTAMP_KEY and col.stats is not None:
                    lo = datetime.fromtimestamp(col.stats.min / 1000, UTC)
                    hi = datetime.fromtimestamp(col.stats.max / 1000, UTC)
            if lo is None:
                partial = True
                break
            inside = (tb.low is None or lo >= tb.low) and (tb.high is None or hi < tb.high)
            if not inside:
                partial = True
                break
            total += f.num_rows
        if partial:
            return None
        # staging rows within range still need counting
        stream = self.p.streams.get(lp.stream)
        if stream is not None and scan._within_staging_window():
            for t in scan.staging_tables():
                t = scan._apply_time_filter(t)
                total += t.num_rows
        return total
