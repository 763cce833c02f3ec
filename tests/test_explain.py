"""EXPLAIN [ANALYZE] (reference: DataFusion explain via the session,
/root/reference/src/query/mod.rs:212-276)."""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from parseable_tpu import DEFAULT_TIMESTAMP_KEY
from parseable_tpu.query.session import QueryError, QuerySession


@pytest.fixture()
def loaded(parseable):
    from datetime import datetime, timedelta

    from parseable_tpu.event import Event

    p = parseable
    stream = p.create_stream_if_not_exists("logs")
    rng = np.random.default_rng(5)
    base = datetime(2024, 5, 1)
    n = 5_000
    t = pa.table(
        {
            DEFAULT_TIMESTAMP_KEY: pa.array(
                [base + timedelta(milliseconds=int(i)) for i in range(n)],
                pa.timestamp("ms"),
            ),
            "host": pa.array([f"h{int(x)}" for x in rng.integers(0, 8, n)]),
            "bytes": pa.array(rng.random(n) * 100),
        }
    )
    for b in t.to_batches():
        Event(
            stream_name="logs", rb=b, origin_size=1, is_first_event=True,
            parsed_timestamp=base,
        ).process(stream, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()
    return p


def test_explain_plan_rows(loaded):
    res = QuerySession(loaded, engine="cpu").query(
        "EXPLAIN SELECT host, count(*) c FROM logs "
        "WHERE bytes > 50 GROUP BY host ORDER BY c DESC LIMIT 3"
    )
    rows = {r["plan_type"]: r["plan"] for r in res.to_json_rows()}
    assert "logical_plan" in rows and "physical_plan" in rows
    lp = rows["logical_plan"]
    assert "Limit: 3" in lp and "Sort: c DESC" in lp
    assert "Aggregate: groupBy=[host]" in lp
    assert "Filter:" in lp and "TableScan: logs" in lp
    assert "stream=logs" in rows["physical_plan"]
    assert "two-phase" in rows["physical_plan"]
    assert "top-k" in rows["physical_plan"]


def test_explain_does_not_execute(loaded):
    res = QuerySession(loaded, engine="cpu").query("EXPLAIN SELECT host FROM logs")
    assert "analyze" not in {r["plan_type"] for r in res.to_json_rows()}


def test_explain_analyze_executes_and_reports(loaded):
    res = QuerySession(loaded, engine="cpu").query(
        "EXPLAIN ANALYZE SELECT host, count(*) c FROM logs GROUP BY host"
    )
    rows = {r["plan_type"]: r["plan"] for r in res.to_json_rows()}
    assert "rows_out=8" in rows["analyze"]
    assert "rows_scanned=5000" in rows["analyze"]


def test_explain_unauthorized_stream_blocked(loaded):
    with pytest.raises(QueryError, match="unauthorized"):
        QuerySession(loaded, engine="cpu").query(
            "EXPLAIN SELECT host FROM logs", allowed_streams={"other"}
        )


def test_explain_composite_join(loaded):
    res = QuerySession(loaded, engine="cpu").query(
        "EXPLAIN SELECT a.host FROM logs a JOIN logs b ON a.host = b.host"
    )
    rows = {r["plan_type"]: r["plan"] for r in res.to_json_rows()}
    assert "Join[inner]: logs" in rows["logical_plan"]
    assert "CompositeExec" in rows["physical_plan"]


def test_explain_union_and_cte(loaded):
    res = QuerySession(loaded, engine="cpu").query(
        "EXPLAIN WITH h AS (SELECT host FROM logs) "
        "SELECT host FROM h UNION ALL SELECT host FROM logs"
    )
    lp = {r["plan_type"]: r["plan"] for r in res.to_json_rows()}["logical_plan"]
    assert "CTE: h" in lp and "Union" in lp


def test_column_named_explain_still_works():
    from parseable_tpu.query.executor import QueryExecutor
    from parseable_tpu.query.planner import plan as build_plan
    from parseable_tpu.query.sql import parse_sql

    t = pa.table({"explain": pa.array([1, 2])})
    out = (
        QueryExecutor(build_plan(parse_sql("SELECT explain FROM t")))
        .execute(iter([t]))
        .to_pylist()
    )
    assert out == [{"explain": 1}, {"explain": 2}]


def test_explain_analyze_surfaces_device_routes(loaded):
    """VERDICT r4 #10: EXPLAIN ANALYZE on the TPU engine reports per-block
    routes (device warm/cold, fallback CPU; `cpu_adaptive` is a constant 0
    the benchmark's readers still sum) and actual transfer bytes, observable
    without a profiler. No routing prices a link, so no `link_profile` row."""
    sess = QuerySession(loaded, engine="tpu")
    r = sess.query(
        "EXPLAIN ANALYZE SELECT host, count(*) c, sum(bytes) s FROM logs GROUP BY host",
        "2024-05-01T00:00:00Z",
        "2024-05-02T00:00:00Z",
    )
    rows = {x["plan_type"]: x["plan"] for x in r.to_json_rows()}
    assert "device_routes" in rows, rows
    routes = dict(kv.split("=") for kv in rows["device_routes"].split())
    assert set(routes) == {
        "device_warm", "device_cold", "cpu_adaptive", "cpu_fallback",
        "h2d_bytes", "d2h_bytes",
        # program-cache accounting (dlint): XLA builds/reuses per query and
        # rebuilt-key recompiles — 0 recompiles is the steady-state contract
        "programs_built", "programs_reused", "recompiles",
        # the block-local merge (ISSUE 28): where it ran, entries, survivors
        "merge_device", "merge_host", "merge_entries", "merge_survivors",
        # aggregates over expressions (ISSUE 30): where they were evaluated;
        # blocks whose encoding was declined for a column
        "expr_aggs_device", "expr_aggs_host", "encode_declined",
        # transfers of small operands the dense block loop made (ISSUE 31)
        "operand_puts",
        # the additive reduction's route in each folded block (ISSUE 33)
        "fold_onehot_blocks", "fold_factored_blocks", "fold_scatter_blocks",
        # the min / max fold's route, and where a time bin over a column off
        # the block's origin was computed (ISSUE 34)
        "fold_minmax_scatter_blocks",
        "timebin_offorigin_device_blocks", "timebin_offorigin_host_blocks",
    }
    assert int(routes["recompiles"]) == 0
    total_blocks = sum(
        int(routes[k])
        for k in ("device_warm", "device_cold", "cpu_adaptive", "cpu_fallback")
    )
    assert total_blocks >= 1  # the scan dispatched at least one block
    assert int(routes["cpu_adaptive"]) == 0
    assert "link_profile" not in rows


def test_explain_analyze_cpu_engine_has_no_device_routes(loaded):
    sess = QuerySession(loaded, engine="cpu")
    r = sess.query(
        "EXPLAIN ANALYZE SELECT count(*) c FROM logs",
        "2024-05-01T00:00:00Z",
        "2024-05-02T00:00:00Z",
    )
    rows = {x["plan_type"]: x["plan"] for x in r.to_json_rows()}
    assert "device_routes" not in rows
