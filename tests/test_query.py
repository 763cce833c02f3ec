"""Query engine tests: planning, pruning, CPU execution, TPU parity.

The TPU executor runs on the virtual CPU backend here (conftest forces
JAX_PLATFORMS=cpu); kernel semantics are identical on real TPU."""

from datetime import UTC, datetime, timedelta

import pyarrow as pa
import pytest

from parseable_tpu import DEFAULT_TIMESTAMP_KEY
from parseable_tpu.catalog import Column as CatColumn
from parseable_tpu.catalog import ManifestFile, TypedStatistics
from parseable_tpu.query.executor import QueryExecutor
from parseable_tpu.query.executor_tpu import TpuQueryExecutor
from parseable_tpu.query.planner import (
    extract_time_bounds,
    plan as build_plan,
    prune_file,
)
from parseable_tpu.query.session import QuerySession
from parseable_tpu.query.sql import parse_sql


BASE = datetime(2024, 5, 1, 10, 0)


def make_table(n=100):
    ts = [BASE + timedelta(seconds=i) for i in range(n)]
    status = [200 if i % 3 else 500 for i in range(n)]
    host = [f"web-{i % 4}" for i in range(n)]
    latency = [float(i % 50) for i in range(n)]
    msg = [f"request {i} {'error timeout' if i % 7 == 0 else 'ok'}" for i in range(n)]
    return pa.table(
        {
            DEFAULT_TIMESTAMP_KEY: pa.array(ts, pa.timestamp("ms")),
            "status": pa.array(status, pa.float64()),
            "host": pa.array(host),
            "latency": pa.array(latency),
            "msg": pa.array(msg),
        }
    )


def run_cpu(sql, tables):
    lp = build_plan(parse_sql(sql))
    return QueryExecutor(lp).execute(iter(tables))


def run_tpu(sql, tables):
    lp = build_plan(parse_sql(sql))
    return TpuQueryExecutor(lp).execute(iter(tables))


def as_dict(table: pa.Table, key_cols, val_col):
    out = {}
    for row in table.to_pylist():
        key = tuple(row[k] for k in key_cols)
        out[key] = row[val_col]
    return out


# --------------------------------------------------------------- time bounds


def test_extract_time_bounds():
    q = parse_sql(
        "SELECT * FROM t WHERE p_timestamp >= '2024-05-01T00:00:00Z' AND p_timestamp < '2024-05-02T00:00:00Z'"
    )
    tb = extract_time_bounds(q.where)
    assert tb.low == datetime(2024, 5, 1, tzinfo=UTC)
    assert tb.high == datetime(2024, 5, 2, tzinfo=UTC)


def test_time_bounds_ignore_or():
    q = parse_sql("SELECT * FROM t WHERE p_timestamp >= '2024-05-01T00:00:00Z' OR a = 1")
    tb = extract_time_bounds(q.where)
    assert tb.low is None and tb.high is None


# ------------------------------------------------------------------- pruning


def _entry(lo, hi, col="status"):
    return ManifestFile(
        file_path="f.parquet",
        num_rows=10,
        file_size=100,
        columns=[CatColumn(name=col, stats=TypedStatistics("Float", lo, hi))],
    )


def test_prune_by_stats():
    lp = build_plan(parse_sql("SELECT count(*) FROM t WHERE status = 500"))
    assert prune_file(_entry(100, 600), lp.constraints)
    assert not prune_file(_entry(100, 400), lp.constraints)
    lp2 = build_plan(parse_sql("SELECT count(*) FROM t WHERE status > 500"))
    assert not prune_file(_entry(100, 500), lp2.constraints)
    assert prune_file(_entry(100, 501), lp2.constraints)


# --------------------------------------------------------------- CPU engine


def test_count_star_filter():
    t = make_table()
    out = run_cpu("SELECT count(*) FROM t WHERE status = 500", [t])
    expected = sum(1 for i in range(100) if i % 3 == 0)
    assert out.to_pylist()[0]["count(*)"] == expected


def test_group_by_count():
    t = make_table()
    out = run_cpu("SELECT host, count(*) AS c FROM t GROUP BY host ORDER BY host", [t])
    rows = out.to_pylist()
    assert len(rows) == 4
    assert rows[0]["host"] == "web-0" and rows[0]["c"] == 25


def test_group_by_multiple_aggs():
    t = make_table()
    out = run_cpu(
        "SELECT host, sum(latency) s, min(latency) mn, max(latency) mx, avg(latency) a "
        "FROM t GROUP BY host ORDER BY host",
        [t],
    )
    rows = out.to_pylist()
    lat = [float(i % 50) for i in range(100)]
    hosts = [f"web-{i % 4}" for i in range(100)]
    exp_sum = sum(v for v, h in zip(lat, hosts) if h == "web-1")
    assert rows[1]["s"] == pytest.approx(exp_sum)
    assert rows[1]["a"] == pytest.approx(exp_sum / 25)


def test_like_filter():
    t = make_table()
    out = run_cpu("SELECT count(*) c FROM t WHERE msg LIKE '%error%'", [t])
    expected = sum(1 for i in range(100) if i % 7 == 0)
    assert out.to_pylist()[0]["c"] == expected


def test_date_bin_group():
    t = make_table()
    out = run_cpu(
        "SELECT date_bin(interval '1 minute', p_timestamp) b, count(*) c FROM t GROUP BY b ORDER BY b",
        [t],
    )
    rows = out.to_pylist()
    assert len(rows) == 2  # 100 seconds spans 2 minute-bins
    assert rows[0]["c"] == 60 and rows[1]["c"] == 40


def test_order_limit_offset():
    t = make_table()
    out = run_cpu("SELECT latency FROM t ORDER BY latency DESC LIMIT 3 OFFSET 1", [t])
    vals = [r["latency"] for r in out.to_pylist()]
    assert vals == [49.0, 48.0, 48.0]  # two of each value; offset skips one 49


def test_distinct():
    t = make_table()
    out = run_cpu("SELECT DISTINCT host FROM t", [t])
    assert sorted(r["host"] for r in out.to_pylist()) == ["web-0", "web-1", "web-2", "web-3"]


def test_count_distinct():
    t = make_table()
    out = run_cpu("SELECT count(DISTINCT host) c FROM t", [t])
    assert out.to_pylist()[0]["c"] == 4


def test_having():
    t = make_table()
    out = run_cpu("SELECT host, count(*) c FROM t GROUP BY host HAVING count(*) > 24", [t])
    assert len(out.to_pylist()) == 4  # all hosts have 25
    out2 = run_cpu("SELECT status, count(*) c FROM t GROUP BY status HAVING count(*) > 40", [t])
    assert len(out2.to_pylist()) == 1  # only status=200


def test_case_expression():
    t = make_table()
    out = run_cpu(
        "SELECT CASE WHEN status = 500 THEN 'err' ELSE 'ok' END k, count(*) c FROM t GROUP BY k ORDER BY k",
        [t],
    )
    rows = out.to_pylist()
    assert rows[0]["k"] == "err"


def test_multi_table_merge():
    t = make_table()
    out = run_cpu("SELECT count(*) c FROM t", [t.slice(0, 50), t.slice(50)])
    assert out.to_pylist()[0]["c"] == 100


# ------------------------------------------------------------- TPU parity


TPU_QUERIES = [
    "SELECT count(*) c FROM t WHERE status = 500",
    "SELECT count(*) c FROM t WHERE host = 'web-1' AND status = 200",
    "SELECT host, count(*) c FROM t GROUP BY host",
    "SELECT host, sum(latency) s, min(latency) mn, max(latency) mx, avg(latency) a FROM t GROUP BY host",
    "SELECT status, count(*) c FROM t GROUP BY status",
    "SELECT host, status, count(*) c FROM t GROUP BY host, status",
    "SELECT date_bin(interval '1 minute', p_timestamp) b, count(*) c FROM t GROUP BY b",
    "SELECT date_bin(interval '30s', p_timestamp) b, status, count(*) c FROM t GROUP BY b, status",
    "SELECT count(*) c FROM t WHERE msg LIKE '%error%'",
    "SELECT host, count(*) c FROM t WHERE msg LIKE '%error%' GROUP BY host",
    "SELECT count(*) c FROM t WHERE latency > 25 AND latency <= 40",
    "SELECT count(*) c FROM t WHERE host IN ('web-1', 'web-2')",
    "SELECT count(*) c FROM t WHERE host = 'web-1' OR status = 500",
    "SELECT count(latency) c FROM t GROUP BY host",
    "SELECT host, count(*) c FROM t GROUP BY host ORDER BY c DESC LIMIT 2",
]


@pytest.mark.parametrize("sql", TPU_QUERIES)
def test_tpu_matches_cpu(sql):
    t = make_table()
    tables = [t.slice(0, 37), t.slice(37, 41), t.slice(78)]
    cpu = run_cpu(sql, tables)
    tpu = run_tpu(sql, tables)
    cpu_rows = sorted(map(tuple_sorted, cpu.to_pylist()))
    tpu_rows = sorted(map(tuple_sorted, tpu.to_pylist()))
    assert len(cpu_rows) == len(tpu_rows), f"row count mismatch for {sql}"
    for cr, tr in zip(cpu_rows, tpu_rows):
        assert len(cr) == len(tr)
        for a, b in zip(cr, tr):
            if isinstance(a, float) and isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-4), sql
            else:
                assert a == b, sql


def tuple_sorted(row: dict):
    return tuple(row[k] for k in sorted(row))


def test_tpu_nulls_in_group_and_agg():
    t = pa.table(
        {
            DEFAULT_TIMESTAMP_KEY: pa.array([BASE] * 6, pa.timestamp("ms")),
            "host": pa.array(["a", "a", None, "b", None, "b"]),
            "v": pa.array([1.0, None, 3.0, 4.0, 5.0, None]),
        }
    )
    sql = "SELECT host, count(*) c, count(v) cv, sum(v) s FROM t GROUP BY host"
    cpu = run_cpu(sql, [t]).to_pylist()
    tpu = run_tpu(sql, [t]).to_pylist()
    assert sorted(map(tuple_sorted, cpu)) == sorted(map(tuple_sorted, tpu))


def test_tpu_fallback_unsupported():
    # an aggregate over a function call is no device program: the CPU engine
    # answers the whole query, every table counted and the reason kept (an
    # arithmetic argument, this test's old query, now folds on the device:
    # tests/test_expr_aggregates_tpu.py)
    t = make_table()
    sql = "SELECT host, count(upper(msg)) s FROM t GROUP BY host"
    cpu = run_cpu(sql, [t, t.slice(0, 10)]).to_pylist()
    ex = TpuQueryExecutor(build_plan(parse_sql(sql)))
    tpu = ex.execute(iter([t, t.slice(0, 10)])).to_pylist()
    assert cpu and sorted(map(tuple_sorted, cpu)) == sorted(map(tuple_sorted, tpu))
    assert ex.route_stats["cpu_fallback"] == 2 and "upper" in ex.route_stats["cpu_fallback_reason"]


# ------------------------------------------------------------- full session


def test_session_end_to_end(parseable):
    from parseable_tpu.event.json_format import JsonEvent

    p = parseable
    stream = p.create_stream_if_not_exists("web")
    records = [
        {"host": f"h{i % 3}", "status": 200 if i % 4 else 500, "msg": f"m{i}"}
        for i in range(200)
    ]
    ev = JsonEvent(records, "web").into_event(stream.metadata)
    ev.process(stream, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()

    for engine in ("cpu", "tpu"):
        sess = QuerySession(p, engine=engine)
        res = sess.query("SELECT host, count(*) c FROM web GROUP BY host ORDER BY host")
        rows = res.to_json_rows()
        assert [r["c"] for r in rows] == [67, 67, 66]

    # count fast path off manifests
    sess = QuerySession(p, engine="cpu")
    res = sess.query("SELECT count(*) FROM web")
    assert res.to_json_rows()[0]["count(*)"] == 200
    assert res.stats.get("fast_path") == "manifest_count"


def test_session_time_range_prunes(parseable):
    from parseable_tpu.event.json_format import JsonEvent

    p = parseable
    stream = p.create_stream_if_not_exists("tr")
    ev = JsonEvent([{"a": 1}], "tr").into_event(stream.metadata)
    ev.process(stream, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()
    sess = QuerySession(p, engine="cpu")
    res = sess.query(
        "SELECT count(*) FROM tr", start_time="2000-01-01T00:00:00Z", end_time="2000-01-02T00:00:00Z"
    )
    assert res.to_json_rows()[0]["count(*)"] == 0


def test_stddev_var_aggregates(parseable):
    """stddev/var (sample, n-1): exact on the CPU engine; the TPU path runs
    on device (centered-M2 accumulation, round-4 VERDICT #3) and agrees to
    f32 accuracy."""
    import statistics

    from parseable_tpu.event.json_format import JsonEvent

    p = parseable
    s = p.create_stream_if_not_exists("sd")
    vals = [float(i * i % 17) for i in range(60)]
    ev = JsonEvent([{"v": v} for v in vals], "sd").into_event(s.metadata)
    ev.process(s, commit_schema=p.commit_schema)
    for engine, tol in (("cpu", 1e-6), ("tpu", 1e-4)):
        r = QuerySession(p, engine=engine).query("SELECT stddev(v) sd, var(v) vr FROM sd")
        row = r.to_json_rows()[0]
        assert abs(row["sd"] - statistics.stdev(vals)) < tol * max(1.0, statistics.stdev(vals))
        assert abs(row["vr"] - statistics.variance(vals)) < tol * max(1.0, statistics.variance(vals))


def test_legacy_prefix_listing_fallback(parseable):
    """Parquet uploaded without manifests (pre-catalog deployments) is
    still queryable via prefix listing (reference:
    listing_table_builder.rs:41-147)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    import io
    from datetime import UTC, datetime

    p = parseable
    p.create_stream_if_not_exists("legacyq")
    ts = datetime(2024, 5, 1, 10, 0, tzinfo=UTC)
    t = pa.table(
        {
            DEFAULT_TIMESTAMP_KEY: pa.array([ts.replace(tzinfo=None)] * 20, pa.timestamp("ms")),
            "n": pa.array([float(i) for i in range(20)]),
        }
    )
    buf = io.BytesIO()
    pq.write_table(t, buf)
    # drop the parquet straight into the store with NO manifest/snapshot
    p.storage.put_object(
        "legacyq/date=2024-05-01/hour=10/minute=00/old.data.parquet", buf.getvalue()
    )
    sess = QuerySession(p, engine="cpu")
    r = sess.query(
        "SELECT count(*) c, sum(n) s FROM legacyq",
        start_time="2024-05-01T09:00:00Z",
        end_time="2024-05-01T11:00:00Z",
    )
    assert r.to_json_rows() == [{"c": 20, "s": 190.0}]


def test_schema_evolution_across_files(parseable):
    """SURVEY hard-part: type widening + conflict renames must keep queries
    working over MIXED files written before/after the schema evolved."""
    from parseable_tpu.event.json_format import JsonEvent

    p = parseable
    s = p.create_stream_if_not_exists("evolve")
    # epoch 1: status is numeric
    ev = JsonEvent([{"status": 200, "msg": "ok"}] * 10, "evolve").into_event(s.metadata)
    ev.process(s, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()

    # epoch 2: a client sends status as a STRING -> conflict rename
    ev = JsonEvent([{"status": "timeout", "msg": "bad"}] * 5, "evolve").into_event(
        s.metadata
    )
    ev.process(s, commit_schema=p.commit_schema)
    # epoch 3: numeric again, plus a brand-new column (widening union)
    ev = JsonEvent([{"status": 500, "msg": "err", "retry": 1}] * 3, "evolve").into_event(
        s.metadata
    )
    ev.process(s, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()

    for engine in ("cpu", "tpu"):
        sess = QuerySession(p, engine=engine)
        rows = sess.query(
            "SELECT status, count(*) c FROM evolve GROUP BY status ORDER BY status"
        ).to_json_rows()
        # string-typed conflicts live in status_str; numeric rows grouped
        assert {r["status"]: r["c"] for r in rows} == {200.0: 10, 500.0: 3, None: 5}
        renamed = sess.query(
            "SELECT count(status_str) c FROM evolve WHERE status_str = 'timeout'"
        ).to_json_rows()
        assert renamed[0]["c"] == 5
        # new column is NULL for old files, present for new
        retry = sess.query("SELECT count(retry) c FROM evolve").to_json_rows()
        assert retry[0]["c"] == 3
