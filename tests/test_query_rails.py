"""Query safety rails: timeout, memory cap, top-K pushdown, streaming.

Reference: dedicated runtime + SQL timeout (query/mod.rs:92,152-165),
memory pool (:216-226), chunked streaming (handlers/http/query.rs:325-407).
"""

import time
from datetime import datetime, timedelta

import pyarrow as pa
import pytest

from parseable_tpu import DEFAULT_TIMESTAMP_KEY
from parseable_tpu.query.executor import (
    MemoryLimitExceeded,
    QueryExecutor,
    QueryTimeout,
)
from parseable_tpu.query.executor_tpu import TpuQueryExecutor
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql

BASE = datetime(2024, 5, 1, 10, 0)


def make_table(n=5000, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    ts = [BASE + timedelta(seconds=int(i)) for i in rng.integers(0, 3600, n)]
    return pa.table(
        {
            DEFAULT_TIMESTAMP_KEY: pa.array(ts, pa.timestamp("ms")),
            "v": pa.array(rng.random(n) * 1000),
            "host": pa.array(rng.choice(["a", "b", "c"], n).tolist()),
        }
    )


def test_timeout_cuts_off_scan():
    lp = build_plan(parse_sql("SELECT host, count(*) c FROM t GROUP BY host"))
    lp.deadline = time.monotonic() - 1  # already expired

    def slow_tables():
        yield make_table()

    with pytest.raises(QueryTimeout):
        QueryExecutor(lp).execute(slow_tables())


def test_timeout_cuts_off_tpu_scan():
    lp = build_plan(parse_sql("SELECT host, count(*) c FROM t GROUP BY host"))
    lp.deadline = time.monotonic() - 1
    with pytest.raises(QueryTimeout):
        TpuQueryExecutor(lp).execute(iter([make_table()]))


def test_memory_limit_select():
    lp = build_plan(parse_sql("SELECT * FROM t"))
    lp.memory_limit_bytes = 10_000  # tiny
    tables = [make_table(seed=s) for s in range(4)]
    with pytest.raises(MemoryLimitExceeded):
        QueryExecutor(lp).execute(iter(tables))


def test_topk_pushdown_bounds_memory_and_matches_full_sort():
    """ORDER BY + LIMIT over many blocks compacts the working set instead of
    materializing everything — and still returns the globally correct K."""
    sql = "SELECT v, host FROM t ORDER BY v DESC LIMIT 7"
    tables = [make_table(seed=s) for s in range(6)]
    lp = build_plan(parse_sql(sql))
    # a memory cap far below the full concat proves compaction happened
    lp.memory_limit_bytes = 500_000
    got = QueryExecutor(lp).execute(iter(tables)).to_pylist()
    all_rows = pa.concat_tables(
        [t.select(["v", "host"]) for t in tables]
    ).to_pylist()
    want = sorted(all_rows, key=lambda r: -r["v"])[:7]
    assert [r["v"] for r in got] == [r["v"] for r in want]


def test_topk_with_offset():
    sql = "SELECT v FROM t ORDER BY v LIMIT 5 OFFSET 3"
    tables = [make_table(seed=s) for s in range(3)]
    lp = build_plan(parse_sql(sql))
    got = [r["v"] for r in QueryExecutor(lp).execute(iter(tables)).to_pylist()]
    every = sorted(
        v for t in tables for v in t.column("v").to_pylist()
    )
    assert got == every[3:8]


def test_select_stream_yields_incrementally():
    lp = build_plan(parse_sql("SELECT host, v FROM t WHERE v >= 0 LIMIT 9000"))
    tables = [make_table(seed=s) for s in range(3)]
    out = list(QueryExecutor(lp).execute_select_stream(iter(tables)))
    assert len(out) >= 2  # streamed per block, not one materialized table
    assert sum(t.num_rows for t in out) == 9000


def test_select_stream_offset_and_order_fallback():
    # ORDER BY forces materialization but still returns correct rows
    lp = build_plan(parse_sql("SELECT v FROM t ORDER BY v LIMIT 4"))
    tables = [make_table(seed=s) for s in range(2)]
    out = list(QueryExecutor(lp).execute_select_stream(iter(tables)))
    assert len(out) == 1
    every = sorted(v for t in tables for v in t.column("v").to_pylist())
    assert [r["v"] for r in out[0].to_pylist()] == every[:4]


def test_session_applies_rails(parseable):
    from parseable_tpu.event.json_format import JsonEvent
    from parseable_tpu.query.session import QuerySession

    p = parseable
    p.options.query_timeout_secs = 300
    stream = p.create_stream_if_not_exists("railed")
    ev = JsonEvent([{"a": i} for i in range(50)], "railed").into_event(stream.metadata)
    ev.process(stream, commit_schema=p.commit_schema)

    sess = QuerySession(p, engine="cpu")
    res = sess.query("SELECT a FROM railed ORDER BY a DESC LIMIT 3")
    assert [r["a"] for r in res.to_json_rows()] == [49.0, 48.0, 47.0]

    # streaming variant
    parts = list(sess.query_stream("SELECT a FROM railed LIMIT 10"))
    assert sum(t.num_rows for t in parts) == 10

    # timeout = 0-ish -> the query is cut off
    p.options.query_timeout_secs = -1
    with pytest.raises(QueryTimeout):
        sess.query("SELECT a, count(*) FROM railed GROUP BY a")
    p.options.query_timeout_secs = 300


def _rails_table(n: int = 4096):
    import numpy as np
    import pyarrow as pa

    rng = np.random.default_rng(11)
    return pa.table(
        {
            "g": pa.array([f"g{int(x)}" for x in rng.integers(0, 8, n)]),
            "v": pa.array(rng.random(n) * 100),
            "tags": pa.array([[int(x)] for x in rng.integers(0, 4, n)]),
        }
    )


def test_device_program_error_fails_the_query(parseable, monkeypatch):
    """With engine tpu, an exception from building or running a device
    program (a compiler refusal, an HBM OOM) is the query's error. It is
    never folded on the CPU behind the caller's back, and `cpu_fallback`
    — the count of DECLARED UnsupportedOnDevice decisions — stays 0."""
    from parseable_tpu.event.json_format import JsonEvent
    from parseable_tpu.query import executor_tpu as ET
    from parseable_tpu.query.planner import plan as build_plan
    from parseable_tpu.query.session import QuerySession
    from parseable_tpu.query.sql import parse_sql

    def boom(*a, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected device failure")

    monkeypatch.setattr(ET.kernels, "fused_groupby_block", boom)
    # distinct SQL text: the shape-keyed program cache must not serve a
    # program traced before the patch
    ex = ET.TpuQueryExecutor(
        build_plan(parse_sql("SELECT g, count(*) c, sum(v) s, max(v) mx FROM t GROUP BY g"))
    )
    with pytest.raises(RuntimeError, match="injected device failure"):
        ex.execute(iter([_rails_table()]))
    assert ex.route_stats["cpu_fallback"] == 0

    # the same through the session, i.e. what the HTTP layer turns into a 5xx
    p = parseable
    s = p.create_stream_if_not_exists("wedge")
    ev = JsonEvent([{"a": float(i)} for i in range(20)], "wedge").into_event(s.metadata)
    ev.process(s, commit_schema=p.commit_schema)
    with pytest.raises(RuntimeError, match="injected device failure"):
        QuerySession(p, engine="tpu").query("SELECT max(a) m, sum(a) s FROM wedge")


def test_declared_unsupported_block_still_answers_and_is_counted():
    """The one way work leaves the device: a declared UnsupportedOnDevice
    (here a nested column the device cannot encode) folds the block on the
    CPU engine, answers exactly, and is counted in `cpu_fallback`."""
    from parseable_tpu.query import executor_tpu as ET
    from parseable_tpu.query.executor import QueryExecutor
    from parseable_tpu.query.planner import plan as build_plan
    from parseable_tpu.query.sql import parse_sql

    sql = "SELECT g, count(*) c, count(tags) n FROM t GROUP BY g ORDER BY g"
    t = _rails_table()
    ex = ET.TpuQueryExecutor(build_plan(parse_sql(sql)))
    out = ex.execute(iter([t])).to_pylist()
    assert out == QueryExecutor(build_plan(parse_sql(sql))).execute(iter([t])).to_pylist()
    assert ex.route_stats["cpu_fallback"] == 1
    assert ex.route_stats["device_cold"] == 0 and ex.route_stats["device_warm"] == 0


@pytest.mark.parametrize("order", [(0, 1), (1, 0)])
def test_null_group_survives_a_capacity_epoch_flush(order):
    """A dict key's null slot is code `capacity - 1` of its epoch. When the
    NEXT block grows the dictionary, the old epoch is flushed after the
    dictionary already absorbed the new values — the decode must still read
    that slot as NULL, not as the value that now sits at that index (found
    when the CPU fallback stopped papering over an order-dependent scan)."""
    import numpy as np
    import pyarrow as pa

    from parseable_tpu.query import executor_tpu as ET
    from parseable_tpu.query.planner import plan as build_plan
    from parseable_tpu.query.sql import parse_sql

    blocks = [
        pa.table({"status": pa.array([500.0] * 3 + [None] * 5), "v": pa.array(np.ones(8))}),
        pa.table({"status": pa.array([200.0] * 10), "v": pa.array(np.ones(10))}),
    ]
    sql = "SELECT status, count(*) c FROM t GROUP BY status"
    ex = ET.TpuQueryExecutor(build_plan(parse_sql(sql)))
    # a source id keeps the blocks un-coalesced, as scanned parquet files are
    tagged = [
        blocks[i].replace_schema_metadata({ET.SOURCE_ID_META: f"epoch-{i}".encode()})
        for i in order
    ]
    got = {r["status"]: r["c"] for r in ex.execute(iter(tagged)).to_pylist()}
    assert got == {500.0: 3, 200.0: 10, None: 5}
    assert ex.route_stats["cpu_fallback"] == 0
