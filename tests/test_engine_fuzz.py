"""Differential engine fuzz: random queries, CPU vs TPU must agree.

Every query shape the generator emits is within both engines' contract
(the TPU engine may fall back internally — that's part of the contract).
Mismatches are real bugs. The suite runs a bounded number of trials;
crank FUZZ_TRIALS up for a deep soak.

Round-4 scope (VERDICT r3 #6): grammar covers stddev/var, approx
percentiles, HAVING-on-aggregate; every trial is a THREE-way differential
— CPU engine vs single-device TPU path vs the virtual 8-device mesh path
(conftest pins the mesh) — and a session-level lane fuzzes CTE / UNION /
window shapes end-to-end.

ISSUE 30: the grammar's aggregates take arithmetic expressions over the
numeric columns too (sum(lat * 2), avg(lat + status), ...).

ISSUE 34: a lane of its own bins an event-time column eight years off the
rows' p_timestamp (NULLs among its values), with min / max beside the bins.

Tolerance model per aggregate kind (alias prefix encodes it):
  a*  exact/f32 sums        rel 2e-4
  s*  stddev/var            rel 5e-3 abs 1e-3 (centered-M2 on device)
  p*  approx percentiles    rel 8e-2 (documented sketch bin error)
Row identity sorts on GROUP KEYS ONLY (floats with per-engine noise must
never decide row order).
"""

import os
import random
from datetime import datetime, timedelta

import numpy as np
import pyarrow as pa
import pytest

from parseable_tpu import DEFAULT_TIMESTAMP_KEY
from parseable_tpu.config import Options
from parseable_tpu.query.executor import QueryExecutor
from parseable_tpu.query.executor_tpu import TpuQueryExecutor
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql

TRIALS = int(os.environ.get("FUZZ_TRIALS", "200"))
BASE = datetime(2024, 5, 1, 10, 0)


def make_table(rng: random.Random, n: int) -> pa.Table:
    np_rng = np.random.default_rng(rng.randrange(1 << 30))
    ts = [
        BASE + timedelta(seconds=int(s)) for s in np_rng.integers(0, 7200, n)
    ]
    cols = {
        DEFAULT_TIMESTAMP_KEY: pa.array(ts, pa.timestamp("ms")),
        "host": pa.array(np_rng.choice([f"h{i}" for i in range(rng.choice([2, 5, 40]))], n).tolist()),
        "path": pa.array(np_rng.choice([f"/p{i}" for i in range(12)], n).tolist()),
        "status": pa.array(np_rng.choice([200.0, 301.0, 404.0, 500.0], n)),
        "lat": pa.array(np_rng.random(n) * 100),
    }
    # sprinkle nulls into one column
    null_mask = np_rng.random(n) < 0.1
    lat = np.where(null_mask, np.nan, np_rng.random(n) * 100)
    cols["lat"] = pa.array([None if m else float(v) for m, v in zip(null_mask, lat)])
    return pa.table(cols)


# alias prefix encodes comparison tolerance (module docstring)
AGGS = [
    ("a", "count(*)"), ("a", "count(lat)"), ("a", "sum(lat)"), ("a", "avg(lat)"),
    ("a", "min(lat)"), ("a", "max(lat)"), ("a", "sum(status)"),
    ("a", "count(distinct host)"), ("a", "count(distinct path)"),
    # bit-identical across engines: both build the same HLL registers
    ("a", "approx_distinct(host)"), ("a", "approx_distinct(path)"),
    ("s", "stddev(lat)"), ("s", "var(lat)"), ("s", "stddev(status)"),
    ("p", "approx_percentile_cont(lat, 0.9)"),
    ("p", "approx_percentile_cont(lat, 0.5)"),
    ("p", "approx_median(lat)"),
    # arithmetic arguments fold inside the device program (ISSUE 30): a
    # NULL `lat` makes the row NULL for that aggregate alone
    ("a", "sum(lat * 2)"), ("a", "avg(lat + status)"), ("a", "sum(lat * (1 - status / 1000.0))"),
    ("a", "max(status - lat)"), ("a", "min(-lat)"), ("a", "count(lat * status)"),
    ("a", "sum(CAST(status AS double) / 100 - 1)"), ("s", "stddev(lat * 2 + 1)"),
]
GROUPS = ["host", "path", "status", "date_bin(interval '10m', p_timestamp)",
          "date_trunc('minute', p_timestamp)"]
FILTERS = [
    "status >= 400", "status = 200", "lat > 50", "lat IS NOT NULL",
    "host != 'h0'", "host IN ('h0', 'h1')", "path LIKE '/p1%'",
    "status >= 300 AND lat < 80", "status = 500 OR status = 404",
    "p_timestamp >= '2024-05-01T10:30:00Z'",
    "p_timestamp < '2024-05-01T11:00:00Z'",
    # ms-exact device time (no second-floor fallbacks): every op at any
    # precision must agree with the CPU engine
    "p_timestamp > '2024-05-01T10:30:00.250Z'",
    "p_timestamp <= '2024-05-01T10:45:30.500Z'",
    "p_timestamp = '2024-05-01T10:30:05Z'",
    "NOT (host = 'h1')",
]
# HAVING only over COUNTS: they are exact on both engines, so threshold
# flips can't produce flaky row-set mismatches (sums carry f32 noise)
HAVINGS = ["count(*) > 2", "count(*) >= 10", "count(lat) > 3"]

TOL = {
    "a": dict(rel=2e-4, abs=1e-6),
    "s": dict(rel=5e-3, abs=1e-3),
}

# percentiles: CPU keeps raw values below 1024/group (exact linear
# interpolation BETWEEN points) while the device always bins (linear
# interpolation WITHIN the landing bin) — on sparse few-row groups the two
# legitimately differ by up to the gap between adjacent values, which is
# bounded only by the data range. So the generator pairs every percentile
# with an exact count column (`z9`) and the comparison is count-aware:
# dense groups (>= PCT_DENSE rows) compare to sketch-error tolerance,
# sparse groups check null-consistency and the generator's value range.
# Accuracy is pinned tight on dense groups in tests/test_device_stats.py.
PCT_DENSE = 128
PCT_TOL = dict(rel=0.1, abs=8.0)
LAT_MAX = 100.0


def gen_query(rng: random.Random) -> str:
    n_aggs = rng.randint(1, 3)
    picks = rng.sample(AGGS, n_aggs)
    aggs = [f"{expr} {kind}{i}" for i, (kind, expr) in enumerate(picks)]
    if any(kind == "p" for kind, _ in picks):
        aggs.append("count(lat) z9")  # count-aware percentile comparison
    n_groups = rng.randint(0, 2)
    groups = rng.sample(GROUPS, n_groups)
    sel = ", ".join(([f"{g} g{i}" for i, g in enumerate(groups)]) + aggs)
    sql = f"SELECT {sel} FROM t"
    if rng.random() < 0.7:
        sql += f" WHERE {rng.choice(FILTERS)}"
    if groups:
        sql += " GROUP BY " + ", ".join(f"g{i}" for i in range(len(groups)))
        if rng.random() < 0.3:
            sql += f" HAVING {rng.choice(HAVINGS)}"
    return sql


def rows_equal(cpu: list[dict], other: list[dict], sql: str, lane: str) -> None:
    # row identity = group keys only; engine float noise must never
    # decide ordering (approx percentiles differ by whole sort buckets)
    def key(r):
        return tuple(str(r[k]) for k in sorted(r) if k.startswith("g"))

    cpu, other = sorted(cpu, key=key), sorted(other, key=key)
    assert len(cpu) == len(other), f"[{lane}] {sql}\ncpu={len(cpu)} vs {len(other)} rows"
    for rc, rt in zip(cpu, other):
        assert set(rc) == set(rt), (lane, sql)
        for k in rc:
            a, b = rc[k], rt[k]
            if k.startswith("p"):
                assert (a is None) == (b is None), (lane, sql, k, a, b)
                if a is None:
                    continue
                cnt = rc.get("z9")
                if cnt is not None and cnt >= PCT_DENSE:
                    assert a == pytest.approx(b, **PCT_TOL), (lane, sql, k, a, b)
                else:  # sparse: interp-mode divergence is legitimate
                    assert -1e-6 <= b <= LAT_MAX * 1.07, (lane, sql, k, a, b)
                continue
            tol = TOL.get(k[0], TOL["a"])
            if isinstance(a, float) and isinstance(b, float):
                assert a == pytest.approx(b, **tol), (sql, k, a, b)
            else:
                assert a == b, (lane, sql, k, a, b)


def test_differential_fuzz():
    """CPU vs mesh-TPU vs single-device-TPU, seed-pinned."""
    rng = random.Random(int(os.environ.get("FUZZ_SEED", "1234")))
    no_mesh = Options()
    no_mesh.mesh_shape = "off"
    for trial in range(TRIALS):
        n_tables = rng.randint(1, 3)
        tables = [make_table(rng, rng.choice([500, 3000])) for _ in range(n_tables)]
        sql = gen_query(rng)
        cpu = QueryExecutor(build_plan(parse_sql(sql))).execute(iter(tables)).to_pylist()
        mesh = TpuQueryExecutor(build_plan(parse_sql(sql))).execute(iter(tables)).to_pylist()
        rows_equal(cpu, mesh, f"[trial {trial}] {sql}", "mesh")
        if trial % 4 == 0:  # single-device lane on a rotating subset
            solo = (
                TpuQueryExecutor(build_plan(parse_sql(sql)), no_mesh)
                .execute(iter(tables))
                .to_pylist()
            )
            rows_equal(cpu, solo, f"[trial {trial}] {sql}", "solo")


# ------------------------------------------- event-time bins (ISSUE 34)

EVENT_BASE = datetime(2016, 1, 1)  # eight years before the rows' p_timestamp: off every block's origin
EVENT_GROUPS = [
    "date_bin(interval '1 hour', ev)", "date_bin(interval '10m', ev)", "date_trunc('minute', ev)",
    "date_trunc('day', ev)", "date_bin(interval '90 seconds', ev)", "host", "status",
]
EVENT_AGGS = ["min(lat)", "max(lat)", "max(status)", "min(status - lat)", "count(*)", "count(lat)", "avg(lat)", "sum(status)"]
EVENT_FILTERS = [
    "ev >= '2016-01-01T01:00:00Z'", "ev < '2016-01-01T03:30:00Z'", "ev >= '2016-01-01T00:30:00Z' AND ev < '2016-01-01T04:00:00Z'",
    "ev > '2016-01-01T02:00:00.500Z'", "ev IS NOT NULL", "host IN ('h0', 'h1', 'h3')", "lat > 50",
]


def with_event_time(rng: random.Random, table: pa.Table) -> pa.Table:
    """`ev`: the time the rows carry, in whole steps of a unit drawn per table (so neighbouring blocks differ in unit and
    in origin), a twentieth of it NULL."""
    n = table.num_rows
    np_rng = np.random.default_rng(rng.randrange(1 << 30))
    unit_ms = rng.choice([1, 1000, 60_000])
    first_ms = rng.choice([0, 3_600_000, 86_400_000])
    steps = np_rng.integers(0, 5 * 3_600_000 // unit_ms, n)
    ev = [None if np_rng.random() < 0.05 else EVENT_BASE + timedelta(milliseconds=int(first_ms + s * unit_ms)) for s in steps]
    return table.append_column("ev", pa.array(ev, pa.timestamp("ms")))


def test_differential_fuzz_event_time_bins_with_min_and_max():
    """Time bins over an event-time column off the block's origin, `min` / `max` beside them: the three lanes again.
    A bin its column's unit does not divide (90 s over whole minutes) is the CPU engine's, declared: still the same rows."""
    rng = random.Random(int(os.environ.get("FUZZ_SEED", "1234")) + 34)
    no_mesh = Options()
    no_mesh.mesh_shape = "off"
    binned = 0
    for trial in range(max(20, TRIALS // 4)):
        tables = [with_event_time(rng, make_table(rng, rng.choice([500, 3000]))) for _ in range(rng.randint(1, 3))]
        groups = rng.sample(EVENT_GROUPS, rng.randint(1, 2))
        aggs = [f"{expr} a{i}" for i, expr in enumerate(rng.sample(EVENT_AGGS, rng.randint(1, 3)))]
        sql = "SELECT " + ", ".join([f"{g} g{i}" for i, g in enumerate(groups)] + aggs) + " FROM t"
        if rng.random() < 0.6:
            sql += f" WHERE {rng.choice(EVENT_FILTERS)}"
        sql += " GROUP BY " + ", ".join(f"g{i}" for i in range(len(groups)))
        cpu = QueryExecutor(build_plan(parse_sql(sql))).execute(iter(tables)).to_pylist()
        ex = TpuQueryExecutor(build_plan(parse_sql(sql)))
        rows_equal(cpu, ex.execute(iter(tables)).to_pylist(), f"[trial {trial}] {sql}", "mesh")
        binned += ex.route_stats["timebin_offorigin_device_blocks"]
        if trial % 3 == 0:
            solo = TpuQueryExecutor(build_plan(parse_sql(sql)), no_mesh).execute(iter(tables)).to_pylist()
            rows_equal(cpu, solo, f"[trial {trial}] {sql}", "solo")
    assert binned > 0  # the device did bin some of them


# ----------------------------------------------------- session-level shapes


SESSION_TRIALS = int(os.environ.get("FUZZ_SESSION_TRIALS", "30"))


def _session_queries(rng: random.Random) -> str:
    """CTE / UNION / window shapes with deterministic cross-engine results
    (windows order by exact counts; rank/dense_rank are tie-stable)."""
    f1, f2 = rng.sample(FILTERS[:9], 2)
    g = rng.choice(["host", "path", "status"])
    shape = rng.randrange(5)
    if shape == 0:  # CTE over an aggregate, re-filtered
        return (
            f"WITH x AS (SELECT {g} k, count(*) c, sum(lat) s FROM web "
            f"WHERE {f1} GROUP BY k) SELECT k, c FROM x WHERE c > 1"
        )
    if shape == 1:  # UNION ALL of two filtered aggregates
        return (
            f"SELECT {g} k, count(*) c FROM web WHERE {f1} GROUP BY k "
            f"UNION ALL SELECT {g} k, count(*) c FROM web WHERE {f2} GROUP BY k"
        )
    if shape == 2:  # UNION dedup of key sets
        return (
            f"SELECT {g} k FROM web WHERE {f1} GROUP BY k "
            f"UNION SELECT {g} k FROM web WHERE {f2} GROUP BY k"
        )
    if shape == 3:  # window over aggregate output (tie-stable rank)
        return (
            f"SELECT {g} k, count(*) c, rank() OVER (ORDER BY count(*) DESC) rk "
            f"FROM web GROUP BY k"
        )
    # CTE + window + HAVING
    return (
        f"WITH x AS (SELECT {g} k, count(*) c FROM web WHERE {f1} "
        f"GROUP BY k HAVING count(*) > 1) "
        f"SELECT k, c, dense_rank() OVER (ORDER BY c DESC) rk FROM x"
    )


def test_session_fuzz_cte_union_window(parseable):
    from parseable_tpu.event.json_format import JsonEvent
    from parseable_tpu.query.session import QuerySession

    rng = random.Random(int(os.environ.get("FUZZ_SEED", "1234")) + 7)
    np_rng = np.random.default_rng(99)
    n = 4000
    rows = [
        {
            "host": f"h{int(np_rng.integers(0, 5))}",
            "path": f"/p{int(np_rng.integers(0, 8))}",
            "status": float(np_rng.choice([200.0, 301.0, 404.0, 500.0])),
            "lat": float(np_rng.random() * 100),
        }
        for _ in range(n)
    ]
    s = parseable.create_stream_if_not_exists("web")
    ev = JsonEvent(rows, "web").into_event(s.metadata)
    ev.process(s, commit_schema=parseable.commit_schema)
    cpu_sess = QuerySession(parseable, engine="cpu")
    tpu_sess = QuerySession(parseable, engine="tpu")
    for trial in range(SESSION_TRIALS):
        sql = _session_queries(rng)
        cpu = cpu_sess.query(sql).to_json_rows()
        tpu = tpu_sess.query(sql).to_json_rows()
        # UNION ALL emits duplicate keys: compare as sorted multisets
        def key(r):
            return tuple(
                (k, f"{v:.6g}" if isinstance(v, float) else str(v))
                for k, v in sorted(r.items())
            )
        cpu_s, tpu_s = sorted(cpu, key=key), sorted(tpu, key=key)
        assert len(cpu_s) == len(tpu_s), f"[session {trial}] {sql}"
        for rc, rt in zip(cpu_s, tpu_s):
            for k in rc:
                a, b = rc[k], rt[k]
                if isinstance(a, float) and isinstance(b, float):
                    assert a == pytest.approx(b, rel=2e-4, abs=1e-6), (sql, k)
                else:
                    assert a == b, (sql, k, a, b)
