"""Device-side stddev / var / approx_percentile (round-4 VERDICT #3).

The p95-latency workhorse must not force a whole-query CPU fallback:
stddev/var ride the packed accumulator as fused sum+sumsq rows and
percentiles accumulate per-group log2 histograms (query/sketch.py DEVICE_NB
layout) via the same dense segment_sum machinery as every other aggregate.
Under conftest's virtual 8-device mesh these tests also exercise the
shard_map psum path for the new accumulators.

Reference behavior matched: DataFusion executes approx_percentile_cont /
stddev in-engine (/root/reference/src/query/mod.rs:212-276); the device
histogram answer carries the sketch's documented ~5.6% per-value error.
"""

from __future__ import annotations

import logging

import numpy as np
import pyarrow as pa
import pytest

from parseable_tpu.query import executor_tpu as ET
from parseable_tpu.query.executor import QueryExecutor
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql


def run(sql: str, tables: list[pa.Table], engine: str = "cpu"):
    lp = build_plan(parse_sql(sql))
    ex = QueryExecutor(lp) if engine == "cpu" else ET.TpuQueryExecutor(lp)
    return ex.execute(iter(tables)).to_pylist()


def run_device_strict(sql: str, tables: list[pa.Table], caplog):
    """Run on the TPU engine and assert NO CPU fallback happened."""
    with caplog.at_level(logging.DEBUG, logger="parseable_tpu.query.executor_tpu"):
        out = run(sql, tables, "tpu")
    fallbacks = [
        r.message
        for r in caplog.records
        if "falling back" in r.message.lower() or "batch on CPU" in r.message
    ]
    assert not fallbacks, fallbacks
    return out


def latency_table(n=20_000, seed=0, groups=8):
    rng = np.random.default_rng(seed)
    v = np.exp(rng.normal(3.0, 1.0, n))  # lognormal latencies
    v[rng.random(n) < 0.05] = np.nan  # arrow -> null via mask below
    mask = np.isnan(v)
    return pa.table(
        {
            "g": pa.array([f"g{int(x)}" for x in rng.integers(0, groups, n)]),
            "v": pa.array(np.where(mask, 0.0, v), mask=mask),
        }
    )


# --------------------------------------------------------------- stddev / var


def test_stddev_var_on_device_matches_cpu(caplog):
    t = latency_table()
    sql = (
        "SELECT g, stddev(v) s, var(v) va, avg(v) a, count(v) c "
        "FROM t GROUP BY g ORDER BY g"
    )
    cpu = run(sql, [t], "cpu")
    tpu = run_device_strict(sql, [t], caplog)
    assert [r["g"] for r in cpu] == [r["g"] for r in tpu]
    for rc, rt in zip(cpu, tpu):
        assert rt["c"] == rc["c"]
        # f32 on-device sum/sumsq accumulation vs f64 host
        assert rt["s"] == pytest.approx(rc["s"], rel=1e-3)
        assert rt["va"] == pytest.approx(rc["va"], rel=1e-3)
        assert rt["a"] == pytest.approx(rc["a"], rel=1e-4)


def test_stddev_single_row_group_is_null(caplog):
    t = pa.table(
        {
            "g": pa.array(["lone", "pair", "pair"]),
            "v": pa.array([5.0, 1.0, 3.0]),
        }
    )
    sql = "SELECT g, stddev(v) s, var(v) va FROM t GROUP BY g ORDER BY g"
    for engine_rows in (run(sql, [t], "cpu"), run_device_strict(sql, [t], caplog)):
        by_g = {r["g"]: r for r in engine_rows}
        assert by_g["lone"]["s"] is None  # n < 2 -> NULL (sample variance)
        assert by_g["lone"]["va"] is None
        assert by_g["pair"]["s"] == pytest.approx(np.sqrt(2.0))
        assert by_g["pair"]["va"] == pytest.approx(2.0)


def test_stddev_all_null_group(caplog):
    t = pa.table(
        {
            "g": pa.array(["a", "a", "b"]),
            "v": pa.array([None, None, 7.0], pa.float64()),
        }
    )
    sql = "SELECT g, stddev(v) s FROM t GROUP BY g ORDER BY g"
    cpu = run(sql, [t], "cpu")
    tpu = run_device_strict(sql, [t], caplog)
    assert cpu == tpu
    assert cpu[0]["s"] is None and cpu[1]["s"] is None


def test_stddev_partializable_highcard_two_phase():
    """stddev is now partial-format (sum/sumsq columns): the block-local
    two-phase path and the CPU engine's partial path both carry it."""
    from parseable_tpu.query.partials import specs_partializable

    rng = np.random.default_rng(3)
    n = 30_000
    t = pa.table(
        {
            "k": pa.array([f"k{int(x)}" for x in rng.integers(0, 9000, n)]),
            "v": pa.array(rng.random(n) * 100),
        }
    )
    lp = build_plan(parse_sql("SELECT k, stddev(v) s FROM t GROUP BY k"))
    agg, _, _ = QueryExecutor(lp).build_aggregator()
    assert specs_partializable(agg.specs)
    cpu = {r["k"]: r["s"] for r in run("SELECT k, stddev(v) s FROM t GROUP BY k", [t], "cpu")}
    tpu = {r["k"]: r["s"] for r in run("SELECT k, stddev(v) s FROM t GROUP BY k", [t], "tpu")}
    assert set(cpu) == set(tpu)
    for k, s in cpu.items():
        if s is None:
            assert tpu[k] is None
        else:
            # f32 sum/sumsq cancellation is worst when mean >> stddev and
            # groups are tiny (~3 rows here): accept 2% relative
            assert tpu[k] == pytest.approx(s, rel=2e-2, abs=1e-4)


# ---------------------------------------------------------------- percentiles


def test_percentile_on_device_within_sketch_error(caplog):
    t = latency_table(seed=11)
    sql = (
        "SELECT g, approx_percentile_cont(v, 0.95) p, approx_median(v) m, "
        "count(*) c FROM t GROUP BY g ORDER BY g"
    )
    cpu = run(sql, [t], "cpu")
    tpu = run_device_strict(sql, [t], caplog)
    assert [r["g"] for r in cpu] == [r["g"] for r in tpu]
    for rc, rt in zip(cpu, tpu):
        assert rt["c"] == rc["c"]
        assert rt["p"] == pytest.approx(rc["p"], rel=0.06)
        assert rt["m"] == pytest.approx(rc["m"], rel=0.06)


def test_percentile_negatives_zeros_device(caplog):
    rng = np.random.default_rng(13)
    v = np.concatenate(
        [-np.exp(rng.normal(2, 1, 6000)), np.zeros(1000), np.exp(rng.normal(2, 1, 6000))]
    )
    rng.shuffle(v)
    t = pa.table({"v": pa.array(v)})
    for p in (0.05, 0.5, 0.95):
        sql = f"SELECT approx_percentile_cont(v, {p}) p FROM t"
        got = run_device_strict(sql, [t], caplog)[0]["p"]
        exact = np.quantile(v, p)
        tol = max(abs(exact) * 0.08, 0.5)
        assert abs(got - exact) <= tol, (p, got, exact)


def test_percentile_p0_p100_exact_on_device(caplog):
    """vmin/vmax ride the accumulator's min/max rows, so the sketch clamp
    makes p0/p100 EXACT even though interior quantiles are binned."""
    rng = np.random.default_rng(17)
    v = rng.random(9_000) * 777.7
    t = pa.table({"v": pa.array(v)})
    lo = run_device_strict("SELECT approx_percentile_cont(v, 0.0) p FROM t", [t], caplog)
    hi = run_device_strict("SELECT approx_percentile_cont(v, 1.0) p FROM t", [t], caplog)
    # f32 encode rounds the values once; compare at f32 resolution
    assert lo[0]["p"] == pytest.approx(float(np.float32(v.min())), rel=1e-6)
    assert hi[0]["p"] == pytest.approx(float(np.float32(v.max())), rel=1e-6)


def test_percentile_nulls_dont_count_device(caplog):
    t = pa.table(
        {
            "g": pa.array(["a"] * 4 + ["b"] * 4),
            "v": pa.array([1.0, 2.0, 3.0, None, 10.0, None, None, 30.0], pa.float64()),
        }
    )
    sql = "SELECT g, approx_median(v) m FROM t GROUP BY g ORDER BY g"
    out = run_device_strict(sql, [t], caplog)
    assert out[0]["m"] == pytest.approx(2.0, rel=0.06)
    # histogram mode interpolates within the landing bin, not between the
    # two distant data points (the host's raw mode would say 20): the
    # contract here is that the 2 nulls neither count (target rank would
    # shift toward 1.0) nor contribute zero-bin mass (answer would be ~0)
    assert 10.0 <= out[1]["m"] <= 30.0
    assert out[1]["m"] == pytest.approx(10.0, rel=0.06)


def test_percentile_epoch_flush_merges_sketches(caplog):
    """A mid-scan capacity epoch change (new dict values) flushes the dense
    accumulator through the sparse aggregator: device sketches from both
    epochs and the histogram partials must merge associatively."""
    rng = np.random.default_rng(19)
    t1 = pa.table(
        {
            "g": pa.array([f"g{int(x)}" for x in rng.integers(0, 2, 6000)]),
            "v": pa.array(rng.random(6000) * 100),
        }
    )
    t2 = pa.table(
        {
            "g": pa.array([f"g{int(x)}" for x in rng.integers(0, 40, 6000)]),
            "v": pa.array(rng.random(6000) * 100),
        }
    )
    sql = "SELECT g, approx_percentile_cont(v, 0.9) p, count(*) c FROM t GROUP BY g"
    cpu = {r["g"]: r for r in run(sql, [t1, t2], "cpu")}
    tpu = {r["g"]: r for r in run(sql, [t1, t2], "tpu")}
    assert set(cpu) == set(tpu)
    for g, rc in cpu.items():
        assert tpu[g]["c"] == rc["c"]
        assert tpu[g]["p"] == pytest.approx(rc["p"], rel=0.06)


def test_percentile_with_count_distinct_both_device(caplog):
    rng = np.random.default_rng(23)
    n = 8_000
    t = pa.table(
        {
            "g": pa.array([f"g{int(x)}" for x in rng.integers(0, 4, n)]),
            "v": pa.array(rng.random(n) * 50),
            "u": pa.array([f"u{int(x)}" for x in rng.integers(0, 64, n)]),
        }
    )
    sql = (
        "SELECT g, approx_percentile_cont(v, 0.5) p, count(distinct u) d "
        "FROM t GROUP BY g ORDER BY g"
    )
    cpu = run(sql, [t], "cpu")
    tpu = run(sql, [t], "tpu")
    for rc, rt in zip(cpu, tpu):
        assert rt["d"] == rc["d"]  # distinct stays exact
        assert rt["p"] == pytest.approx(rc["p"], rel=0.06)


def test_percentile_highcard_falls_back_exact():
    """Past the histogram budget (G * DEVICE_NB > PCT_MAX_ELEMS) the scan
    aggregates host-side with exact sketches — answers match the CPU
    engine exactly, and force_cpu_rest stops re-encoding every block."""
    rng = np.random.default_rng(29)
    n = 40_000
    t = pa.table(
        {
            "k": pa.array([f"k{int(x)}" for x in rng.integers(0, 9000, n)]),
            "v": pa.array(rng.random(n) * 100),
        }
    )
    sql = "SELECT k, approx_percentile_cont(v, 0.9) p FROM t GROUP BY k"
    cpu = {r["k"]: r["p"] for r in run(sql, [t], "cpu")}
    tpu = {r["k"]: r["p"] for r in run(sql, [t], "tpu")}
    assert cpu == tpu  # host sketches both sides: exact match


def test_having_on_stddev_device(caplog):
    t = latency_table(seed=31)
    sql = (
        "SELECT g, stddev(v) s FROM t GROUP BY g HAVING stddev(v) > 0 ORDER BY g"
    )
    cpu = run(sql, [t], "cpu")
    tpu = run_device_strict(sql, [t], caplog)
    assert [r["g"] for r in cpu] == [r["g"] for r in tpu]
    for rc, rt in zip(cpu, tpu):
        assert rt["s"] == pytest.approx(rc["s"], rel=1e-3)


# ------------------------------------------------------- top-K ordering rails


def _topk_acc(vals_by_group):
    """Build a tiny packed accumulator for one sum spec over len(vals)
    groups: rows = count | pac | sum."""
    import jax.numpy as jnp

    g = len(vals_by_group)
    count = np.array([1.0 if v is not ... else 0.0 for v in vals_by_group], np.float32)
    pac = np.array(
        [1.0 if (v is not ... and v is not None) else 0.0 for v in vals_by_group],
        np.float32,
    )
    sums = np.array(
        [float(v) if (v is not ... and v is not None) else 0.0 for v in vals_by_group],
        np.float32,
    )
    count = np.where(np.array([v is ... for v in vals_by_group]), 0.0, 1.0).astype(np.float32)
    return jnp.asarray(np.stack([count, pac, sums]))


def test_topk_null_groups_never_displace_extreme_keys():
    """ADVICE r3 #1: a real group whose key is -inf (or f32 min) must beat
    every NULL-agg group in the gather — the int32 total-order composite
    has no finite sentinel to collide with."""
    from parseable_tpu.query.executor import AggSpec

    lay = ET.AccLayout(
        sum_idx=(0,), sq_idx=(), min_idx=(), max_idx=(), countcol_idx=(),
        pct_idx=(),
    )
    specs = [AggSpec("sum", None, "__agg0")]
    ex = ET.TpuQueryExecutor(build_plan(parse_sql("SELECT count(*) FROM t")))
    # groups: 0 -> -inf, 1 -> NULL agg, 2 -> 5.0, 3 -> empty slot, 4 -> f32min
    acc = _topk_acc([float("-inf"), None, 5.0, ..., -3.4028235e38])
    # ascending: -inf, f32min, 5.0, then the NULL group; empty slots never
    gathered, idx = ex._run_topk_program(acc, 0, desc=False, k=4, lay=lay, specs=specs)
    assert list(idx) == [0, 4, 2, 1]
    # descending: 5.0, f32min? no - desc wants largest first
    gathered, idx = ex._run_topk_program(acc, 0, desc=True, k=4, lay=lay, specs=specs)
    assert list(idx) == [2, 4, 0, 1]


def test_topk_orders_by_stddev_on_device():
    """ORDER BY stddev(v) LIMIT k computes sample variance in-program."""
    from parseable_tpu.query.executor import AggSpec

    import jax.numpy as jnp

    lay = ET.AccLayout(
        sum_idx=(), sq_idx=(0,), min_idx=(), max_idx=(), countcol_idx=(),
        pct_idx=(),
    )
    specs = [AggSpec("stddev", None, "__agg0")]
    ex = ET.TpuQueryExecutor(build_plan(parse_sql("SELECT count(*) FROM t")))
    rng = np.random.default_rng(5)
    data = [rng.normal(0, sd, 50) for sd in (1.0, 9.0, 3.0, 5.0)]
    count = np.full(4, 50.0, np.float32)
    pac = count.copy()
    s = np.array([d.sum() for d in data], np.float32)
    sq = np.array([(d * d).sum() for d in data], np.float32)
    acc = jnp.asarray(np.stack([count, pac, s, sq]))
    _, idx = ex._run_topk_program(acc, 0, desc=True, k=2, lay=lay, specs=specs)
    assert list(idx) == [1, 3]  # sd=9 then sd=5
