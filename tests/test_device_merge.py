"""High-cardinality top-K: select on the device, merge only the survivors on
the host (ISSUE 28). `jit_executor_merge` sorts, totals and ranks the
block-local partials with a margin; `partials.merge_partials` and `finish()`
make the answer from the survivors' per-block rows as from any partials.

The yardstick of every case is the full host merge of the SAME partials
(the path a plain multi-key GROUP BY takes), reached by setting the entry
budget to 0. Everything runs on the CPU backend: what is selected, counted
and named, never a time."""

from __future__ import annotations

import functools
import time

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from parseable_tpu import DEFAULT_TIMESTAMP_KEY
from parseable_tpu.query import executor_tpu as ET
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql
from parseable_tpu.utils import metrics, telemetry

MERGE_KEYS = ("merge_device", "merge_host", "merge_entries", "merge_survivors")


@pytest.fixture(autouse=True)
def _small_dense_budget(monkeypatch):
    # a few thousand groups leave the dense path, as the cell's 2^31 do
    monkeypatch.setattr(ET, "DENSE_G_MAX", 1 << 10)
    monkeypatch.setenv("P_TPU_BLOCK_ROWS", str(BLOCK_ROWS))


def execute(sql: str, tables: list[pa.Table], mesh: bool = False) -> tuple[pa.Table, ET.RouteStats]:
    ex = ET.TpuQueryExecutor(build_plan(parse_sql(sql)))
    if not mesh:
        ex.mesh = None  # one device, as the benchmark's cells run (the tests' eight virtual devices make a mesh)
    return ex.execute(iter(tables)), ex.route_stats


def both(sql: str, tables: list[pa.Table], monkeypatch) -> tuple[list[dict], list[dict], ET.RouteStats]:
    """The device-merge answer and the full host merge of the same partials."""
    dev, routes = execute(sql, tables)
    with monkeypatch.context() as m:
        m.setattr(ET, "MERGE_DEVICE_MAX_ENTRIES", 0)
        host, host_routes = execute(sql, tables)
    assert host_routes["merge_host"] == 1 and host_routes["merge_host_reason"] == "budget"
    return dev.to_pylist(), host.to_pylist(), routes


def assert_same_answer(dev: list[dict], host: list[dict], order_col: str) -> None:
    """Rank by rank the ordering values are equal; keys (and every other
    column) are equal wherever the value differs from both neighbours. The
    last rank's other neighbour lies outside the answer: another group
    there with the same value is a tie with it."""
    assert len(dev) == len(host)
    values = [r[order_col] for r in host]
    assert [r[order_col] for r in dev] == values
    for i, (d, h) in enumerate(zip(dev, host)):
        tied = (i > 0 and values[i - 1] == values[i]) or (i + 1 < len(values) and values[i + 1] == values[i])
        if i + 1 == len(values) and [v for v in d.values() if isinstance(v, str)] != [v for v in h.values() if isinstance(v, str)]:
            tied = True
        if not tied:
            assert d == h, (i, d, h)


# the executor coalesces tables up to P_TPU_BLOCK_ROWS (2^16 at the least) into one device block: with
# that option at its floor, a table this long is a block of its own
BLOCK_ROWS = 1 << 16


def names(prefix: str, ids: np.ndarray, width: int = 5) -> pa.Array:
    return pc.binary_join_element_wise(prefix, pc.utf8_lpad(pc.cast(pa.array(ids), pa.string()), width, "0"), "")


@functools.lru_cache(maxsize=None)
def blocks(n_blocks: int = 3, users: int = 6_000, seed: int = 28) -> tuple[pa.Table, ...]:
    """`n_blocks` blocks whose users overlap, so a group spans several; values of mixed sign; some NULL values."""
    rng = np.random.default_rng(seed)
    out, rows = [], BLOCK_ROWS
    for b in range(n_blocks):
        out.append(
            pa.table(
                {
                    "user": names("u", rng.integers(b * users // 4, b * users // 4 + users, rows)),
                    "host": names("h", rng.integers(0, 40, rows), 2),
                    "region": names("r", rng.integers(0, 3, rows), 1),
                    "v": pa.array(rng.random(rows) * 200.0 - 80.0, mask=rng.random(rows) < 0.05),
                    "lat": pa.array(rng.random(rows) * 10.0),
                }
            )
        )
    return tuple(out)


@functools.lru_cache(maxsize=None)
def count_blocks() -> tuple[pa.Table, ...]:
    """Counts that do not tie at either end (a mass tie at the k-th value is a way out, tested below):
    of 6,000 users the first 30 hold 1..30 rows, the last 30 hold 100..129, the middle 30 + i % 7;
    every fifth row's v is NULL, and a user's rows are dealt over three blocks."""
    i = np.arange(6_000)
    users = np.repeat(i, np.where(i < 30, i + 1, np.where(i >= 5_970, i - 5_870, 30 + i % 7)))
    assert len(users) >= 3 * BLOCK_ROWS
    nulls = np.arange(len(users)) % 5 == 0
    return tuple(
        pa.table({"user": names("u", users[b::3]), "v": pa.array(np.ones(len(users[b::3])), mask=nulls[b::3])}) for b in range(3)
    )


AGGREGATES = {
    "sum": ("sum(v)", "user", blocks),
    "count": ("count(v)", "user", count_blocks),
    "count_star": ("count(*)", "user", count_blocks),
    "min": ("min(lat)", "user", blocks),
    "max": ("max(lat)", "user, host", blocks),
}


@pytest.mark.parametrize("direction", ["DESC", "ASC"])
@pytest.mark.parametrize("agg", sorted(AGGREGATES))
def test_device_merge_equals_host_merge(monkeypatch, agg, direction):
    expr, keys, make = AGGREGATES[agg]
    sql = f"SELECT {keys}, count(*) c, {expr} x FROM t GROUP BY {keys} ORDER BY x {direction} LIMIT 12"
    dev, host, routes = both(sql, make(), monkeypatch)
    assert len(host) == 12
    assert_same_answer(dev, host, "x")
    assert routes["merge_device"] == 1 and routes["merge_host"] == 0 and "merge_host_reason" not in routes
    assert routes["merge_entries"] > 0 and routes["merge_survivors"] >= 12


@functools.lru_cache(maxsize=None)
def timebin_blocks() -> tuple[pa.Table, ...]:
    rng = np.random.default_rng(9)
    base_ms = 1_714_521_600_000
    out = []
    for b in (1, 0, 2):  # the second block lies before the first: a negative lane
        ms = base_ms + rng.integers(b * 1_200_000, b * 1_200_000 + 1_800_000, BLOCK_ROWS)
        out.append(
            pa.table(
                {
                    DEFAULT_TIMESTAMP_KEY: pa.array(ms.astype("datetime64[ms]"), pa.timestamp("ms")),
                    "user": names("u", rng.integers(0, 900, BLOCK_ROWS)),
                    "v": pa.array(rng.random(BLOCK_ROWS) * 50.0 - 10.0),
                }
            )
        )
    return tuple(out)


@functools.lru_cache(maxsize=None)
def null_key_blocks() -> tuple[pa.Table, ...]:
    rng = np.random.default_rng(13)
    out = []
    for _ in range(3):
        uid = rng.integers(0, 5_000, BLOCK_ROWS)
        out.append(pa.table({"user": pc.if_else(pa.array(uid % 5 != 0), names("u", uid), None), "v": pa.array(rng.random(BLOCK_ROWS))}))
    return tuple(out)


@functools.lru_cache(maxsize=None)
def null_agg_blocks() -> tuple[pa.Table, ...]:
    """Ten groups with a value; every other group's aggregate is NULL in every block."""
    rng = np.random.default_rng(31)
    out = []
    for _ in range(3):
        uid = rng.integers(0, 3_000, BLOCK_ROWS)
        out.append(pa.table({"user": names("u", uid), "v": pa.array(rng.random(BLOCK_ROWS), mask=uid >= 10)}))
    return tuple(out)


# name -> (SQL, blocks, the column that orders)
SHAPES = {
    "offset": ("SELECT user, sum(v) x FROM t GROUP BY user ORDER BY x DESC LIMIT 5 OFFSET 7", blocks, "x"),
    "where_empties_groups": (
        "SELECT user, host, count(*) c, sum(v) x FROM t WHERE v > 90 GROUP BY user, host ORDER BY x LIMIT 9", blocks, "x",
    ),
    "null_keys": ("SELECT user, count(*) x, sum(v) s FROM t GROUP BY user ORDER BY x DESC LIMIT 3", null_key_blocks, "x"),
    "null_aggregates_fill_the_tail": ("SELECT user, sum(v) x FROM t GROUP BY user ORDER BY x DESC LIMIT 10", null_agg_blocks, "x"),
    "mixed_sign_ascending": ("SELECT user, sum(v) x, count(*) c FROM t GROUP BY user ORDER BY x ASC LIMIT 10", blocks, "x"),
    "spans_many_blocks": (
        "SELECT user, sum(v) x, min(lat) mn, max(lat) mx FROM t GROUP BY user ORDER BY x DESC LIMIT 10",
        lambda: blocks(n_blocks=7, users=3_000),
        "x",
    ),
    "timebin_and_dict": (
        "SELECT date_bin(interval '1 minute', p_timestamp) b, user, count(*) c, sum(v) x "
        "FROM t GROUP BY b, user ORDER BY x DESC LIMIT 10",
        timebin_blocks,
        "x",
    ),
    "three_keys": (
        "SELECT user, host, region, count(*) c, sum(v) x FROM t GROUP BY user, host, region ORDER BY x DESC LIMIT 10",
        blocks,
        "x",
    ),
    "order_by_expression_of_the_select_list": (
        "SELECT user, max(lat) FROM t GROUP BY user ORDER BY max(lat) DESC LIMIT 4", blocks, "max(lat)",
    ),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_device_merge_over_the_shapes_a_query_takes(monkeypatch, shape):
    sql, make, order_col = SHAPES[shape]
    dev, host, routes = both(sql, make(), monkeypatch)
    assert host
    assert_same_answer(dev, host, order_col)
    assert routes["merge_device"] == 1 and routes["merge_host"] == 0


def test_pair_compacted_blocks_merge_on_the_device(monkeypatch):
    """The cell's own path: the cap product passes LOCAL_G_MAX, np.unique compacts the pairs (first key major)."""
    monkeypatch.setattr(ET, "LOCAL_G_MAX", 1 << 14)  # 512 x 64 slots pass it; a block's 12,000 pairs do not
    sql = "SELECT user, host, count(*) c, sum(v) x FROM t GROUP BY user, host ORDER BY x DESC LIMIT 10"
    dev, host, routes = both(sql, blocks(users=300), monkeypatch)
    assert_same_answer(dev, host, "x")
    assert routes["merge_device"] == 1
    assert any(k[0] == "local" and k[3] and k[3][0][0] == "pair" for k in ET._PROGRAM_CACHE)


def test_the_margin_keeps_a_group_that_f32_totals_would_drop(monkeypatch):
    """Two groups over 16 blocks whose f32 totals order one way and whose
    f64 sums order the other: a selection without the margin would keep
    the wrong one for LIMIT 1; the answer comes out in the f64 order."""
    big, small = np.float32(2.0**24), np.float32(1.0)
    per_block = {
        # f32: 2^24 + 1 + 1 + ... stays 2^24 (each 1 is half an ulp, ties to even); f64: 2^24 + 15
        "a": [big] + [small] * 15,
        # f32 and f64: 2^24 + 8
        "b": [big + np.float32(8.0)] + [np.float32(0.0)] * 15,
    }
    tables = []
    for b in range(16):
        # the filler leaves the dense path and keeps a table a block of its own; its sums lie far below the two
        tables.append(
            pa.table(
                {
                    "k": pa.concat_arrays([pa.array(["a", "b"]), names("f", np.arange(BLOCK_ROWS) % 2_000)]),
                    "v": pa.array([float(per_block["a"][b]), float(per_block["b"][b])] + [1.0] * BLOCK_ROWS),
                }
            )
        )
    f32 = {g: np.float32(0.0) for g in per_block}
    for g, xs in per_block.items():
        for x in xs:
            f32[g] = np.float32(f32[g] + x)
    f64 = {g: float(np.sum(np.asarray(xs, np.float64))) for g, xs in per_block.items()}
    assert f32["b"] > f32["a"] and f64["a"] > f64["b"], "the case no longer separates the two orders"
    sql = "SELECT k, sum(v) x FROM t GROUP BY k ORDER BY x DESC LIMIT 1"
    dev, host, routes = both(sql, tables, monkeypatch)
    assert routes["merge_device"] == 1 and routes["merge_survivors"] >= 2
    assert dev == host == [{"k": "a", "x": f64["a"]}]


# ------------------------------------------------------------ every way out: the reason, and an unchanged answer


def cpu_forced_block(monkeypatch):
    """The third block's key column is missing from the batch: a declared UnsupportedOnDevice, folded on the CPU."""
    real = ET.TpuQueryExecutor._local_block
    calls = []

    def local_block(self, *args, **kwargs):
        calls.append(1)
        if len(calls) == 3:
            raise ET.UnsupportedOnDevice("test: this block goes to the CPU")
        return real(self, *args, **kwargs)

    monkeypatch.setattr(ET.TpuQueryExecutor, "_local_block", local_block)


def dense_epoch_first() -> tuple[pa.Table, ...]:
    """A low-cardinality block, then the explosion: the dense epoch is flushed to a partial as local mode begins."""
    rng = np.random.default_rng(5)
    low = pa.table(
        {
            "user": names("u", rng.integers(0, 20, BLOCK_ROWS)),
            "host": names("h", np.zeros(BLOCK_ROWS, np.int64), 2), "region": names("r", np.zeros(BLOCK_ROWS, np.int64), 1),
            "v": pa.array(rng.random(BLOCK_ROWS)), "lat": pa.array(rng.random(BLOCK_ROWS)),
        }
    )
    return (low, *blocks())


TOPK = "SELECT user, count(*) c, sum(v) x FROM t GROUP BY user ORDER BY x DESC LIMIT 10"
# name -> (SQL, blocks, what to patch, mesh, the reason, the column that orders (None: compare as sets))
WAYS_OUT = {
    "no_limit": ("SELECT user, count(*) c, sum(v) x FROM t GROUP BY user ORDER BY x DESC", blocks, None, False, "plan", None),
    "having": (
        "SELECT user, count(*) c, sum(v) x FROM t GROUP BY user HAVING count(*) > 2 ORDER BY x DESC LIMIT 10",
        blocks, None, False, "plan", "x",
    ),
    "order_by_avg": ("SELECT user, avg(v) x FROM t GROUP BY user ORDER BY x DESC LIMIT 10", blocks, None, False, "aggregate", "x"),
    "mesh": (TOPK, blocks, None, True, "mesh", "x"),
    "a_block_on_the_cpu": (TOPK, blocks, cpu_forced_block, False, "host_partials", "x"),
    "dense_epoch_before_local_mode": (TOPK, dense_epoch_first, None, False, "host_partials", "x"),
    "entry_budget": (TOPK, blocks, lambda m: m.setattr(ET, "MERGE_DEVICE_MAX_ENTRIES", 20_000), False, "budget", "x"),  # 8,192 slots a block
    "more_ties_than_survivors_gathered": (
        "SELECT user, count(*) x FROM t GROUP BY user ORDER BY x DESC LIMIT 10 OFFSET 30",
        count_blocks,  # the middle 5,940 users tie seven ways: ask for them by skipping the distinct top
        lambda m: m.setattr(ET, "SURVIVORS_MAX", 16), False, "survivors", "x",
    ),
}


@pytest.mark.parametrize("way", sorted(WAYS_OUT))
def test_every_way_out_says_why_and_changes_no_answer(monkeypatch, way):
    sql, make, patch, mesh, reason, order_col = WAYS_OUT[way]
    tables = make()
    with monkeypatch.context() as m:
        m.setattr(ET, "MERGE_DEVICE_MAX_ENTRIES", 0)
        want = execute(sql, tables)[0].to_pylist()
    if patch is not None:
        patch(monkeypatch)
    got, routes = execute(sql, tables, mesh=mesh)
    assert routes["merge_device"] == 0 and routes["merge_host"] == 1
    assert routes["merge_host_reason"] == reason
    got = got.to_pylist()
    if way == "a_block_on_the_cpu":
        assert routes["cpu_fallback"] == 1
        # the CPU folds that block in f64: the sums agree to f32's width, the order stays
        assert [r["user"] for r in got] == [r["user"] for r in want]
        assert [r["x"] for r in got] == pytest.approx([r["x"] for r in want], rel=1e-5)
    elif order_col is None:
        assert sorted(got, key=lambda r: r["user"]) == sorted(want, key=lambda r: r["user"])
    elif way == "mesh":
        # shards of a block are psum-med in another order than one device adds them
        assert [r["x"] for r in got] == pytest.approx([r["x"] for r in want], rel=1e-5)
    else:
        assert_same_answer(got, want, order_col)
    if way == "more_ties_than_survivors_gathered":
        assert routes["merge_survivors"] > 16 and routes["merge_entries"] > 0
    if way == "entry_budget":
        # two blocks were kept, the third passed the budget: all three reach the host merge
        assert routes["merge_entries"] == 0 and routes.readbacks == 3


def test_a_dense_query_never_decides(monkeypatch):
    monkeypatch.setattr(ET, "DENSE_G_MAX", 1 << 19)
    out, routes = execute("SELECT host, count(*) c, sum(v) x FROM t GROUP BY host ORDER BY x DESC LIMIT 3", blocks())
    assert out.num_rows == 3
    assert [routes[k] for k in MERGE_KEYS] == [0, 0, 0, 0] and "merge_host_reason" not in routes


# ------------------------------------------------------------ counters, phases, spans


def test_counters_phases_and_spans_of_a_device_merge():
    def merges(path: str) -> float:
        return metrics.REGISTRY.get_sample_value("parseable_tpu_device_merges_total", {"path": path}) or 0.0

    before = {p: merges(p) for p in ("device", "host")}
    tables = blocks()
    sql = "SELECT user, host, count(*) c, sum(v) x FROM t GROUP BY user, host ORDER BY x DESC LIMIT 10"
    telemetry.clear_recent_spans()
    with telemetry.trace_context() as trace_id:
        ex = ET.TpuQueryExecutor(build_plan(parse_sql(sql)))
        ex.mesh = None
        t0 = time.perf_counter_ns()
        with telemetry.TRACER.span("query.execute"):
            out = ex.execute(iter(tables))
        wall_ns = time.perf_counter_ns() - t0
    assert out.num_rows == 10
    rs = ex.route_stats
    n_keys, n_rows, k_out, n_blocks, run_max = 2, 3, 64, len(tables), 4  # count | pac(sum) | sum; three blocks in a bucket of four
    assert rs["merge_device"] == 1 and rs["merge_host"] == 0
    assert rs["merge_entries"] == n_blocks * 8_192 * 64  # every slot of each block's stride layout: 6,001 users x 41 hosts, to powers of two
    assert 10 <= rs["merge_survivors"] <= k_out
    # nothing left the device per block: the survivors' lanes, then their rows, through `_timed_readback`
    assert rs.readbacks == 2 and rs.blocks == n_blocks
    assert rs["d2h_bytes"] == (n_keys + 1) * k_out * 4 + n_rows * k_out * run_max * 4
    # phases never overlap: their sum stays inside the wall time; the small table still goes through the host merge
    assert sum(rs.ns.values()) <= wall_ns
    assert rs.ns["merge"] > 0 and rs.ns["finalize"] > 0 and rs.ns["dispatch"] > 0 and rs.ns["partial"] > 0
    assert merges("device") - before["device"] == 1 and merges("host") == before["host"]
    spans = {s["name"]: s for s in telemetry.recent_spans(trace_id)}
    assert spans["execute.merge"]["rows"] == rs["merge_entries"]
    assert spans["execute.merge"]["survivors"] == rs["merge_survivors"]
    assert spans["execute.merge"]["parent_span_id"] == spans["query.execute"]["span_id"]
    assert any(k[0] == "merge" for k in ET._PROGRAM_CACHE)
    # a second run of the same query builds nothing
    built = ET.PROGRAM_BUILDS[0]
    ex2 = ET.TpuQueryExecutor(build_plan(parse_sql(sql)))
    ex2.mesh = None
    assert ex2.execute(iter(tables)).to_pylist() == out.to_pylist()
    assert ET.PROGRAM_BUILDS[0] == built and ex2.route_stats["recompiles"] == 0 and ex2.route_stats["programs_built"] == 0


def test_the_merge_program_carries_its_name_and_scopes_and_scatters_nothing():
    import jax.numpy as jnp

    prog = ET._merge_program(128, 2, 3, 2, "sum", 2, 1, True, 3, 8)
    lowered = prog.lower(jnp.zeros((3, 128), jnp.float32), jnp.zeros((2, 128), jnp.int32))
    text = lowered.as_text(debug_info=True)
    assert "jit_executor_merge" in text
    for scope in ("merge/sort", "merge/scan", "merge/topk"):
        assert scope in text, scope
    assert "scatter" not in lowered.as_text()


def test_served_stats_carry_the_merge_counters(parseable, monkeypatch):
    """Through the session: `stats.device_routes` holds the four counters, which the benchmark's window sums."""
    from parseable_tpu.query.session import QuerySession

    from tests.test_execute_phases import PHASE_KEYS, load_stream

    monkeypatch.setattr(ET, "DENSE_G_MAX", 1 << 9)
    monkeypatch.setenv("P_QUERY_RESULT_CACHE_BYTES", "0")
    load_stream(parseable, "merged")
    sess = QuerySession(parseable, engine="tpu")
    real = ET.TpuQueryExecutor.__init__

    def one_device(self, *a, **kw):
        real(self, *a, **kw)
        self.mesh = None

    monkeypatch.setattr(ET.TpuQueryExecutor, "__init__", one_device)
    res = sess.query("SELECT user, sum(bytes) s FROM merged GROUP BY user ORDER BY s DESC LIMIT 5")
    routes, stages = res.stats["device_routes"], res.stats["stages"]
    assert res.table.num_rows == 5
    assert routes["merge_device"] == 1 and routes["merge_host"] == 0 and routes["merge_survivors"] >= 5
    assert all(isinstance(routes[k], int) for k in MERGE_KEYS)
    ex = stages["execute"]
    assert ex["readbacks"] == 2 and ex["merge_ms"] > 0
    assert sum(ex[k] for k in PHASE_KEYS) <= stages["execute_ms"] + 0.005
    plain = sess.query("SELECT user, sum(bytes) s FROM merged GROUP BY user")
    assert plain.stats["device_routes"]["merge_host"] == 1 and plain.stats["device_routes"]["merge_host_reason"] == "plan"
