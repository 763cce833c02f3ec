"""The dense block loop ships a dispatch group's small operands in one transfer
a dtype (ISSUE 31): `_pack_operands` on the host, `_unpack_operands` inside the
program, `route_stats["operand_puts"]` the count of transfers made.

The yardstick of every answer is the CPU engine over the same tables, row for
row. Everything runs on the CPU backend, on one device and on the tests' eight
virtual devices as a mesh: what is counted and answered, never a time."""

from __future__ import annotations

from datetime import UTC, datetime, timedelta

import numpy as np
import pyarrow as pa
import pytest

from parseable_tpu import DEFAULT_TIMESTAMP_KEY
from parseable_tpu.query import executor_tpu as ET
from parseable_tpu.query.executor import QueryExecutor
from parseable_tpu.query.planner import TimeBounds
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql

BASE = datetime(2024, 6, 1, 0, 0, tzinfo=UTC)
ROWS = 2_048  # a block; divides over the eight virtual devices


def stream(tag: str, n_blocks: int, grow_at: int | None = None, seed: int = 31) -> list[pa.Table]:
    """`n_blocks` blocks of one minute each, kept apart by a source id as scanned parquet files are.
    From block `grow_at` on the hosts are drawn from 40 and not 8: the key's capacity grows there."""
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_blocks):
        hosts = 40 if grow_at is not None and b >= grow_at else 8
        ts = [BASE + timedelta(minutes=b, milliseconds=int(i) * 25) for i in range(ROWS)]
        kind = rng.integers(0, 6, ROWS)
        t = pa.table(
            {
                DEFAULT_TIMESTAMP_KEY: pa.array(ts, pa.timestamp("ms")),
                "host": pa.array([f"h{int(x):02d}" for x in rng.integers(0, hosts, ROWS)]),
                "status": pa.array([("200", "204", "404", "500", "503")[int(x)] for x in rng.integers(0, 5, ROWS)]),
                "message": pa.array([f"GET /p{int(k)} {'error: upstream' if k % 3 == 0 else 'ok'}" for k in kind]),
                "user": pa.array([f"u{int(x):03d}" for x in rng.integers(0, 300, ROWS)]),
                "bytes": pa.array(rng.integers(1, 2_000, ROWS).astype(np.float64), mask=rng.random(ROWS) < 0.03),
            }
        )
        out.append(t.replace_schema_metadata({ET.SOURCE_ID_META: f"{tag}-{seed}-{b}".encode()}))
    return out


def planned(sql: str, n_blocks: int, end_shift_s: int = 0):
    """The plan as the session hands it over: the request's own time range on it (so the bounds travel as
    runtime scalars) and the scan's time range as the hint that sizes a time-bin key once."""
    lp = build_plan(parse_sql(sql))
    lp.time_bounds = TimeBounds(BASE - timedelta(minutes=1), BASE + timedelta(minutes=n_blocks, seconds=-5 - end_shift_s))
    lp.scan_time_hint = (BASE, BASE + timedelta(minutes=n_blocks))
    return lp


def on_device(sql: str, tables: list[pa.Table], mesh: bool, end_shift_s: int = 0) -> tuple[pa.Table, ET.RouteStats]:
    ex = ET.TpuQueryExecutor(planned(sql, len(tables), end_shift_s))
    if not mesh:
        ex.mesh = None  # one device, as the one-chip cells run
    out = ex.execute(iter(tables))
    assert ex.route_stats["cpu_fallback"] == 0 and ex.route_stats["cpu_adaptive"] == 0, dict(ex.route_stats)
    return out, ex.route_stats


def on_cpu(sql: str, tables: list[pa.Table], end_shift_s: int = 0) -> pa.Table:
    return QueryExecutor(planned(sql, len(tables), end_shift_s)).execute(iter(tables))


def rows_of(t: pa.Table) -> list[tuple]:
    cols = sorted(t.column_names)
    return sorted((tuple(r[c] for c in cols) for r in t.to_pylist()), key=lambda x: tuple(str(v) for v in x))


def assert_same_rows(dev: pa.Table, cpu: pa.Table, rel: float = 1e-5) -> None:
    a, b = rows_of(dev), rows_of(cpu)
    assert len(a) == len(b) and len(a) > 0
    for ra, rb in zip(a, b):
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and isinstance(vb, float):
                assert va == pytest.approx(vb, rel=rel), (ra, rb)
            else:
                assert va == vb, (ra, rb)


# (SQL, dtypes its operands have: a LIKE's table is bool, time scalars and remaps are int32)
TEXTS = {
    "like_minute_bounds": (
        "SELECT date_bin(interval '1 minute', p_timestamp) m, status, count(*) c, sum(bytes) s FROM t "
        "WHERE message LIKE '%error%' GROUP BY m, status",
        2,
    ),
    "two_dict_keys": ("SELECT host, status, count(*) c, sum(bytes) s, min(bytes) lo FROM t GROUP BY host, status", 1),
}
MESH = [pytest.param(False, id="one_device"), pytest.param(True, id="mesh8")]


@pytest.mark.parametrize("mesh", MESH)
@pytest.mark.parametrize("text", sorted(TEXTS))
def test_a_warm_stream_of_16_blocks_makes_one_put_a_dtype_a_group(text, mesh):
    sql, dtypes = TEXTS[text]
    tables = stream(f"warm-{text}-{mesh}", 16)
    on_device(sql, tables, mesh)
    out, rs = on_device(sql, tables, mesh)
    assert rs["device_warm"] == 16 and rs["device_cold"] == 0
    # two groups of GROUP_N = 8 blocks, every block of one signature
    assert rs["operand_puts"] == dtypes * 2 <= 3 * 2
    assert rs["programs_built"] == 0 and rs["recompiles"] == 0
    assert_same_rows(out, on_cpu(sql, tables))
    if mesh:
        # the mesh prices what it puts: the packed buffers hold the operands' own bytes
        assert rs["h2d_bytes"] > 0


@pytest.mark.parametrize("mesh", MESH)
@pytest.mark.parametrize("text", sorted(TEXTS))
def test_a_short_last_group_packs_what_it_has(text, mesh):
    sql, dtypes = TEXTS[text]
    tables = stream(f"short-{text}-{mesh}", 11)
    out, rs = on_device(sql, tables, mesh)
    assert rs.blocks == 11 and rs["operand_puts"] == dtypes * 2  # 8 blocks, then 3
    assert_same_rows(out, on_cpu(sql, tables))


@pytest.mark.parametrize("mesh", MESH)
@pytest.mark.parametrize("grow_at", [8, 5], ids=["between_two_groups", "inside_a_group"])
def test_a_key_capacity_that_grows_mid_scan_dispatches_the_old_epoch_first(grow_at, mesh):
    sql, dtypes = TEXTS["two_dict_keys"]
    tables = stream(f"grow-{grow_at}-{mesh}", 16, grow_at=grow_at)
    out, rs = on_device(sql, tables, mesh)
    # the epoch's change ends the group that is pending: [0, grow_at) goes under the old layout
    groups = 2 if grow_at == 8 else 3
    assert rs["operand_puts"] == dtypes * groups
    assert_same_rows(out, on_cpu(sql, tables))
    assert {r["host"] for r in out.to_pylist()} >= {"h00", "h39"}


@pytest.mark.parametrize("mesh", MESH)
def test_another_end_time_runs_the_same_programs(mesh):
    sql, dtypes = TEXTS["like_minute_bounds"]
    tables = stream(f"endtime-{mesh}", 16)
    first, _ = on_device(sql, tables, mesh)
    out, rs = on_device(sql, tables, mesh, end_shift_s=90)
    assert rs["programs_built"] == 0 and rs["recompiles"] == 0 and rs["programs_reused"] == 2
    assert rs["operand_puts"] == dtypes * 2
    assert_same_rows(out, on_cpu(sql, tables, end_shift_s=90))
    # the bound did its work: the last minutes' rows are not in the second answer
    assert sum(r["c"] for r in out.to_pylist()) < sum(r["c"] for r in first.to_pylist())


# the distinct remaps (a global dictionary's, and an HLL table of two rows a value) and the percentile's
# histogram accumulators travel beside the others
SKETCHES = {
    "count_distinct": ("SELECT status, count(distinct user) d, count(*) c FROM t GROUP BY status", 1e-5),
    "approx_distinct": ("SELECT status, approx_distinct(user) d, count(*) c FROM t GROUP BY status", 0.1),
    "percentile": ("SELECT status, approx_percentile_cont(bytes, 0.5) p, count(*) c FROM t GROUP BY status", 0.06),
}


@pytest.mark.parametrize("mesh", MESH)
@pytest.mark.parametrize("text", sorted(SKETCHES))
def test_distinct_and_percentile_operands_are_packed_too(text, mesh):
    sql, rel = SKETCHES[text]
    tables = stream(f"sketch-{text}-{mesh}", 11)
    out, rs = on_device(sql, tables, mesh)
    # every `_get_program` look-up is one dispatched group (an HLL table is as long as its block's
    # dictionary, so its blocks change signature, and groups, more often than every eight)
    groups = rs["programs_built"] + rs["programs_reused"]
    assert 2 <= groups <= 11 and 0 < rs["operand_puts"] <= 3 * groups
    assert_same_rows(out, on_cpu(sql, tables), rel=rel)


@pytest.mark.parametrize("mesh", MESH)
def test_a_group_whose_program_is_refused_ships_nothing(mesh, monkeypatch):
    """`operand_puts` counts the transfers of dispatched groups: a layout declared UnsupportedOnDevice
    where its program is looked up folds on the CPU and no operand is put."""

    def refuse(self, *a, **kw):
        raise ET.UnsupportedOnDevice("refused for the test")

    monkeypatch.setattr(ET.TpuQueryExecutor, "_get_program", refuse)
    monkeypatch.setattr(ET, "_pack_operands", lambda blocks: pytest.fail("operands packed for a refused group"))
    sql, _ = TEXTS["like_minute_bounds"]
    tables = stream(f"refused-{mesh}", 11)
    ex = ET.TpuQueryExecutor(planned(sql, len(tables)))
    if not mesh:
        ex.mesh = None
    out = ex.execute(iter(tables))
    rs = ex.route_stats
    assert rs["cpu_fallback"] == 11 and rs["operand_puts"] == 0
    assert_same_rows(out, on_cpu(sql, tables))


def test_pack_and_unpack_are_inverse_value_for_value():
    """Same dtypes, same padding sentinels, same order: what the fold reads is what the host made."""
    rng = np.random.default_rng(7)

    def block() -> tuple:
        luts = (rng.random(8) < 0.5, np.asarray([int(rng.integers(-(2**31) + 2, 2**31 - 2))], np.int32), rng.random(1) < 0.5)
        remaps = (np.where(rng.random(16) < 0.2, np.int32(2**30), rng.integers(0, 9, 16).astype(np.int32)),)
        dremaps = (rng.integers(0, 64, (2, 8)).astype(np.int32),)
        return luts, remaps, dremaps

    blocks = [block() for _ in range(3)]
    sig = tuple(ET._operand_sig(part) for part in blocks[0])
    packed = ET._pack_operands(blocks)
    assert [b.dtype.str for b in packed] == sorted({d for part in sig for d, _ in part})
    assert sum(b.nbytes for b in packed) == sum(a.nbytes for blk in blocks for part in blk for a in part)
    got = ET._unpack_operands(packed, sig, 3)
    for want_block, got_block in zip(blocks, got):
        for want_part, got_part in zip(want_block, got_block):
            assert len(want_part) == len(got_part)
            for w, g in zip(want_part, got_part):
                assert g.dtype == w.dtype and g.shape == w.shape and np.array_equal(g, w)
    assert ET._pack_operands([((), (), ())]) == ()
