"""The additive reduction's three routes (ISSUE 33): `kernels.fold_route` picks
the plain one-hot dot, the factored one-hot product or the scatter-add from the
rows a device holds, the group count and the backend; `_factored_additive` is
the middle one; `device_routes.fold_*_blocks` count what each folded block took.

The yardstick of the factored fold is numpy in f64 and `jax.ops.segment_sum`;
of an answer through the executor, the CPU engine over the same tables. All of
it runs on the CPU backend, where the route function never picks the factored
form by itself: the tests that want it there call it directly, or hand the
route function the backend "tpu". What is answered and counted, never a time."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parseable_tpu.ops import kernels as K
from parseable_tpu.query import executor_tpu as ET
from tests.test_operand_packing import assert_same_rows, on_cpu, on_device, planned, stream

# ------------------------------------------------------------- the route table

M = 1 << 20  # a served block's rows; a shard of it on the four-chip mesh is M // 4
EDGE = K.FACTORED_MAX_GROUPS

ROUTES = [
    # the four cells' own folds, by text
    ("flog_lowcard.topk_path_host", M, 8192, "tpu", "factored"),
    ("flog_lowcard.groupby_minute_status", M, 256, "tpu", "onehot"),
    ("flog_lowcard.like_by_status", M, 8, "tpu", "onehot"),
    ("flog_lowcard_x4.topk_path_host", M // 4, 8192, "tpu", "factored"),
    ("flog_lowcard_x4.groupby_minute_status", M // 4, 256, "tpu", "onehot"),
    ("flog_highcard.topk_local_block", M, 1 << 20, "tpu", "scatter"),
    ("flog_highcard.dense_texts", M, 128, "tpu", "onehot"),
    ("tpch_lineitem.q1", M, 6, "tpu", "onehot"),
    ("tpch_lineitem.q6", M, 1, "tpu", "onehot"),
    # the one-hot's element budget, one below and one above, at both row counts
    ("budget_block_below", M, 1024, "tpu", "onehot"),
    ("budget_block_above", M, 1025, "tpu", "factored"),
    ("budget_shard_below", M // 4, 4096, "tpu", "onehot"),
    ("budget_shard_above", M // 4, 4097, "tpu", "factored"),
    # the one-hot's ceiling in groups, where few rows leave the budget room
    ("ceiling_below", 1 << 10, K.MATMUL_MAX_GROUPS, "tpu", "onehot"),
    ("ceiling_above", 1 << 10, K.MATMUL_MAX_GROUPS + 1, "tpu", "factored"),
    # the factored product's upper edge
    ("edge_below", M, EDGE, "tpu", "factored"),
    ("edge_above", M, EDGE + 1, "tpu", "scatter"),
    ("edge_shard_below", M // 4, EDGE, "tpu", "factored"),
    ("edge_shard_above", M // 4, EDGE + 1, "tpu", "scatter"),
    # no systolic array: today's cut to the scatter, and never the factored form
    ("cpu_topk", M, 8192, "cpu", "scatter"),
    ("cpu_small_below", 2048, 2048, "cpu", "onehot"),
    ("cpu_small_above", 2048, 2049, "cpu", "scatter"),
    ("cpu_past_the_edge", M, EDGE + 1, "cpu", "scatter"),
    ("gpu_topk", M, 8192, "gpu", "scatter"),
]


@pytest.mark.parametrize("name,n_rows,num_groups,backend,want", ROUTES, ids=[r[0] for r in ROUTES])
def test_the_route_is_a_table_of_rows_groups_and_backend(name, n_rows, num_groups, backend, want):
    assert K.fold_route(n_rows, num_groups, backend) == want


def test_the_route_asks_the_running_backend_when_given_none():
    assert jax.default_backend() == "cpu"
    assert K.fold_route(M, 8192) == K.fold_route(M, 8192, "cpu") == "scatter"


# ------------------------------------------------- the factored fold by itself


def block(n: int, g: int, seed: int, *, ids=None, keep: float = 0.9, invalid: float = 0.05, n_sum: int = 1):
    """A block as `fused_groupby_block` gets it: ids, mask, summed values (one
    of integers the size of `bytes`, one of small fractions) and validity."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, g, n).astype(np.int32) if ids is None else np.asarray(ids, np.int32)
    mask = rng.random(n) < keep
    vals = np.stack([rng.integers(100, 50_000, n), rng.random(n) * 500])[:n_sum].astype(np.float32)
    valid = rng.random((n_sum + 1, n)) >= invalid  # the n_sum summed inputs and one counted column
    return ids, mask, vals, valid


def rows_of_block(mask, vals, valid):
    vmask = valid & mask[None, :]
    n_sum = vals.shape[0]
    return np.concatenate([mask[None, :], vmask]), np.where(vmask[:n_sum], vals, np.float32(0)).astype(np.float32)


def by_bincount(ids, rows, g):
    return np.stack([np.bincount(ids, weights=r.astype(np.float64), minlength=g) for r in rows]) if len(rows) else np.zeros((0, g))


def by_segment_sum(ids, rows, g):
    return np.stack([np.asarray(jax.ops.segment_sum(jnp.asarray(r, jnp.float32), jnp.asarray(ids), num_segments=g)) for r in rows]) if len(rows) else np.zeros((0, g), np.float32)


def check_factored(ids, mask, vals, valid, g):
    count_rows, sum_rows = rows_of_block(mask, vals, valid)
    fn = jax.jit(K._factored_additive, static_argnames=("num_groups",))
    cnt, sm = (np.asarray(x) for x in fn(jnp.asarray(ids), jnp.asarray(count_rows), jnp.asarray(sum_rows), num_groups=g))
    assert cnt.shape == (count_rows.shape[0], g) and sm.shape == (sum_rows.shape[0], g)
    # counts: exactly the f64 reference's and segment_sum's
    assert np.array_equal(cnt.astype(np.float64), by_bincount(ids, count_rows, g))
    assert np.array_equal(cnt, by_segment_sum(ids, count_rows, g))
    # sums: within f32 of the f64 reference, as segment_sum is
    want_sm = by_bincount(ids, sum_rows, g)
    scale = np.maximum(1.0, np.abs(want_sm))
    assert np.max(np.abs(sm - want_sm) / scale, initial=0.0) <= 1e-6
    assert np.max(np.abs(sm - by_segment_sum(ids, sum_rows, g)) / scale, initial=0.0) <= 2e-6
    return cnt, sm


GROUPS = [2_048, 8_192, 12_000, 32_768]  # 12,000 is no multiple of FACTORED_G_LO: padded up, cut back


@pytest.mark.parametrize("g", GROUPS)
@pytest.mark.parametrize("n_sum", [0, 1, 2])
def test_the_factored_fold_is_the_f64_references_and_segment_sums(g, n_sum):
    check_factored(*block(4_096, g, seed=g + n_sum, n_sum=n_sum), g)


@pytest.mark.parametrize("n", [1, 127, 1_000, K.FACTORED_ROW_TILE, K.FACTORED_ROW_TILE + 5, 3 * K.FACTORED_ROW_TILE + 1_001])
def test_any_row_count_folds(n):
    """Less than a row tile, whole tiles, and whole tiles with a rest that is no multiple of a lane."""
    check_factored(*block(n, 8_192, seed=n), 8_192)


@pytest.mark.parametrize("g", [8_192, 12_000])
def test_all_rows_masked_gives_zeros(g):
    ids, mask, vals, valid = block(2_048, g, seed=3, keep=0.0)
    cnt, sm = check_factored(ids, mask, vals, valid, g)
    assert not cnt.any() and not sm.any()


def test_an_invalid_value_is_no_addend_whatever_it_holds():
    ids, mask, vals, valid = block(4_096, 8_192, seed=5, invalid=0.5)
    vals = np.where(valid[:1], vals, np.float32(np.nan))  # what a null slot may hold
    cnt, sm = check_factored(ids, mask, vals, valid, 8_192)
    assert np.isfinite(sm).all() and sm.sum() > 0


@pytest.mark.parametrize("g", GROUPS)
def test_every_row_in_the_last_group(g):
    n = 3_000
    ids, mask, vals, valid = block(n, g, seed=g, ids=np.full(n, g - 1), invalid=0.0)
    cnt, sm = check_factored(ids, mask, vals, valid, g)
    assert cnt[0, g - 1] == mask.sum() and not cnt[:, : g - 1].any() and not sm[:, : g - 1].any()


def test_a_ten_row_group_beside_a_100k_row_group():
    """chip_smoke.py's sparse-group check at the factored route's own shape: a
    reduced-precision multiply would miss the small group's sum by 1e-4 and more."""
    g, big, small = 8_192, 4_097, 4_098  # neighbours in one high row
    rng = np.random.default_rng(11)
    ids = np.concatenate([np.full(100_000, big), np.full(10, small), rng.integers(0, g, 2_000)]).astype(np.int32)
    rng.shuffle(ids)
    ids, mask, vals, valid = block(len(ids), g, seed=12, ids=ids, keep=1.0, invalid=0.0)
    cnt, sm = check_factored(ids, mask, vals, valid, g)
    assert cnt[0, big] >= 100_000 and 10 <= cnt[0, small] <= 12
    want = vals[0][ids == small].astype(np.float64).sum()
    assert abs(sm[0, small] - want) / want <= 1e-6


# ------------------------------------- the whole kernel with the route handed in


def _as_on_a_tpu(monkeypatch):
    real = K.fold_route
    monkeypatch.setattr(K, "fold_route", lambda n_rows, num_groups, backend=None: real(n_rows, num_groups, "tpu"))
    # a test's block is 2,048 rows where a served one is 2^20: the one-hot's budget and the row tile in proportion,
    # so that a block is 21 tiles and a rest of 32 rows, and a mesh device's 256 rows two tiles and a rest of 64
    monkeypatch.setattr(K, "MATMUL_MAX_ONEHOT_ELEMS", 1 << 18)
    monkeypatch.setattr(K, "FACTORED_ROW_TILE", 96)


def _clear_traced():
    K.fused_groupby_block.clear_cache()
    ET._PROGRAM_CACHE.clear()


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The route function answers as it would on a TPU, so the CPU backend runs
    the factored product where a chip would. The choice is baked into a traced
    program: caches are emptied on the way in and on the way out."""
    _clear_traced()
    _as_on_a_tpu(monkeypatch)
    yield
    monkeypatch.undo()
    _clear_traced()


def fused(ids, mask, vals, valid, g):
    """sum(vals[0]), min(vals[1]), max(vals[2]) by the whole kernel."""
    a = [jnp.asarray(x) for x in (ids, mask, vals[:1], vals[1:2], vals[2:], valid)]
    return [np.asarray(x) for x in K.fused_groupby_block(*a, g, 1, 1, 1)]


@pytest.mark.parametrize("g", [4_096, 12_000])
def test_the_kernels_factored_branch_gives_what_its_scatter_branch_gives(g, monkeypatch):
    n = 2_048
    rng = np.random.default_rng(g)
    ids = rng.integers(0, g, n).astype(np.int32)
    mask = rng.random(n) < 0.9
    vals = np.stack([rng.integers(100, 50_000, n), rng.random(n) * 500, rng.random(n) * 9]).astype(np.float32)
    valid = rng.random((3, n)) >= 0.05
    _clear_traced()
    assert K.fold_route(n, g) == "scatter"
    want = fused(ids, mask, vals, valid, g)
    _as_on_a_tpu(monkeypatch)
    _clear_traced()
    try:
        assert K.fold_route(n, g) == "factored"
        got = fused(ids, mask, vals, valid, g)
    finally:
        monkeypatch.undo()
        _clear_traced()
    count, per_agg, sums, mins, maxs = range(5)
    assert got[count].sum() == mask.sum()
    for k in (count, per_agg, mins, maxs):  # min / max stay on the segment ops, untouched by the route
        assert np.array_equal(got[k], want[k])
    assert np.max(np.abs(got[sums] - want[sums]) / np.maximum(1.0, np.abs(want[sums]))) <= 2e-6


NON_FINITE = {
    "inf": ([np.inf], np.inf),
    "minus_inf": ([-np.inf], -np.inf),
    "nan": ([np.nan], np.nan),
    "inf_and_minus_inf": ([np.inf, -np.inf], np.nan),
    "inf_twice": ([np.inf, np.inf], np.inf),
}


@pytest.mark.parametrize("case", NON_FINITE)
@pytest.mark.parametrize("g", [4_096, 12_000])
def test_a_valid_non_finite_value_stays_in_its_own_group(g, case, monkeypatch):
    """A valid inf or NaN is an addend like any other: its group's sum is what `segment_sum` makes of it, and no
    other group's moves. In a one-hot product it would meet the zeros of its column and be NaN in every group there."""
    held, want_sum = NON_FINITE[case]
    n, bad = 2_048, 4_000  # the group that holds them; 3,968-4,095 share its high row
    rng = np.random.default_rng(g)
    ids = rng.integers(0, g, n).astype(np.int32)
    mask = np.ones(n, bool)
    vals = np.stack([rng.integers(100, 50_000, n), rng.random(n) * 500, rng.random(n) * 9]).astype(np.float32)
    valid = rng.random((3, n)) >= 0.05
    at = 700 + 600 * np.arange(len(held))  # in two row tiles where there are two
    ids[at], valid[0, at], vals[0, at] = bad, True, held
    ids[at + 1], valid[0, at + 1] = bad + 1, True  # a finite neighbour in the same high row
    _clear_traced()
    assert K.fold_route(n, g) == "scatter"
    want = fused(ids, mask, vals, valid, g)
    _as_on_a_tpu(monkeypatch)
    _clear_traced()
    try:
        assert K.fold_route(n, g) == "factored"
        got = fused(ids, mask, vals, valid, g)
    finally:
        monkeypatch.undo()
        _clear_traced()
    count, per_agg, sums, mins, maxs = range(5)
    for k in (count, per_agg):
        assert np.array_equal(got[k], want[k])
    assert np.array_equal(got[sums][0, bad], np.float32(want_sum), equal_nan=True)
    assert np.array_equal(want[sums][0, bad], np.float32(want_sum), equal_nan=True)
    others = np.arange(g) != bad
    assert np.isfinite(got[sums][0, others]).all() and got[sums][0, bad + 1] > 0
    assert np.max(np.abs(got[sums][0, others] - want[sums][0, others]) / np.maximum(1.0, np.abs(want[sums][0, others]))) <= 2e-6


# ----------------------------------------------- the counters, through the executor

MESH = [pytest.param(False, id="one_device"), pytest.param(True, id="mesh8")]
# host x status: 8 x 8 slots, the plain one-hot on any backend; user x host: 512 x 8 = 4,096 slots over 2,048 rows
# (256 a device on the mesh), past the 2^22 elements a backend without an MXU gives the one-hot
SMALL_G = "SELECT host, status, count(*) c, sum(bytes) s FROM t GROUP BY host, status"
LARGE_G = "SELECT user, host, count(*) c, sum(bytes) s, count(bytes) n FROM t GROUP BY user, host"
FOLDS = ("fold_onehot_blocks", "fold_factored_blocks", "fold_scatter_blocks")


@pytest.mark.parametrize("mesh", MESH)
def test_every_folded_block_is_counted_under_one_route(mesh):
    tables = stream(f"routes-{mesh}", 11)
    out, rs = on_device(SMALL_G, tables, mesh)
    assert [rs[k] for k in FOLDS] == [11, 0, 0] and rs.blocks == 11
    assert_same_rows(out, on_cpu(SMALL_G, tables))
    out, rs = on_device(LARGE_G, tables, mesh)
    folded = {k: rs[k] for k in FOLDS}
    assert sum(folded.values()) == rs.blocks == 11 and folded["fold_factored_blocks"] == 0
    # one device: 2,048 rows x 4,096 slots is past the budget; a mesh device's 256 rows x 4,096 are not
    assert folded["fold_scatter_blocks"] == (0 if mesh else 11)
    assert_same_rows(out, on_cpu(LARGE_G, tables))


@pytest.mark.parametrize("mesh", MESH)
def test_on_a_tpu_the_same_stream_takes_the_factored_product_and_answers_the_same(mesh, as_on_a_tpu):
    tables = stream(f"factored-{mesh}", 11)
    out, rs = on_device(LARGE_G, tables, mesh)
    assert [rs[k] for k in FOLDS] == [0, 11, 0] and rs.blocks == 11
    assert_same_rows(out, on_cpu(LARGE_G, tables))
    out, rs = on_device(SMALL_G, tables, mesh)
    assert [rs[k] for k in FOLDS] == [11, 0, 0]
    assert_same_rows(out, on_cpu(SMALL_G, tables))


def _asks(monkeypatch) -> dict[str, set]:
    """Who asked the route function what: the kernel, while a program is traced, with the shapes it really holds;
    the counter (`_note_fold_route`), with the rows and groups the host derives."""
    asks = {"kernel": set(), "counter": set()}
    by = ["kernel"]
    real_route, real_note = K.fold_route, ET.TpuQueryExecutor._note_fold_route

    def route(n_rows, num_groups, backend=None):
        got = real_route(n_rows, num_groups, backend)
        asks[by[0]].add((n_rows, num_groups, got))
        return got

    def note(self, *a):
        by[0] = "counter"
        try:
            real_note(self, *a)
        finally:
            by[0] = "kernel"

    monkeypatch.setattr(K, "fold_route", route)
    monkeypatch.setattr(ET.TpuQueryExecutor, "_note_fold_route", note)
    return asks


@pytest.mark.parametrize("mesh", MESH)
@pytest.mark.parametrize("sql", [SMALL_G, LARGE_G], ids=["small_g", "large_g"])
def test_the_counter_asks_what_the_traced_kernel_asked(sql, mesh, monkeypatch):
    """The counter is ticked on the host from shapes derived there. What ties it to the program that ran: the
    kernel, traced for that program, asked the same function with the same rows and groups, and got the same."""
    _clear_traced()
    asks = _asks(monkeypatch)
    try:
        _, rs = on_device(sql, stream(f"asks-{mesh}", 3), mesh)
    finally:
        monkeypatch.undo()
        _clear_traced()
    assert asks["kernel"] and asks["kernel"] == asks["counter"], asks
    ((_, _, route),) = asks["counter"]
    assert rs[f"fold_{route}_blocks"] == rs.blocks == 3


def test_the_block_local_counter_asks_what_the_traced_kernel_asked(monkeypatch):
    monkeypatch.setattr(ET, "DENSE_G_MAX", 1 << 10)
    _clear_traced()
    asks = _asks(monkeypatch)
    try:
        ex = ET.TpuQueryExecutor(planned(LARGE_G, 3))
        ex.mesh = None
        ex.execute(iter(stream("asks-local", 3)))
    finally:
        monkeypatch.undo()
        _clear_traced()
    assert asks["kernel"] and asks["kernel"] == asks["counter"], asks


def test_a_block_local_fold_is_counted_too(monkeypatch):
    """Past `DENSE_G_MAX` a GROUP BY folds block by block on its own codes (`_local_block`): one tick a block."""
    monkeypatch.setattr(ET, "DENSE_G_MAX", 1 << 10)
    tables = stream("local-routes", 5)
    ex = ET.TpuQueryExecutor(planned(LARGE_G, 5))
    ex.mesh = None
    out = ex.execute(iter(tables))
    rs = ex.route_stats
    assert rs["cpu_fallback"] == 0 and rs["merge_host"] + rs["merge_device"] == 1
    assert sum(rs[k] for k in FOLDS) == rs.blocks == 5
    assert_same_rows(out, on_cpu(LARGE_G, tables))
