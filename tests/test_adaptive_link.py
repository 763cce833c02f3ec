"""Adaptive link dispatch: non-resident blocks route to the CPU when the
measured link makes shipping a losing trade, and warm the device hot set
in the background (ops/link.py; the slow-link counterpart of the
reference's data-local DataFusion execution,
/root/reference/src/query/mod.rs)."""

from __future__ import annotations

import time

import numpy as np
import pyarrow as pa
import pytest

from parseable_tpu.ops import link as L
from parseable_tpu.ops.hotset import get_hotset
from parseable_tpu.query import executor_tpu as ET
from parseable_tpu.query.executor import QueryExecutor
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql


@pytest.fixture()
def fresh_link(monkeypatch):
    prof = L.LinkProfile()
    monkeypatch.setattr(L, "get_link", lambda options=None: prof)
    return prof


def _table(n: int = 1 << 17, seed: int = 3) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "user": pa.array([f"u{int(x)}" for x in rng.integers(0, 64, n)]),
            "v": pa.array(rng.integers(0, 100, n).astype(np.float64)),
        }
    )


SQL = "SELECT user, count(*) c, sum(v) s FROM t GROUP BY user"


def run_cpu(tables):
    return QueryExecutor(build_plan(parse_sql(SQL))).execute(iter(tables)).to_pylist()


def run_tpu(tables):
    return (
        ET.TpuQueryExecutor(build_plan(parse_sql(SQL))).execute(iter(tables)).to_pylist()
    )


def norm(rows):
    return sorted((r["user"], r["c"], r["s"]) for r in rows)


def test_slow_link_routes_blocks_to_cpu(fresh_link):
    # teach the profile a terrible link: 1 MB/s both ways, 100ms latency
    for _ in range(20):
        fresh_link.record_h2d(1 << 20, 1.1)
        fresh_link.record_d2h(1 << 20, 1.1)
        fresh_link.record_cpu_agg(1_000_000, 0.05)
    t = _table()
    before = ET.ADAPTIVE_CPU_BLOCKS[0]
    cpu, tpu = run_cpu([t]), run_tpu([t])
    assert ET.ADAPTIVE_CPU_BLOCKS[0] > before, "block was not routed to CPU"
    assert norm(cpu) == norm(tpu)


def test_fast_link_keeps_blocks_on_device(fresh_link):
    # defaults are optimistic (healthy link): the device path must be taken
    t = _table(seed=5)
    before = ET.ADAPTIVE_CPU_BLOCKS[0]
    cpu, tpu = run_cpu([t]), run_tpu([t])
    assert ET.ADAPTIVE_CPU_BLOCKS[0] == before
    assert norm(cpu) == norm(tpu)


def test_routed_block_warms_hotset_in_background(fresh_link):
    for _ in range(20):
        fresh_link.record_h2d(1 << 20, 1.1)
        fresh_link.record_cpu_agg(1_000_000, 0.05)
    src = b"adaptive-test-source-1"
    real = _table(seed=7)
    stub_free = real.replace_schema_metadata({ET.SOURCE_ID_META: src})
    lp = build_plan(parse_sql(SQL))
    ex = ET.TpuQueryExecutor(lp)
    before = ET.ADAPTIVE_CPU_BLOCKS[0]
    out = ex.execute(iter([stub_free]))
    assert ET.ADAPTIVE_CPU_BLOCKS[0] > before
    assert norm(out.to_pylist()) == norm(run_cpu([real]))
    # the background warmer ships the block so the NEXT query is resident
    key = ET.hot_key(src, lp.needed_columns, {"user"})
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not get_hotset().contains(key):
        time.sleep(0.1)
    assert get_hotset().contains(key), "background warm did not land"


def test_adaptive_off_env(fresh_link, monkeypatch):
    monkeypatch.setenv("P_TPU_ADAPTIVE", "0")
    for _ in range(20):
        fresh_link.record_h2d(1 << 20, 1.1)
    t = _table(seed=9)
    before = ET.ADAPTIVE_CPU_BLOCKS[0]
    run_tpu([t])
    assert ET.ADAPTIVE_CPU_BLOCKS[0] == before


def test_slow_link_routes_select_filter_to_cpu(fresh_link):
    for _ in range(20):
        fresh_link.record_h2d(1 << 20, 1.1)
        fresh_link.record_d2h(1 << 20, 1.1)
        fresh_link.record_cpu_agg(1_000_000, 0.05)
    t = _table(seed=11)
    sql = "SELECT user, v FROM t WHERE v > 50.0"
    before = ET.ADAPTIVE_CPU_BLOCKS[0]
    cpu = QueryExecutor(build_plan(parse_sql(sql))).execute(iter([t])).to_pylist()
    tpu = ET.TpuQueryExecutor(build_plan(parse_sql(sql))).execute(iter([t])).to_pylist()
    assert ET.ADAPTIVE_CPU_BLOCKS[0] > before, "filter block not routed to CPU"
    assert sorted(map(str, cpu)) == sorted(map(str, tpu))


def test_link_profile_flush_bypasses_throttle(tmp_path):
    """ADVICE r3 #4: short-lived processes (CLI one-offs, bench
    subprocesses) must persist learned measurements at exit even inside
    the 5s save-throttle window."""
    from parseable_tpu.ops.link import LinkProfile

    path = tmp_path / "link_profile.json"
    prof = LinkProfile(path, device="cpu/cpu")
    prof.record_h2d(1 << 20, 1.0)  # throttled: first save stamps _last_save
    prof.record_h2d(1 << 20, 1.0)
    prof.flush()
    import json as _json

    stored = _json.loads(path.read_text())
    # the slow measurements made it to disk (EWMA moved off the default)
    assert stored["h2d_bw"] == prof.snapshot()["h2d_bw"] < 8e9 * 0.6


def test_link_profile_merge_on_save(tmp_path):
    """Concurrent processes must not clobber each other last-writer-wins:
    keys another process moved on disk average with ours."""
    import json as _json

    from parseable_tpu.ops.link import LinkProfile

    path = tmp_path / "link_profile.json"
    a = LinkProfile(path, device="cpu/cpu")
    b = LinkProfile(path, device="cpu/cpu")  # loads the same (absent) baseline
    for _ in range(30):
        a.record_h2d(1 << 22, 4.0)  # ~1 MB/s: a learns a terrible link
    a.flush()
    a_bw = _json.loads(path.read_text())["h2d_bw"]
    assert a_bw < 1e8
    # b learned nothing about h2d but measured d2h; its save must not
    # reset a's h2d learning back to the optimistic default
    b.record_d2h(1 << 22, 2.0)
    b.flush()
    stored = _json.loads(path.read_text())
    assert stored["h2d_bw"] <= 0.5 * (a_bw + 8e9) + 1e-6
    assert stored["h2d_bw"] < 8e9 * 0.6  # nowhere near the default
    assert stored["d2h_bw"] < 8e9  # b's own measurement persisted


def test_link_profile_ignores_a_file_stamped_for_another_device(tmp_path):
    """A stored profile steers routing only on the device it was measured
    on: a file stamped for another device (or not stamped at all — a file
    from before the stamp) neither loads nor merges into our save, and a
    profile that knows no device persists nothing."""
    import json as _json

    from parseable_tpu.ops.link import _DEFAULTS, LinkProfile

    path = tmp_path / "link_profile.json"
    slow = {**_DEFAULTS, "h2d_bw": 1e6, "d2h_bw": 9e6}
    for stamp in ({"device": "tpu/TPU v5 lite"}, {}):
        path.write_text(_json.dumps({**slow, **stamp}))
        prof = LinkProfile(path, device="cpu/cpu")
        assert prof.snapshot() == _DEFAULTS
        prof.record_d2h(1 << 22, 2.0)
        prof.flush()
        stored = _json.loads(path.read_text())
        assert stored["device"] == "cpu/cpu"
        assert stored["h2d_bw"] == _DEFAULTS["h2d_bw"]  # not averaged with 1e6
    # the same file loads where it was measured
    assert LinkProfile(path, device="cpu/cpu").snapshot()["d2h_bw"] == stored["d2h_bw"]
    # no device known (a CPU-engine process): nothing loaded, nothing written
    before = path.read_text()
    anon = LinkProfile(path)
    assert anon.snapshot() == _DEFAULTS
    anon.record_cpu_agg(1 << 20, 0.5)
    anon.flush()
    assert path.read_text() == before
