"""Tiering under memory pressure: cost-aware hot-set eviction + admission
control, query-aware prefetch under a capped budget (the eviction path can
never regress to dead code), and enccache write-behind backpressure."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from parseable_tpu.ops.hotset import DeviceHotSet, HotEntry, get_hotset
from parseable_tpu.ops.prefetch import ScanPrefetcher


def _entry(nbytes: int) -> HotEntry:
    return HotEntry(dev={}, meta=None, nbytes=nbytes)


# ---------------------------------------------------------------- cost policy


def test_cost_policy_evicts_cheap_before_expensive():
    """Equal heat, different re-ship cost: the cheap-to-refetch block goes
    first (GDSF score = freq * ship_cost/byte)."""
    costs = {100: 0.001, 101: 1.0}  # keyed by size: cheap vs expensive
    hs = DeviceHotSet(budget_bytes=250, ship_cost=costs.get)
    hs.put(("cheap",), _entry(100))
    hs.put(("exp",), _entry(101))
    hs.put(("new", 101), _entry(101))  # needs room: one of the two must go
    assert not hs.contains(("cheap",))
    assert hs.contains(("exp",))
    assert hs.evictions == 1


def test_scan_resistance_one_shot_scan_does_not_flush_dashboard():
    """A hot dashboard working set (touched repeatedly -> protected) must
    survive one full over-budget scan."""
    hs = DeviceHotSet(budget_bytes=1000, ship_cost=lambda n: 0.01)
    for d in range(4):  # dashboard: 800 bytes, re-touched => protected
        hs.put(("dash", d), _entry(200))
    for _ in range(2):
        for d in range(4):
            assert hs.get(("dash", d)) is not None
    for s in range(20):  # one-shot full scan, 5000 bytes through a 1000 cache
        hs.put(("scan", s), _entry(250))
    for d in range(4):
        assert hs.contains(("dash", d)), f"the scan flushed dash{d}"
    # the scan hit the admission gate: first-touch blocks lost to protected
    assert hs.rejected_admission > 0


def test_scan_churns_probation_with_evictions():
    """With free probation room, an over-budget scan churns among its own
    blocks (evictions > 0) while the protected set survives."""
    hs = DeviceHotSet(budget_bytes=1000, ship_cost=lambda n: 0.01)
    for d in range(3):  # 600 bytes protected, 400 free for probation
        hs.put(("dash", d), _entry(200))
    for _ in range(2):
        for d in range(3):
            assert hs.get(("dash", d)) is not None
    for s in range(20):
        hs.put(("scan", s), _entry(200))

    assert hs.evictions > 0
    for d in range(3):
        assert hs.contains(("dash", d)), f"probation churn flushed dash{d}"


def test_ghost_frequency_displaces_stale_protected():
    """Sustained new heat (not a one-shot scan) must eventually displace a
    stale protected set: rejected keys re-enter with their earned ghost
    frequency and out-score entries nobody touches anymore."""
    hs = DeviceHotSet(budget_bytes=400, ship_cost=lambda n: 0.01)
    for d in range(2):
        hs.put(("old", d), _entry(200))
    for _ in range(2):
        for d in range(2):
            hs.get(("old", d))  # freq 3 -> protected
    # the new working set recurs; ghosts accumulate until it wins
    for _ in range(8):
        for k in range(2):
            hs.put(("new", k), _entry(200))
            hs.get(("new", k))
    assert any(hs.contains(("new", k)) for k in range(2)), (
        "recurring new working set never displaced stale protected entries"
    )


def test_oversize_rejected_counted_and_logged_once(caplog):
    """An entry larger than the whole budget was silently dropped before:
    now it ticks rejected_oversize and logs once per key."""
    hs = DeviceHotSet(budget_bytes=100, ship_cost=lambda n: 0.01)
    with caplog.at_level("WARNING", logger="parseable_tpu.ops.hotset"):
        hs.put(("big",), _entry(1000))
        hs.put(("big",), _entry(1000))
        hs.put(("big2",), _entry(2000))
    assert hs.rejected_oversize == 3
    assert len(hs) == 0
    msgs = [r for r in caplog.records if "exceeds the whole budget" in r.message]
    assert len(msgs) == 2  # once per key, not per put


def test_get_hotset_reroots_on_env_change(monkeypatch):
    """A budget env change rebuilds the singleton (mirrors the
    get_scan_scheduler re-root pattern) — no stale instances in tests or
    long-lived servers."""
    base = get_hotset()
    assert get_hotset() is base  # stable while env is stable
    monkeypatch.setenv("P_TPU_HOT_BYTES", "12345")
    resized = get_hotset()
    assert resized is not base and resized.budget == 12345
    assert get_hotset() is resized


def test_concurrent_get_put_evict_race():
    """Hammer get/put/clear from threads: the budget is never exceeded,
    byte accounting never goes negative, and the final ledger matches the
    resident entries exactly."""
    hs = DeviceHotSet(budget_bytes=10_000, ship_cost=lambda n: 0.01)
    rng = np.random.default_rng(7)
    sizes = rng.integers(100, 1500, 64).tolist()
    errors: list = []
    stop = threading.Event()

    def writer(tid: int):
        try:
            for i in range(300):
                k = ("k", (tid * 7 + i) % 32)
                hs.put(k, _entry(sizes[(tid + i) % len(sizes)]))
        except Exception as e:  # noqa: BLE001 - recorded for the assert
            errors.append(e)

    def reader():
        try:
            i = 0
            while not stop.is_set():
                hs.get(("k", i % 32))
                rb = hs.resident_bytes
                assert 0 <= rb <= 10_000
                i += 1
        except Exception as e:  # noqa: BLE001 - recorded for the assert
            errors.append(e)

    writers = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in writers + readers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors
    with hs._lock:
        ledger = sum(s.entry.nbytes for s in hs._entries.values())
        assert hs._bytes == ledger
        assert 0 <= hs._bytes <= hs.budget
        prot = sum(
            s.entry.nbytes for s in hs._entries.values() if not s.probation
        )
        assert hs._protected_bytes == prot


# ------------------------------------------------------------------- prefetch


def test_prefetcher_ships_ahead_and_counts_hits():
    shipped: list = []

    def ship(sid):
        shipped.append(sid)
        return ("key", sid)

    srcs = [f"s{i}".encode() for i in range(5)]
    pf = ScanPrefetcher(srcs, ship, depth=1)
    try:
        for i, sid in enumerate(srcs):
            pf.on_block(sid)
            key = ("key", sid)
            if i > 0:
                pf.claim(sid, timeout=5.0)
                assert pf.peek(key)
                assert pf.consumed(key)
    finally:
        counters = pf.close()
    assert counters["prefetch_hits"] == 4
    assert counters["prefetch_issued"] == 4
    # every source shipped at most once: claim never double-ships
    assert len(shipped) == len(set(shipped))


def test_prefetch_close_cancels_pending_and_joins():
    """close() during an in-flight ship: the ship completes, nothing else
    starts, the worker thread is joined — no in-flight work survives."""
    started = threading.Event()
    release = threading.Event()
    ships: list = []

    def ship(sid):
        ships.append(sid)
        started.set()
        release.wait(5.0)
        return ("key", sid)

    srcs = [f"s{i}".encode() for i in range(6)]
    pf = ScanPrefetcher(srcs, ship, depth=3)
    pf.on_block(srcs[0])  # schedules s1..s3
    assert started.wait(5.0)
    closer = threading.Thread(target=pf.close)
    closer.start()
    time.sleep(0.05)
    release.set()
    closer.join(timeout=10)
    assert not closer.is_alive()
    assert not pf._thread.is_alive()
    assert ships == [srcs[1]]  # queued s2/s3 were cancelled, never shipped


def test_prefetch_query_leaves_no_thread_or_inflight_ship(parseable, monkeypatch):
    """End-to-end under a tight budget: after the query returns (the
    executor's finally closed the prefetcher), no query-prefetch thread is
    alive and prefetch counters land in the stats. Leaked device bytes
    would show as hot-set residency above budget — also asserted."""
    from parseable_tpu.event.json_format import JsonEvent
    from parseable_tpu.ops.enccache import get_enccache
    from parseable_tpu.query.session import QuerySession

    p = parseable
    stream = p.create_stream_if_not_exists("pf")
    # several minute-buckets -> several parquet files -> several blocks
    from datetime import datetime, timedelta

    for minute in range(6):
        rows = [
            {"host": f"h{i % 8}", "v": float(i)} for i in range(3000)
        ]
        ev = JsonEvent(rows, "pf").into_event(stream.metadata)
        ev.parsed_timestamp = datetime(2024, 5, 1) + timedelta(minutes=minute)
        ev.process(stream, commit_schema=p.commit_schema)
        p.local_sync(shutdown=True)
    p.sync_all_streams()

    sql = "SELECT host, count(*) c, sum(v) s FROM pf GROUP BY host ORDER BY host"
    sess = QuerySession(p, engine="tpu")
    expected = QuerySession(p, engine="cpu").query(sql).to_json_rows()
    get_hotset().clear()
    first = sess.query(sql)
    assert first.to_json_rows() == expected
    ec = get_enccache(p.options)
    assert ec is not None
    ec.wait_idle()

    ws = get_hotset().resident_bytes
    assert ws > 0
    monkeypatch.setenv("P_TPU_HOT_BYTES", str(max(1, int(ws * 0.4))))
    hs = get_hotset()
    hs.clear()
    sess.query(sql)
    res = sess.query(sql)
    assert res.to_json_rows() == expected
    st = res.stats["stages"]["hotset"]
    assert st["evictions"] > 0, "capped budget produced no eviction pressure"
    assert st.get("prefetch_issued", 0) > 0
    assert hs.resident_bytes <= hs.budget, "leaked device bytes past the budget"
    assert not [
        t for t in threading.enumerate() if t.name == "query-prefetch"
    ], "prefetch thread leaked past query end"


# ------------------------------------------------------- enccache backpressure


def test_enccache_backpressure_blocks_then_counts_drop(tmp_path, monkeypatch):
    """Sustained ingest with a wedged writer: producers block up to the
    deadline, then the seed is DROPPED and counted — never silently lost,
    and put_async never raises."""
    import pyarrow as pa

    from parseable_tpu.ops.device import encode_table
    from parseable_tpu.ops.enccache import EncodedBlockCache

    monkeypatch.setenv("P_TPU_ENC_QUEUE_DEPTH", "2")
    monkeypatch.setenv("P_TPU_ENC_QUEUE_TIMEOUT_MS", "30")
    cache = EncodedBlockCache(tmp_path)
    enc = encode_table(
        pa.table({"v": pa.array(np.arange(256, dtype=np.float64))}), {"v"}
    )
    wedge = threading.Event()
    real_put = cache.put

    def wedged_put(source_id, e):
        wedge.wait(10.0)
        return real_put(source_id, e)

    cache.put = wedged_put
    try:
        t0 = time.monotonic()
        for i in range(6):
            cache.put_async(f"src-{i}".encode(), enc)
        waited = time.monotonic() - t0
        assert cache.dropped >= 1, "overflow past the deadline must count a drop"
        # 1 in the writer + 2 queued admitted; the rest waited ~30ms each
        assert waited < 5.0
    finally:
        wedge.set()
        cache.shutdown()
    # queue drained deterministically: admitted seeds landed on disk
    assert cache.get(b"src-0", {"v"}, set()) is not None


def test_enccache_no_drops_when_writer_keeps_up(tmp_path, monkeypatch):
    import pyarrow as pa

    from parseable_tpu.ops.device import encode_table
    from parseable_tpu.ops.enccache import EncodedBlockCache

    monkeypatch.setenv("P_TPU_ENC_QUEUE_DEPTH", "8")
    cache = EncodedBlockCache(tmp_path)
    enc = encode_table(
        pa.table({"v": pa.array(np.arange(64, dtype=np.float64))}), {"v"}
    )
    for i in range(5):
        cache.put_async(f"s{i}".encode(), enc)
    cache.wait_idle()
    cache.shutdown()
    assert cache.dropped == 0
