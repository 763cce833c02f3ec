"""Device hot set: HBM-resident encoded blocks reused across queries."""

from datetime import timedelta

import pytest

from parseable_tpu.event.json_format import JsonEvent
from parseable_tpu.ops.hotset import DeviceHotSet, HotEntry, get_hotset
from parseable_tpu.query.session import QuerySession


@pytest.fixture()
def loaded(parseable):
    p = parseable
    stream = p.create_stream_if_not_exists("hot")
    records = [
        {"host": f"h{i % 3}", "status": float(200 if i % 4 else 500), "msg": f"m {i}"}
        for i in range(1000)
    ]
    ev = JsonEvent(records, "hot").into_event(stream.metadata)
    ev.process(stream, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()
    get_hotset().clear()
    return p


def test_second_query_hits_hotset(loaded):
    sess = QuerySession(loaded, engine="tpu")
    hs = get_hotset()
    h0, m0 = hs.hits, hs.misses
    r1 = sess.query("SELECT host, count(*) c FROM hot GROUP BY host ORDER BY host")
    assert hs.misses > m0
    misses_after_first = hs.misses
    r2 = sess.query("SELECT host, count(*) c FROM hot GROUP BY host ORDER BY host")
    assert hs.hits > h0
    assert hs.misses == misses_after_first  # no new encodes
    assert r1.to_json_rows() == r2.to_json_rows()


def test_cached_blocks_respect_different_time_ranges(loaded):
    """THE caching-correctness regression: blocks are query-independent, so
    two queries with different time ranges over the same cached block must
    filter independently."""
    sess = QuerySession(loaded, engine="tpu")
    all_rows = sess.query("SELECT count(*) c FROM hot WHERE status = 500").to_json_rows()
    assert all_rows[0]["c"] == 250
    # a range in the past excludes everything, even though the block is hot
    past = sess.query(
        "SELECT count(*) c FROM hot WHERE status = 500",
        start_time="2001-01-01T00:00:00Z",
        end_time="2001-01-02T00:00:00Z",
    ).to_json_rows()
    assert past[0]["c"] == 0
    # and again without bounds: still correct (cache not poisoned)
    again = sess.query("SELECT count(*) c FROM hot WHERE status = 500").to_json_rows()
    assert again[0]["c"] == 250


def test_lru_eviction_by_budget():
    hs = DeviceHotSet(budget_bytes=100)
    hs.put(("a",), HotEntry(dev={}, meta=None, nbytes=60))
    hs.put(("b",), HotEntry(dev={}, meta=None, nbytes=60))
    assert hs.get(("a",)) is None  # evicted
    assert hs.get(("b",)) is not None
    # oversized entries are not admitted
    hs.put(("c",), HotEntry(dev={}, meta=None, nbytes=1000))
    assert hs.get(("c",)) is None
    assert len(hs) == 1


def test_stub_eviction_race_rereads_source(loaded):
    """A block evicted between the provider's hot check and execution must
    re-read from its source (executor.source_loader), not fail or return
    partial results."""
    from parseable_tpu.ops.hotset import get_hotset
    from parseable_tpu.query.session import QuerySession

    sess = QuerySession(loaded, engine="tpu")
    sql = "SELECT host, count(*) c FROM hot GROUP BY host ORDER BY host"
    first = sess.query(sql).to_json_rows()

    # second run: scan yields stubs for hot blocks; evict EVERYTHING after
    # planning by clearing inside a wrapped hotset.get (simulating pressure
    # mid-query)
    hs = get_hotset()
    orig_get = hs.get
    state = {"cleared": False}

    def evil_get(key, **kw):
        entry = orig_get(key, **kw)
        if entry is not None and not state["cleared"]:
            # let the provider see it as hot, then evict before execution
            state["cleared"] = True
            hs.clear()
            return None
        return entry

    hs.get = evil_get
    try:
        again = sess.query(sql).to_json_rows()
    finally:
        hs.get = orig_get
    assert again == first
