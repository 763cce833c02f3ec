"""psan (runtime concurrency sanitizer) tests.

Seeded-bug fixture suite: each detector catches its class of bug (true
positive), idiomatic code passes (true negative), and `# plint: disable=`
suppression is honored — the same contract plint's rule tests enforce for
the static checker. Plus regression tests for the real defects psan
surfaced and this PR fixed:

- the per-flush fire-and-forget `otlp-export` thread in utils/telemetry.py
  (now tracked, at most one in flight, joined by Tracer.drain());
- scrypt password verification on the event loop in the auth middleware
  (psan-loop-block: rbac/__init__.py hash_password blocked the loop 58ms;
  cache misses — including every wrong-password attempt — now verify on
  the worker pool);
- the hotset/prefetch claim() interleaving where a ship completing between
  `peek()` and `get(touch=...)` promoted prefetch cargo into the protected
  segment (consumption now fetches untouched and lets `consumed()` decide
  atomically, with `DeviceHotSet.touch()` applying proven reuse after).

The fixture tests run against a *scoped* sanitizer session: when the
whole suite already runs under P_PSAN=1 the global runtime is reused
(fixture findings live outside the repo root, which the gate ignores);
otherwise the session enables/disables the patches around each scenario.
"""

from __future__ import annotations

import importlib
import sys
import textwrap
import threading
import time
from contextlib import contextmanager
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]


@contextmanager
def psan_session(tmp_path, modname: str, source: str):
    """Scoped sanitizer over one fixture module written to `tmp_path`.

    Yields (module, runtime, new_findings) where new_findings() returns the
    findings this scenario produced inside the fixture module."""
    from parseable_tpu.analysis.psan import contracts, runtime

    rt = runtime.get_runtime()
    was_enabled = rt.enabled
    path = tmp_path / f"{modname}.py"
    path.write_text(textwrap.dedent(source), encoding="utf-8")
    sys.path.insert(0, str(tmp_path))
    saved_prefixes = rt.watch_prefixes
    pre = {f.fingerprint for f in rt.findings()}
    try:
        if was_enabled:
            rt.watch_prefixes = rt.watch_prefixes + (modname,)
            cs = contracts.build_contracts(tmp_path, [f"{modname}.py"])
        else:
            rt.enable(root=str(tmp_path), extra_prefixes=(modname,))
            cs = contracts.build_contracts(tmp_path, [f"{modname}.py"])
        contracts.instrument(rt, cs)
        mod = importlib.import_module(modname)

        def new_findings():
            return [
                f
                for f in rt.findings()
                if f.fingerprint not in pre and modname in f.path
            ]

        yield mod, rt, new_findings
    finally:
        sys.path.remove(str(tmp_path))
        sys.modules.pop(modname, None)
        rt.watch_prefixes = saved_prefixes
        if not was_enabled:
            rt.disable()
            rt.reset_findings()


# ------------------------------------------------------------ psan-race


RACE_SRC = """
    import threading

    class {cls}:
        def __init__(self):
            self._lock = threading.Lock()
            self.value = 0  # guarded-by: self._lock

        def safe_add(self):
            with self._lock:
                self.value += 1

        def racy_add(self):
            self.value += 1{suffix}
"""


def test_race_detector_catches_unguarded_write(tmp_path):
    src = RACE_SRC.format(cls="RacyCounter", suffix="")
    with psan_session(tmp_path, "psan_fix_race_tp", src) as (mod, rt, new):
        c = mod.RacyCounter()
        c.safe_add()  # main thread takes shared ownership first
        t = threading.Thread(target=c.racy_add, name="racer")
        t.start()
        t.join()
        races = [f for f in new() if f.rule == "psan-race"]
        assert races, "unguarded cross-thread write not detected"
        assert "RacyCounter.value" in races[0].message
        assert "self._lock" in races[0].message  # cites the declared guard
        assert "racy_add" in races[0].message  # both stacks in the report
        assert "safe_add" in races[0].message or "previously" in races[0].message


def test_race_detector_clean_on_locked_access(tmp_path):
    src = RACE_SRC.format(cls="CleanCounter", suffix="")
    with psan_session(tmp_path, "psan_fix_race_tn", src) as (mod, rt, new):
        c = mod.CleanCounter()
        threads = [
            threading.Thread(target=lambda: [c.safe_add() for _ in range(50)])
            for _ in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # owner reads after join are exempt too (join happens-before)
        with c._lock:
            total = c.value
        assert total == 150
        assert [f for f in new() if f.rule == "psan-race"] == []


def test_race_detector_honors_suppression(tmp_path):
    src = RACE_SRC.format(
        cls="SuppressedCounter", suffix="  # plint: disable=psan-race"
    )
    with psan_session(tmp_path, "psan_fix_race_sup", src) as (mod, rt, new):
        before = rt.stats()["suppressed"]
        c = mod.SuppressedCounter()
        c.safe_add()
        t = threading.Thread(target=c.racy_add)
        t.start()
        t.join()
        assert [f for f in new() if f.rule == "psan-race"] == []
        assert rt.stats()["suppressed"] > before


def test_race_detector_init_then_single_reader_clean(tmp_path):
    """Publication to ONE other thread with read-only sharing is not a
    race (Eraser initialization + read-share states)."""
    src = RACE_SRC.format(cls="PublishOnly", suffix="")
    with psan_session(tmp_path, "psan_fix_race_pub", src) as (mod, rt, new):
        c = mod.PublishOnly()
        seen = []
        t = threading.Thread(target=lambda: seen.append(c.value))  # bare read
        t.start()
        t.join()
        assert seen == [0]
        assert [f for f in new() if f.rule == "psan-race"] == []


# ------------------------------------------------------- psan-lock-order


ORDER_SRC = """
    import threading

    # lock-order: OrdFix.a < OrdFix.b

    class OrdFix:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def forward(self):
            with self.a:
                with self.b:
                    pass

        def inverted(self):
            with self.b:
                with self.a:
                    pass
"""


def test_lock_order_contradiction_without_deadlock(tmp_path):
    """The declared-hierarchy contradiction fires from ONE thread's
    acquisition order — no actual deadlock needed."""
    with psan_session(tmp_path, "psan_fix_order", ORDER_SRC) as (mod, rt, new):
        o = mod.OrdFix()
        o.inverted()  # b then a: contradicts `# lock-order: OrdFix.a < OrdFix.b`
        finds = [f for f in new() if f.rule == "psan-lock-order"]
        assert finds, "declared-order contradiction not detected"
        assert "OrdFix.a" in finds[0].message and "OrdFix.b" in finds[0].message
        assert "lock-order" in finds[0].message


CYCLE_SRC = """
    import threading

    class CycFix:
        def __init__(self):
            self.x = threading.Lock()
            self.y = threading.Lock()

        def xy(self):
            with self.x:
                with self.y:
                    pass

        def yx(self):
            with self.y:
                with self.x:
                    pass
"""


def test_lock_order_cycle_detected(tmp_path):
    with psan_session(tmp_path, "psan_fix_cycle", CYCLE_SRC) as (mod, rt, new):
        c = mod.CycFix()
        c.xy()
        c.yx()
        finds = [f for f in new() if f.rule == "psan-lock-order"]
        assert finds and "cycle" in finds[0].message


def test_lock_order_consistent_nesting_clean(tmp_path):
    with psan_session(tmp_path, "psan_fix_nest_ok", CYCLE_SRC) as (mod, rt, new):
        c = mod.CycFix()
        for _ in range(3):
            c.xy()  # always x < y: consistent
        assert [f for f in new() if f.rule == "psan-lock-order"] == []


# ------------------------------------------------------------ psan-stall


STALL_SRC = """
    import threading

    class StallFix:
        def __init__(self):
            self.lock = threading.Lock()

        def grab(self):
            return self.lock
"""


def test_watchdog_dumps_on_blocked_acquisition(tmp_path):
    with psan_session(tmp_path, "psan_fix_stall", STALL_SRC) as (mod, rt, new):
        old_wd = rt.watchdog_s
        rt.watchdog_s = 0.2
        try:
            s = mod.StallFix()
            holder_has_it = threading.Event()
            release = threading.Event()

            def holder():
                with s.grab():
                    holder_has_it.set()
                    release.wait(10)

            t = threading.Thread(target=holder)
            t.start()
            assert holder_has_it.wait(5)
            got = s.grab().acquire(timeout=1.0)  # blocks past the watchdog
            if got:
                s.grab().release()
            release.set()
            t.join()
            finds = [f for f in rt.findings() if f.rule == "psan-stall"]
            assert finds, "blocked acquisition did not trip the watchdog"
            assert "blocked" in finds[0].message
            # the stall site is THIS test file (deliberate sabotage): keep
            # the session gate about the tree, not the detector's own test
            rt.remove_findings(f.fingerprint for f in finds)
        finally:
            rt.watchdog_s = old_wd


# ------------------------------------------------------- psan-loop-block


LOOP_SRC = """
    import asyncio
    import time

    async def slow_handler():
        time.sleep(0.12)  # blocks the loop: the exact anti-pattern

    async def good_handler():
        await asyncio.sleep(0.12)

    def run_slow():
        asyncio.new_event_loop().run_until_complete(slow_handler())

    def run_good():
        asyncio.new_event_loop().run_until_complete(good_handler())
"""


def test_loop_monitor_attributes_blocking_sleep(tmp_path):
    with psan_session(tmp_path, "psan_fix_loop", LOOP_SRC) as (mod, rt, new):
        mod.run_slow()
        deadline = time.monotonic() + 2
        finds = []
        while time.monotonic() < deadline and not finds:
            finds = [f for f in new() if f.rule == "psan-loop-block"]
            time.sleep(0.02)
        assert finds, "loop-blocking time.sleep not detected"
        f = finds[0]
        assert "slow_handler" in f.message
        # attributed to the offending frame, not the asyncio machinery
        assert "psan_fix_loop" in f.path
        assert "time.sleep(0.12)" in f.snippet


def test_loop_monitor_clean_on_awaited_sleep(tmp_path):
    with psan_session(tmp_path, "psan_fix_loop_ok", LOOP_SRC) as (mod, rt, new):
        mod.run_good()
        time.sleep(0.1)
        assert [f for f in new() if f.rule == "psan-loop-block"] == []


# ------------------------------------------------------ psan-thread-leak


LEAK_SRC = """
    import threading

    STOP = threading.Event()

    def leak_worker():
        t = threading.Thread(target=STOP.wait, name="fixture-leaker", daemon=True)
        t.start()
        return t

    def tidy_worker():
        t = threading.Thread(target=lambda: None, name="fixture-tidy")
        t.start()
        t.join()
        return t

    def allowlisted_worker():
        t = threading.Thread(target=STOP.wait, name="enccache-writer", daemon=True)
        t.start()
        return t
"""


def test_leak_detector_flags_surviving_thread(tmp_path):
    with psan_session(tmp_path, "psan_fix_leak", LEAK_SRC) as (mod, rt, new):
        old_grace = rt.leak_grace_ms
        rt.leak_grace_ms = 50.0
        try:
            pre_t, pre_e = rt.thread_snapshot(), rt.executor_snapshot()
            mod.leak_worker()
            rt.check_leaks(pre_t, pre_e)
            finds = [f for f in new() if f.rule == "psan-thread-leak"]
            assert finds, "surviving worker not detected"
            assert "fixture-leaker" in finds[0].message
        finally:
            mod.STOP.set()
            rt.leak_grace_ms = old_grace


def test_leak_detector_clean_on_joined_and_allowlisted(tmp_path):
    with psan_session(tmp_path, "psan_fix_leak_ok", LEAK_SRC) as (mod, rt, new):
        old_grace = rt.leak_grace_ms
        rt.leak_grace_ms = 50.0
        try:
            pre_t, pre_e = rt.thread_snapshot(), rt.executor_snapshot()
            mod.tidy_worker()  # joined before the check
            mod.allowlisted_worker()  # known daemon name
            rt.check_leaks(pre_t, pre_e)
            assert [f for f in new() if f.rule == "psan-thread-leak"] == []
        finally:
            mod.STOP.set()


EXEC_LEAK_SRC = """
    from concurrent.futures import ThreadPoolExecutor

    def make_pool():
        pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="fixture-pool")
        pool.submit(lambda: None)
        return pool
"""


def test_leak_detector_flags_unshut_executor(tmp_path):
    with psan_session(tmp_path, "psan_fix_pool", EXEC_LEAK_SRC) as (mod, rt, new):
        old_grace = rt.leak_grace_ms
        rt.leak_grace_ms = 50.0
        pool = None
        try:
            pre_t, pre_e = rt.thread_snapshot(), rt.executor_snapshot()
            pool = mod.make_pool()
            rt.check_leaks(pre_t, pre_e)
            finds = [f for f in new() if f.rule == "psan-thread-leak"]
            assert finds and "fixture-pool" in finds[0].message
            # shut down -> clean on a fresh snapshot window
            pre_t, pre_e = rt.thread_snapshot(), rt.executor_snapshot()
            pool.shutdown(wait=True)
            rt.check_leaks(pre_t, pre_e)
            assert len([f for f in new() if f.rule == "psan-thread-leak"]) == len(finds)
        finally:
            if pool is not None:
                pool.shutdown(wait=False)
            rt.leak_grace_ms = old_grace


# ----------------------------------------- regressions: what psan found


def test_tracer_export_thread_tracked_and_drained(monkeypatch):
    """Regression (psan-thread-leak seed: utils/telemetry.py otlp-export):
    the per-flush exporter used to be a fire-and-forget daemon, one per
    tipped batch. Now: at most ONE in flight, and drain() joins it."""
    from parseable_tpu.utils import telemetry as T

    tr = T.Tracer(endpoint="http://127.0.0.1:9")
    gate = threading.Event()
    flushed = threading.Event()

    def slow_flush():
        gate.wait(5)
        flushed.set()
        return True

    monkeypatch.setattr(tr, "_flush_locked", slow_flush)
    tr._spawn_export()
    first = [t for t in threading.enumerate() if t.name == "otlp-export"]
    assert len(first) == 1
    tr._spawn_export()  # in flight: must NOT stack a second exporter
    assert len([t for t in threading.enumerate() if t.name == "otlp-export"]) == 1
    gate.set()
    tr.drain(timeout=5)
    assert flushed.is_set()
    assert all(t.name != "otlp-export" for t in threading.enumerate()), (
        "drain() left an exporter thread alive"
    )


def test_psan_leak_detector_catches_undrained_export(monkeypatch):
    """The satellite contract: if the exporter regresses to an unjoined
    thread surviving a test, psan's leak accounting reports it."""
    from parseable_tpu.analysis.psan import runtime as R
    from parseable_tpu.utils import telemetry as T

    rt = R.get_runtime()
    was_enabled = rt.enabled
    if not was_enabled:
        rt.enable(root=str(REPO_ROOT))
    pre = {f.fingerprint for f in rt.findings()}
    old_grace = rt.leak_grace_ms
    rt.leak_grace_ms = 50.0
    gate = threading.Event()
    try:
        tr = T.Tracer(endpoint="http://127.0.0.1:9")
        monkeypatch.setattr(tr, "_flush_locked", lambda: gate.wait(10))
        pre_t, pre_e = rt.thread_snapshot(), rt.executor_snapshot()
        tr._spawn_export()  # simulate "still in flight at teardown"
        rt.check_leaks(pre_t, pre_e)
        finds = [
            f
            for f in rt.findings()
            if f.fingerprint not in pre
            and f.rule == "psan-thread-leak"
            and "otlp-export" in f.message
        ]
        assert finds, "undrained otlp-export thread not caught"
        gate.set()
        tr.drain(timeout=5)
    finally:
        gate.set()
        rt.leak_grace_ms = old_grace
        if not was_enabled:
            rt.disable()
            rt.reset_findings()
        else:
            # this test SABOTAGED product code on purpose; the session gate
            # must judge the tree, not the sabotage
            rt.remove_findings(
                f.fingerprint for f in rt.findings() if f.fingerprint not in pre
            )


def test_prefetch_consumption_never_promotes():
    """Regression (psan seed: hotset/prefetch claim() interleaving): the
    consumer now fetches with touch=False unconditionally and applies
    `DeviceHotSet.touch()` only when `consumed()` says the hit was NOT the
    prefetcher's planned consumption — there is no longer a peek-then-get
    window in which a completing ship gets promoted as proven reuse."""
    from parseable_tpu.ops.hotset import DeviceHotSet, HotEntry
    from parseable_tpu.ops.prefetch import ScanPrefetcher

    hs = DeviceHotSet(budget_bytes=10_000, ship_cost=lambda n: 1.0)
    key = ("blk", "cols")
    shipped = threading.Event()

    def ship(sid):
        hs.put(key, HotEntry(dev={}, meta=None, nbytes=100))
        shipped.set()
        return key

    pf = ScanPrefetcher([b"a", b"b"], ship=ship, depth=1)
    try:
        pf.on_block(b"a")  # schedules b"b"; the worker ships it
        assert shipped.wait(5)
        # consumer path: untouched fetch, then consumed() decides
        entry = hs.get(key, touch=False)
        assert entry is not None
        assert pf.claim(b"b") or True  # ship already landed; claim is moot
        was_prefetch = pf.consumed(key)
        assert was_prefetch
        slot = hs._entries[key]
        assert slot.freq == 1 and slot.probation, (
            "planned prefetch consumption was promoted as proven reuse"
        )
        # a REAL re-touch afterwards is proven reuse and promotes
        hs.touch(key)
        slot = hs._entries[key]
        assert slot.freq == 2 and not slot.probation
        assert pf.hits == 1
    finally:
        pf.close()


def test_hotset_touch_matches_get_touch_semantics():
    from parseable_tpu.ops.hotset import DeviceHotSet, HotEntry

    a = DeviceHotSet(budget_bytes=10_000, ship_cost=lambda n: 1.0)
    b = DeviceHotSet(budget_bytes=10_000, ship_cost=lambda n: 1.0)
    for hs in (a, b):
        hs.put(("k",), HotEntry(dev={}, meta=None, nbytes=64))
    a.get(("k",), touch=True)
    b.get(("k",), touch=False)
    b.touch(("k",))
    sa, sb = a._entries[("k",)], b._entries[("k",)]
    assert (sa.freq, sa.probation) == (sb.freq, sb.probation)
    assert a._protected_bytes == b._protected_bytes


def test_auth_scrypt_leaves_the_event_loop(tmp_path):
    """Regression (psan-loop-block: rbac hash_password blocked the loop
    58ms): a Basic-auth credential-cache MISS must verify scrypt on a
    worker thread, never on the event loop; cache hits stay inline."""
    import asyncio

    from tests.test_server import AUTH, make_state, run, with_client

    state = make_state(tmp_path)
    verify_threads: list[int] = []
    orig = state.rbac.authenticate

    def recording_authenticate(user, pw):
        verify_threads.append(threading.get_ident())
        return orig(user, pw)

    state.rbac.authenticate = recording_authenticate

    async def fn(client):
        loop_thread = threading.get_ident()
        r = await client.get("/api/v1/liveness")  # unauthenticated: no verify
        assert r.status == 200
        r = await client.get("/api/v1/logstream", headers=AUTH)
        assert r.status == 200
        assert verify_threads, "slow-path authenticate never ran"
        assert loop_thread not in verify_threads, (
            "scrypt verification ran on the event loop"
        )
        # second request: cache hit, no slow-path call at all
        n = len(verify_threads)
        r = await client.get("/api/v1/logstream", headers=AUTH)
        assert r.status == 200
        assert len(verify_threads) == n

    run(with_client(state, fn))


def test_rbac_cached_authenticate_fast_path():
    from parseable_tpu.rbac import RbacStore

    rbac = RbacStore()
    rbac.put_user("admin", "admin")
    user, decided = rbac.try_cached_authenticate("admin", "admin")
    assert not decided and user is None  # cold cache: needs scrypt
    assert rbac.authenticate("admin", "admin") is not None
    user, decided = rbac.try_cached_authenticate("admin", "admin")
    assert decided and user is not None  # warm: decided inline
    user, decided = rbac.try_cached_authenticate("admin", "wrong")
    assert decided and user is None  # warm wrong password: decided inline
    user, decided = rbac.try_cached_authenticate("ghost", "x")
    assert decided and user is None  # unknown user: decided inline


# ----------------------------------------------------- report machinery


def test_findings_share_plint_fingerprints_and_baseline(tmp_path):
    from parseable_tpu.analysis.framework import Finding
    from parseable_tpu.analysis.psan.report import assemble_report, render_lines

    f = Finding(
        rule="psan-race",
        path="parseable_tpu/x.py",
        line=10,
        message="m",
        snippet="self.v += 1",
    )
    rep = assemble_report([f], {"raw_hits": {"psan-race": 1}}, tmp_path)
    assert not rep["clean"] and len(rep["findings"]) == 1
    # baseline the fingerprint -> clean (same schema as plint's baseline)
    (tmp_path / ".psan-baseline.json").write_text(
        '{"findings": [{"fingerprint": "%s"}]}' % f.fingerprint
    )
    rep2 = assemble_report([f], {}, tmp_path)
    assert rep2["clean"] and len(rep2["baselined"]) == 1
    assert any("psan:" in line for line in render_lines(rep2))


def test_contracts_shared_with_plint(tmp_path):
    """One annotation source: the guarded-by/lock-order comments psan
    parses are the same ones plint's rules read."""
    from parseable_tpu.analysis.psan.contracts import build_contracts

    cs = build_contracts(REPO_ROOT, ["parseable_tpu"])
    guarded = {k[1]: set(v) for k, v in cs.guarded.items()}
    # spot-check known contracts from the live tree
    assert "_rows" in guarded.get("SpanSink", set())
    assert "_entries" in guarded.get("DeviceHotSet", set())
    assert ("Tracer._flush_inflight", "Tracer._lock") in cs.declared_order
    assert ("Streams._lock", "Stream.lock") in cs.declared_order


def test_repo_baseline_is_empty():
    """Policy gate: like plint's, the psan baseline stays EMPTY."""
    import json

    doc = json.loads((REPO_ROOT / ".psan-baseline.json").read_text())
    assert doc["findings"] == []
