"""plint analyzer tests.

Per rule: a seeded-violation fixture (true positive), an idiomatic-clean
fixture (true negative), and suppression-comment handling; plus baseline
round-tripping, the `--json` CLI, the live-tree gate (the repo must lint
clean with zero unbaselined findings), and regression tests for the
concrete concurrency bugs the rules surfaced in PR 4 (leaked monitor /
enccache-writer threads, trace context dropped across the cluster pool,
Context.run reentrancy under pool.map).
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import pytest

from parseable_tpu.analysis.framework import (
    Project,
    SourceFile,
    load_baseline,
    run_analysis,
    write_baseline,
)
from parseable_tpu.analysis.rules import (
    BlockingInAsyncRule,
    ConfigDriftRule,
    LockDisciplineRule,
    PoolLifecycleRule,
    SilentSwallowRule,
    TracePropagationRule,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


def check(rule, code: str, rel: str) -> list:
    """Run one rule over a snippet the way the runner would: applies() is
    honored and same-line suppressions are dropped."""
    if not rule.applies(rel):
        return []
    sf = SourceFile(rel, textwrap.dedent(code))
    return [f for f in rule.check(sf) if not sf.is_suppressed(f.rule, f.line)]


# ---------------------------------------------------------------- rule 1


LOCKED_CLASS = """
    import threading

    class Box:
        def __init__(self):
            self._items = []  # guarded-by: self._lock
            self._lock = threading.Lock()

        def good(self):
            with self._lock:
                self._items.append(1)

        def bad(self):
            self._items.append(2)
"""


def test_lock_discipline_flags_unlocked_access():
    out = check(LockDisciplineRule(), LOCKED_CLASS, "parseable_tpu/streams.py")
    assert len(out) == 1
    assert out[0].context == "Box.bad"
    assert "_items" in out[0].message and "_lock" in out[0].message


def test_lock_discipline_init_and_locked_access_clean():
    code = LOCKED_CLASS.replace("self._items.append(2)", "pass")
    assert check(LockDisciplineRule(), code, "parseable_tpu/streams.py") == []


def test_lock_discipline_closure_does_not_inherit_lock():
    code = """
        import threading

        class Box:
            def __init__(self):
                self._items = []  # guarded-by: self._lock
                self._lock = threading.Lock()

            def escape(self, pool):
                with self._lock:
                    def job():
                        self._items.append(1)
                    pool.submit(job)
    """
    out = check(LockDisciplineRule(), code, "parseable_tpu/streams.py")
    assert len(out) == 1 and out[0].context == "Box.escape"


def test_lock_discipline_suppression():
    code = LOCKED_CLASS.replace(
        "self._items.append(2)",
        "self._items.append(2)  # plint: disable=lock-discipline",
    )
    assert check(LockDisciplineRule(), code, "parseable_tpu/streams.py") == []


# ---------------------------------------------------------------- rule 2


def test_pool_lifecycle_flags_missing_shutdown():
    code = """
        from concurrent.futures import ThreadPoolExecutor

        class Svc:
            def start(self):
                self.pool = ThreadPoolExecutor(2)
    """
    out = check(PoolLifecycleRule(), code, "parseable_tpu/core.py")
    assert len(out) == 1 and "self.pool" in out[0].message


def test_pool_lifecycle_direct_shutdown_clean():
    code = """
        from concurrent.futures import ThreadPoolExecutor

        class Svc:
            def start(self):
                self.pool = ThreadPoolExecutor(2)

            def stop(self):
                self.pool.shutdown(wait=True)
    """
    assert check(PoolLifecycleRule(), code, "parseable_tpu/core.py") == []


def test_pool_lifecycle_unload_then_join_idiom_clean():
    code = """
        import threading

        class Svc:
            def start(self):
                self._worker = threading.Thread(target=print)

            def stop(self):
                w, self._worker = self._worker, None
                if w is not None:
                    w.join(timeout=5)
    """
    assert check(PoolLifecycleRule(), code, "parseable_tpu/core.py") == []


def test_pool_lifecycle_context_managed_local_clean():
    code = """
        from concurrent.futures import ThreadPoolExecutor

        class Svc:
            def work(self):
                with ThreadPoolExecutor(2) as pool:
                    pool.map(print, range(3))
    """
    assert check(PoolLifecycleRule(), code, "parseable_tpu/core.py") == []


def test_pool_lifecycle_flags_fire_and_forget_thread():
    """The pre-PR-9 otlp-export pattern: Thread(...).start() with the
    object dropped on the floor — nothing can ever join or stop it."""
    code = """
        import threading

        def kick(fn):
            threading.Thread(target=fn, name="otlp-export", daemon=True).start()
    """
    out = check(PoolLifecycleRule(), code, "parseable_tpu/utils/telemetry.py")
    assert len(out) == 1
    assert "fire-and-forget" in out[0].message


def test_pool_lifecycle_flags_unjoined_local_thread():
    code = """
        import threading

        def kick(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
    """
    out = check(PoolLifecycleRule(), code, "parseable_tpu/core.py")
    assert len(out) == 1
    assert "custody" in out[0].message


def test_pool_lifecycle_local_bounded_join_clean():
    """A local thread with a bounded join: spawn, start, join(wait)."""
    code = """
        import threading

        def probe(fn, wait):
            t = threading.Thread(target=fn, name="probe", daemon=True)
            t.start()
            t.join(wait)
    """
    assert check(PoolLifecycleRule(), code, "parseable_tpu/utils/probe.py") == []


def test_pool_lifecycle_custody_transfer_clean():
    """Storing on self, registering into a container, or returning the
    thread all transfer custody to something that can stop it."""
    code = """
        import threading

        class Svc:
            def spawn_self(self, fn):
                t = threading.Thread(target=fn)
                self._t = t
                t.start()

            def stop(self):
                self._t.join()

        def spawn_registered(fn, registry):
            t = threading.Thread(target=fn)
            registry.append(t)
            t.start()

        def spawn_returned(fn):
            t = threading.Thread(target=fn)
            t.start()
            return t
    """
    assert check(PoolLifecycleRule(), code, "parseable_tpu/core.py") == []


def test_pool_lifecycle_global_with_module_stop_clean():
    """A module-global thread whose stop path joins it through a
    tuple-unload alias."""
    code = """
        import threading

        _WORKER = None

        def kick(fn):
            global _WORKER
            _WORKER = threading.Thread(target=fn, daemon=True)
            _WORKER.start()

        def shutdown():
            global _WORKER
            w, _WORKER = _WORKER, None
            if w is not None:
                w.join(5)
    """
    assert check(PoolLifecycleRule(), code, "parseable_tpu/ops/prefetch.py") == []


def test_pool_lifecycle_global_without_stop_flagged():
    code = """
        import threading

        _WORKER = None

        def kick(fn):
            global _WORKER
            _WORKER = threading.Thread(target=fn, daemon=True)
            _WORKER.start()
    """
    out = check(PoolLifecycleRule(), code, "parseable_tpu/ops/prefetch.py")
    assert len(out) == 1
    assert "_WORKER" in out[0].message


def test_pool_lifecycle_bare_spawn_suppression():
    code = """
        import threading

        def kick(fn):
            threading.Thread(target=fn, daemon=True).start()  # plint: disable=pool-lifecycle
    """
    assert check(PoolLifecycleRule(), code, "parseable_tpu/core.py") == []


# ---------------------------------------------------------------- rule 3


def test_trace_propagation_flags_bare_submit_and_map():
    code = """
        class Svc:
            def tick(self, fn):
                self.sync_pool.submit(fn, 1)
                self.sync_pool.map(fn, [1, 2])
    """
    out = check(TracePropagationRule(), code, "parseable_tpu/core.py")
    assert len(out) == 2


def test_trace_propagation_wrapped_and_bound_clean():
    code = """
        from parseable_tpu.utils import telemetry
        import contextvars

        class Svc:
            def tick(self, fn, items):
                self.sync_pool.submit(telemetry.propagate(fn), 1)
                ctx = contextvars.copy_context()
                self.sync_pool.submit(ctx.run, fn, 2)
                bound = telemetry.propagate(fn)
                self.sync_pool.map(bound, items)
    """
    assert check(TracePropagationRule(), code, "parseable_tpu/core.py") == []


def test_trace_propagation_non_pool_receiver_and_scope():
    code = """
        class Svc:
            def tick(self, key, path):
                self.uploader.submit(key, path)
    """
    # `uploader` is a domain API, not an executor
    assert check(TracePropagationRule(), code, "parseable_tpu/core.py") == []
    # out-of-scope module: rule does not apply at all
    bare = "class S:\n    def t(self, fn):\n        self.pool.submit(fn)\n"
    assert check(TracePropagationRule(), bare, "parseable_tpu/apikeys.py") == []


# ---------------------------------------------------------------- rule 4


def test_silent_swallow_flags_broad_pass():
    code = """
        def f():
            try:
                g()
            except Exception:
                pass
    """
    out = check(SilentSwallowRule(), code, "parseable_tpu/storage/s3.py")
    assert len(out) == 1


def test_silent_swallow_logged_or_counted_clean():
    code = """
        import logging
        logger = logging.getLogger(__name__)

        def f(counter):
            try:
                g()
            except Exception as e:
                logger.debug("boom: %s", e)
            try:
                g()
            except Exception:
                counter.labels("s3", "op").inc()
            try:
                g()
            except Exception:
                raise RuntimeError("wrapped")
    """
    assert check(SilentSwallowRule(), code, "parseable_tpu/storage/s3.py") == []


def test_silent_swallow_narrow_catch_and_scope():
    narrow = """
        def f():
            try:
                g()
            except (OSError, ValueError):
                pass
    """
    assert check(SilentSwallowRule(), narrow, "parseable_tpu/storage/s3.py") == []
    broad = "def f():\n    try:\n        g()\n    except Exception:\n        pass\n"
    # outside storage/, streams.py, core.py the rule does not apply
    assert check(SilentSwallowRule(), broad, "parseable_tpu/query/sql.py") == []


def test_silent_swallow_contextlib_suppress():
    code = """
        import contextlib

        def f():
            with contextlib.suppress(Exception):
                g()
            with contextlib.suppress(FileNotFoundError):
                g()
    """
    out = check(SilentSwallowRule(), code, "parseable_tpu/storage/s3.py")
    assert len(out) == 1


# ---------------------------------------------------------------- rule 5


def test_config_drift_flags_direct_reads():
    code = """
        import os

        A = os.environ.get("P_FOO", "1")
        B = os.environ["P_BAR"]
        C = os.getenv("P_BAZ")
        D = os.environ.get("HOME")  # not a P_* knob
    """
    out = check(ConfigDriftRule(), code, "parseable_tpu/streams.py")
    assert len(out) == 3


def test_config_drift_accessors_and_config_py_clean():
    code = """
        from parseable_tpu.config import env_str

        A = env_str("P_FOO", "1")
    """
    assert check(ConfigDriftRule(), code, "parseable_tpu/streams.py") == []
    direct = 'import os\nA = os.environ.get("P_FOO")\n'
    assert check(ConfigDriftRule(), direct, "parseable_tpu/config.py") == []


def _project_with_readme(tmp_path: Path, readme: str, code: str) -> Project:
    (tmp_path / "README.md").write_text(readme)
    project = Project(root=tmp_path)
    project.files.append(SourceFile("parseable_tpu/config.py", textwrap.dedent(code)))
    return project


def test_config_drift_readme_check(tmp_path):
    code = """
        def _env(name, default=None):
            return default

        A = _env("P_DOCUMENTED")
        B = _env("P_UNDOCUMENTED")
        C = _env("P_KAFKA_TOPICS")
    """
    readme = "knobs: `P_DOCUMENTED` and the `P_KAFKA_*` family\n"
    out = list(ConfigDriftRule().finalize(_project_with_readme(tmp_path, readme, code)))
    assert len(out) == 1
    assert "P_UNDOCUMENTED" in out[0].message


def test_config_drift_gate_escape_hatches(tmp_path):
    """Every `${VAR:-default}` opt-out in scripts/check_green.sh must be a
    standalone word in README — `P_UNDOC_PORT` does not document UNDOC."""
    gate = tmp_path / "scripts" / "check_green.sh"
    gate.parent.mkdir(parents=True)
    gate.write_text(
        '#!/bin/bash\n'
        'if [ "${PSAN:-1}" != "0" ]; then :; fi\n'
        'if [ "${UNDOC:-1}" != "0" ]; then :; fi\n'
    )
    readme = "Skip the sanitizer pass with PSAN=0. Also see `P_UNDOC_PORT`.\n"
    project = _project_with_readme(tmp_path, readme, "A = 1\n")
    out = list(ConfigDriftRule().finalize(project))
    assert len(out) == 1
    f = out[0]
    assert f.path == "scripts/check_green.sh" and "UNDOC" in f.message
    assert f.line == 3


def test_config_drift_live_gate_knobs_documented():
    """The PR 16-18 subsystem knobs and every check_green.sh escape hatch
    are documented in the real README (the rule enforces this at the lint
    gate; this pins it in the suite with named knobs)."""
    import re

    readme = (REPO_ROOT / "README.md").read_text(encoding="utf-8")
    for knob in (
        "P_EDGE_PORT",
        "P_EDGE_DISPATCHERS",
        "P_FLIGHT_PORT",
        "P_FLIGHT_CLIENT",
        "P_NATIVE_TELEM",
    ):
        assert knob in readme, f"{knob} missing from README"
    gate_text = (REPO_ROOT / "scripts" / "check_green.sh").read_text(
        encoding="utf-8"
    )
    hatches = set(re.findall(r"\$\{([A-Z][A-Z0-9_]*):-", gate_text))
    assert {"PLINT_FULL", "WLINT", "PSAN", "NSAN"} <= hatches
    for var in sorted(hatches):
        assert re.search(rf"(?<![A-Z0-9_]){var}(?![A-Z0-9_])", readme), (
            f"check_green.sh escape hatch {var} undocumented in README"
        )


# ---------------------------------------------------------------- rule 6


def test_blocking_in_async_flags_sleep_and_storage():
    code = """
        import time

        async def handler(request, state):
            time.sleep(1)
            state.p.storage.list_dirs("")
            return None
    """
    out = check(BlockingInAsyncRule(), code, "parseable_tpu/server/app.py")
    assert len(out) == 2


def test_blocking_in_async_nested_sync_def_clean():
    code = """
        import asyncio
        import time

        async def handler(request, state):
            def work():
                time.sleep(0.1)
                return state.p.storage.list_dirs("")
            await asyncio.sleep(0)
            return await asyncio.get_running_loop().run_in_executor(None, work)

        def sync_helper(state):
            time.sleep(0.1)
            return state.p.storage.list_dirs("")
    """
    assert check(BlockingInAsyncRule(), code, "parseable_tpu/server/app.py") == []


def test_blocking_in_async_scope():
    code = "import time\n\nasync def f():\n    time.sleep(1)\n"
    assert check(BlockingInAsyncRule(), code, "parseable_tpu/query/sql.py") == []


# ------------------------------------------------------------ baseline/CLI


VIOLATION_TREE = {
    "parseable_tpu/streams.py": """
        import os

        FLAG = os.environ.get("P_SNEAKY")
    """,
}


def _make_tree(tmp_path: Path) -> Path:
    for rel, code in VIOLATION_TREE.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(code))
    (tmp_path / "README.md").write_text("`P_SNEAKY` documented here\n")
    return tmp_path


def test_baseline_roundtrip(tmp_path):
    root = _make_tree(tmp_path)
    baseline = root / ".plint-baseline.json"
    report = run_analysis(root, baseline_path=baseline)
    assert [f.rule for f in report.unbaselined] == ["config-drift"]
    assert not report.clean

    write_baseline(baseline, report.findings)
    assert load_baseline(baseline) == {f.fingerprint for f in report.findings}
    again = run_analysis(root, baseline_path=baseline)
    assert again.clean and len(again.baselined) == 1

    # fingerprints ignore line numbers: shifting the file does not unbaseline
    p = root / "parseable_tpu/streams.py"
    p.write_text("# a new leading comment\n" + p.read_text())
    shifted = run_analysis(root, baseline_path=baseline)
    assert shifted.clean and len(shifted.baselined) == 1


def test_cli_json_and_exit_codes(tmp_path):
    root = _make_tree(tmp_path)
    cmd = [sys.executable, "-m", "parseable_tpu.analysis", "--root", str(root), "--json"]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 1, proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["clean"] is False
    assert [f["rule"] for f in doc["findings"]] == ["config-drift"]
    assert all("fingerprint" in f for f in doc["findings"])

    fixed = (
        "from parseable_tpu.config import env_str\n\nFLAG = env_str('P_SNEAKY')\n"
    )
    (root / "parseable_tpu/streams.py").write_text(fixed)
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["clean"] is True


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "parseable_tpu.analysis", "--list-rules"],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0
    for name in (
        "lock-discipline",
        "pool-lifecycle",
        "trace-propagation",
        "silent-swallow",
        "config-drift",
        "blocking-in-async",
    ):
        assert name in proc.stdout


def test_live_tree_lints_clean():
    """The acceptance gate: zero unbaselined findings across all rules on
    the real package (and, stronger: the baseline is empty — every finding
    the rules ever raised has been fixed, not acknowledged)."""
    report = run_analysis(
        REPO_ROOT, baseline_path=REPO_ROOT / ".plint-baseline.json"
    )
    assert report.parse_errors == []
    assert report.files_checked > 50
    rendered = "\n".join(f.render() for f in report.unbaselined)
    assert report.clean, f"plint findings on the live tree:\n{rendered}"
    assert report.findings == [], "baseline policy: fix findings, don't acknowledge"


# ------------------------------------------------- concrete-bug regressions


def test_resource_monitor_stop_joins_thread():
    """pool-lifecycle finding: ResourceMonitor.stop() used to set the event
    and leave the thread running; a stop/start pair stacked monitors."""
    from parseable_tpu.utils.resources import ResourceMonitor

    m = ResourceMonitor(0.0, 0.0)  # thresholds off
    m.start()
    t = m._thread
    assert t is not None and t.is_alive()
    m.stop()
    assert not t.is_alive()
    assert m._thread is None


def test_enccache_shutdown_stops_writer(tmp_path):
    """pool-lifecycle finding: the write-behind thread had no stop path at
    all — it leaked on every engine restart."""
    import pyarrow as pa

    from parseable_tpu.ops.device import encode_table
    from parseable_tpu.ops.enccache import EncodedBlockCache

    table = pa.table({"host": ["a", "b", "c", "d"]})
    cache = EncodedBlockCache(tmp_path)
    enc = encode_table(table, {"host"})
    cache.put_async(b"sid", enc)
    w = cache._writer
    assert w is not None
    cache.wait_idle()
    cache.shutdown()
    assert not w.is_alive()
    # idempotent, and a later put_async restarts cleanly
    cache.shutdown()
    cache.put_async(b"sid2", enc)
    cache.wait_idle()
    assert cache.get(b"sid", {"host"}, set()) is not None
    cache.shutdown()


def test_cluster_staging_fanout_propagates_trace(monkeypatch):
    """trace-propagation finding: the querier's staging fan-out dropped the
    query's trace context on the cluster pool, detaching every remote-fetch
    span from the query trace."""
    from parseable_tpu.server import cluster
    from parseable_tpu.utils import telemetry

    seen: list[str | None] = []

    def fake_fetch(p, domain, stream, *args, **kwargs):
        seen.append(telemetry.current_trace_id())
        return []

    monkeypatch.setattr(cluster, "_fetch_one", fake_fetch)
    monkeypatch.setattr(
        cluster, "live_ingestors", lambda p: [{"domain_name": "http://peer"}]
    )
    with telemetry.trace_context() as trace_id:
        cluster.fetch_staging_batches(object(), "web")
    assert seen == [trace_id]


def test_propagate_is_safe_under_concurrent_map():
    """A single propagate()-wrapped callable is fanned out via pool.map in
    the storage backends; contextvars.Context.run raises RuntimeError when
    one Context is entered by two threads at once, so propagate must run
    each call in its own copy."""
    from concurrent.futures import ThreadPoolExecutor

    from parseable_tpu.utils import telemetry

    barrier = threading.Barrier(4)
    ids: list[str | None] = []

    def task(_):
        barrier.wait(timeout=10)
        ids.append(telemetry.current_trace_id())
        return True

    with telemetry.trace_context() as trace_id:
        bound = telemetry.propagate(task)
        with ThreadPoolExecutor(max_workers=4) as pool:
            assert all(pool.map(bound, range(4)))
    assert ids == [trace_id] * 4
