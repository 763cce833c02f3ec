"""psan runtime: the instrumentation layer behind the dynamic sanitizer.

plint (analysis/rules*.py) proves the annotated concurrency contracts
*statically*; this module enforces the same contracts *dynamically*, under
the real interleavings of a live test run. One `PsanRuntime` owns four
detectors:

- **psan-race** — Eraser-style lockset race detection. `threading.Lock` /
  `RLock` / `Condition` constructed from watched modules are swapped for
  delegating wrappers that maintain a per-thread lockset; every attribute
  annotated `# guarded-by:` (the same comment plint reads — one contract
  source for both checkers) gets a data descriptor that records each
  read/write together with the accessor's held locks. A variable accessed
  by two threads whose candidate lockset intersects to empty — with at
  least one write after sharing began — is a race, reported with both
  access stacks. Initialization is exempt the way Eraser's state machine
  makes it exempt: a variable owned by one thread (or whose previous
  owners all terminated — join() publication) never reports.

- **psan-lock-order** — runtime lockdep. Each acquisition while other
  instrumented locks are held records an edge in the process-wide
  lock-order graph, keyed by the `# lock-id:` / `Class.attr` names plint
  uses. An edge that contradicts a declared `# lock-order: A < B`, closes
  a cycle, or re-acquires a non-reentrant lock the thread already holds is
  a finding even when no deadlock actually fires.

- **psan-stall** (deadlock watchdog) — an acquisition blocked longer than
  `P_PSAN_WATCHDOG_S` dumps every thread's stack plus its held-lock set to
  the log and records a finding at the blocked call site, then keeps
  waiting (semantics are never changed, only observed).

- **psan-loop-block** — the dynamic sibling of plint's
  transitive-blocking-in-async rule: every asyncio callback is timed, and
  a sampler thread attributes a stall > `P_PSAN_LOOP_MS` to the innermost
  watched frame that was on the loop thread's stack mid-stall (so a
  `time.sleep` inside a handler is pinned to its exact line, not to the
  aiohttp machinery that scheduled it).

- **psan-thread-leak** — `threading.Thread` / `ThreadPoolExecutor`
  construction from watched modules is stamped with its creation site;
  the pytest plugin snapshots live stamped threads and tracked executors
  around each test and flags anything that survives teardown (plus a
  grace join) and is not on the known-daemon allowlist.

Findings reuse plint's `Finding` (same fingerprints), honor the same
`# plint: disable=<rule>` line suppressions, and gate against their own
baseline file (`.psan-baseline.json` — kept empty, like plint's).

Everything is reversible: `disable()` restores the patched factories and
uninstalls the descriptors, so fixture tests can enable a scoped sanitizer
mid-session without leaking instrumentation into the rest of the suite.
"""

from __future__ import annotations

import _thread
import logging
import os
import sys
import threading
import time
import weakref
from dataclasses import dataclass, field

from parseable_tpu.analysis.framework import Finding, SourceFile

logger = logging.getLogger(__name__)

_RAW_LOCK = _thread.allocate_lock  # always the uninstrumented factory

# default allowlist: process-wide daemons that legitimately outlive a test
# (singleton schedulers, monitors). Extend via P_PSAN_ALLOW.
DEFAULT_THREAD_ALLOW = (
    "device-probe",
    "resource-monitor",
    "profiler-sampler",
    "qsched-",
    "enccache-writer",
    "cluster",
    "alert-notify",
    "psan-",
)

_PSAN_DIR = os.path.dirname(os.path.abspath(__file__))
# <repo>/tests and <repo>/scripts drive sync product APIs from their own
# async scenarios on purpose; their coroutines are exempt from the
# loop-blocking contract (the product's handlers and coroutines are not)
_REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(_PSAN_DIR)))
_TEST_DIRS = (
    os.path.join(_REPO_DIR, "tests") + os.sep,
    os.path.join(_REPO_DIR, "scripts") + os.sep,
)


def _is_watched_frame(frame, prefixes: tuple[str, ...]) -> bool:
    name = frame.f_globals.get("__name__", "")
    return bool(name) and name.startswith(prefixes)


def _caller_site(skip: int, depth: int = 5) -> list[tuple[str, int, str]]:
    """Cheap partial stack: (filename, lineno, funcname) for up to `depth`
    frames starting `skip` levels above this call, psan frames dropped."""
    try:
        f = sys._getframe(skip)
    except ValueError:  # pragma: no cover - shallower stack than skip
        return []
    out: list[tuple[str, int, str]] = []
    while f is not None and len(out) < depth:
        co = f.f_code
        if not co.co_filename.startswith(_PSAN_DIR):
            out.append((co.co_filename, f.f_lineno, co.co_name))
        f = f.f_back
    return out


def _fmt_site(site: list[tuple[str, int, str]]) -> str:
    if not site:
        return "<unknown>"
    return " <- ".join(f"{os.path.basename(fn)}:{ln}({name})" for fn, ln, name in site)


# --------------------------------------------------------------- thread state


class _TState(threading.local):
    """Per-thread sanitizer state: the ordered multiset of held locks."""

    def __init__(self):
        self.counts: dict[int, int] = {}  # id(wrapper) -> recursion depth
        self.order: list = []  # wrappers, outermost first, unique


# ------------------------------------------------------------- lock wrappers


class _LockSiteInfo:
    __slots__ = ("name", "reentrant", "file", "line")

    def __init__(self, name: str, reentrant: bool, file: str, line: int):
        self.name = name
        self.reentrant = reentrant
        self.file = file
        self.line = line


class PsanLock:
    """Delegating wrapper over a raw lock; tracks held-set + order edges.

    Mirrors the full lock protocol including the private hooks
    `threading.Condition` uses (`_is_owned`, `_release_save`,
    `_acquire_restore`), so a Condition built over a wrapped RLock keeps
    the sanitizer's view of the held-set exact across `wait()`.
    """

    _reentrant = False

    def __init__(self, raw, site: _LockSiteInfo, rt: "PsanRuntime"):
        self._raw = raw
        self.site = site
        self._rt = rt

    # ------------------------------------------------------------- protocol

    def acquire(self, blocking: bool = True, timeout: float = -1):
        rt = self._rt
        if not blocking:
            ok = self._raw.acquire(False)
            if ok:
                rt._note_acquire(self)
            return ok
        rt._pre_acquire(self)
        ok = rt._acquire_with_watchdog(self, timeout)
        if ok:
            rt._note_acquire(self)
        return ok

    def release(self):
        self._raw.release()
        self._rt._note_release(self)

    def locked(self):
        return self._raw.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()

    def __repr__(self):  # pragma: no cover - debug aid
        return f"<PsanLock {self.site.name} over {self._raw!r}>"


class PsanRLock(PsanLock):
    _reentrant = True

    def _is_owned(self):
        return self._raw._is_owned()

    def _release_save(self):
        state = self._raw._release_save()
        depth = self._rt._note_release_all(self)
        return (state, depth)

    def _acquire_restore(self, saved):
        state, depth = saved
        self._raw._acquire_restore(state)
        self._rt._note_acquire(self, depth=depth)


# ------------------------------------------------------------ variable state


class _VarState:
    """Eraser state machine for one (object, attribute)."""

    __slots__ = ("oid", "phase", "owner", "cands", "last", "last_held", "threads")

    VIRGIN, EXCLUSIVE, SHARED, MODIFIED, REPORTED = range(5)

    def __init__(self, oid: int):
        self.oid = oid
        self.phase = self.VIRGIN
        self.owner: int | None = None
        self.cands: frozenset[int] | None = None
        self.last: tuple | None = None  # (tid, site, write)
        self.last_held: frozenset[int] = frozenset()
        self.threads: set[int] = set()


# ------------------------------------------------------------------- runtime


@dataclass
class _LoopBusy:
    t0: float
    sampled: list = field(default_factory=list)  # innermost watched frames


class PsanRuntime:
    """Process-wide sanitizer state + the monkeypatch lifecycle."""

    def __init__(self):
        self._state_lock = _RAW_LOCK()  # guards everything cross-thread below
        self.enabled = False
        self.watch_prefixes: tuple[str, ...] = ("parseable_tpu",)
        self.root: str = os.getcwd()
        # knobs (re-read from config at enable())
        self.watchdog_s = 20.0
        self.loop_ms = 50.0
        self.leak_grace_ms = 500.0
        self.max_findings_per_rule = 200
        self.thread_allow: tuple[str, ...] = DEFAULT_THREAD_ALLOW
        # contracts (set by contracts.instrument)
        self.lock_sites: dict[tuple[str, int], tuple[str, bool]] = {}
        self.declared_order: dict[tuple[str, str], tuple[str, int]] = {}
        # detector state
        self._tstate = _TState()
        self._tstates: dict[int, _TState] = {}  # tid -> state (watchdog dumps)
        # thread identity survives OS tid reuse: tid -> generation counter,
        # (tid, gen) -> weakref(Thread). The Eraser join exemption must not
        # mistake a NEW worker that inherited a dead worker's tid for the
        # dead worker still being alive (pthread ids recycle aggressively).
        self._tid_gen: dict[int, int] = {}
        self._gen_thread: dict[tuple[int, int], "weakref.ref"] = {}
        self._edges: dict[tuple[str, str], list] = {}  # (a,b) -> site
        self._adj: dict[str, set[str]] = {}
        self._var_fallback: dict[tuple[int, str], _VarState] = {}
        self._loop_busy: dict[int, _LoopBusy] = {}
        self._executors: "weakref.WeakSet" = weakref.WeakSet()
        self._findings: dict[str, Finding] = {}  # fingerprint -> finding
        self._counts: dict[str, int] = {}  # rule -> raw hit count (pre-dedup)
        self._suppressed = 0
        self._sf_cache: dict[str, SourceFile | None] = {}
        self._stalled: set[int] = set()  # id(lock) currently past watchdog
        self.test_context: str = ""  # current test id (plugin sets it)
        # patch bookkeeping
        self._orig: dict[str, object] = {}
        self._guard_undo: list[tuple[type, str, object, bool]] = []
        self._sampler: threading.Thread | None = None
        self._sampler_stop: threading.Event | None = None

    # ------------------------------------------------------------ lifecycle

    def enable(
        self,
        root: str | None = None,
        extra_prefixes: tuple[str, ...] = (),
    ) -> None:
        """Patch the threading/asyncio seams. Idempotent."""
        if self.enabled:
            return
        from parseable_tpu.config import psan_options

        opts = psan_options()
        self.watchdog_s = max(1.0, opts["watchdog_s"])
        self.loop_ms = max(1.0, opts["loop_ms"])
        self.leak_grace_ms = max(0.0, opts["leak_grace_ms"])
        self.max_findings_per_rule = max(1, opts["max_findings"])
        self.thread_allow = DEFAULT_THREAD_ALLOW + opts["allow"]
        if root:
            self.root = os.path.abspath(root)
        self.watch_prefixes = ("parseable_tpu",) + tuple(extra_prefixes)

        self._patch()
        self._sampler_stop = threading.Event()
        self._sampler = threading.Thread(
            target=self._sample_loop, name="psan-loop-monitor", daemon=True
        )
        self._sampler.start()
        self.enabled = True

    def disable(self) -> None:
        """Restore every patch and uninstall guard descriptors."""
        if not self.enabled:
            return
        self.enabled = False
        if self._sampler_stop is not None:
            self._sampler_stop.set()
        if self._sampler is not None:
            self._sampler.join(timeout=2.0)
            self._sampler = None
        self._unpatch()
        for cls, attr, prev, had in self._guard_undo:
            try:
                if had:
                    setattr(cls, attr, prev)
                else:
                    delattr(cls, attr)
            except (AttributeError, TypeError):  # pragma: no cover
                pass
        self._guard_undo.clear()

    def reset_findings(self) -> None:
        with self._state_lock:
            self._findings.clear()
            self._counts.clear()
            self._suppressed = 0

    # -------------------------------------------------------------- patches

    def _patch(self) -> None:
        import asyncio.events
        import concurrent.futures

        rt = self
        self._orig["Lock"] = threading.Lock
        self._orig["RLock"] = threading.RLock
        self._orig["Condition"] = threading.Condition
        self._orig["Thread.__init__"] = threading.Thread.__init__
        self._orig["Executor.__init__"] = (
            concurrent.futures.ThreadPoolExecutor.__init__
        )
        self._orig["Handle._run"] = asyncio.events.Handle._run
        raw_lock, raw_rlock = threading.Lock, threading.RLock
        raw_condition = threading.Condition

        def _site_for_caller(depth: int) -> _LockSiteInfo | None:
            try:
                f = sys._getframe(depth)
            except ValueError:  # pragma: no cover
                return None
            if not _is_watched_frame(f, rt.watch_prefixes):
                return None
            return _LockSiteInfo("", False, f.f_code.co_filename, f.f_lineno)

        def Lock():
            site = _site_for_caller(2)
            if site is None or not rt.enabled:
                return raw_lock()
            rt._name_site(site, reentrant=False)
            return PsanLock(raw_lock(), site, rt)

        def RLock():
            site = _site_for_caller(2)
            if site is None or not rt.enabled:
                return raw_rlock()
            rt._name_site(site, reentrant=True)
            return PsanRLock(raw_rlock(), site, rt)

        def Condition(lock=None):
            if lock is None:
                site = _site_for_caller(2)
                if site is not None and rt.enabled:
                    rt._name_site(site, reentrant=True)
                    lock = PsanRLock(raw_rlock(), site, rt)
            return raw_condition(lock)

        threading.Lock = Lock
        threading.RLock = RLock
        threading.Condition = Condition

        orig_thread_init = self._orig["Thread.__init__"]

        def thread_init(tself, *args, **kwargs):
            orig_thread_init(tself, *args, **kwargs)
            try:
                f = sys._getframe(1)
                if _is_watched_frame(f, rt.watch_prefixes):
                    tself._psan_site = (f.f_code.co_filename, f.f_lineno)
            except ValueError:  # pragma: no cover
                pass

        threading.Thread.__init__ = thread_init

        orig_exec_init = self._orig["Executor.__init__"]

        def exec_init(eself, *args, **kwargs):
            orig_exec_init(eself, *args, **kwargs)
            try:
                f = sys._getframe(1)
                if _is_watched_frame(f, rt.watch_prefixes):
                    eself._psan_site = (f.f_code.co_filename, f.f_lineno)
                    rt._executors.add(eself)
            except (ValueError, TypeError):  # pragma: no cover
                pass

        concurrent.futures.ThreadPoolExecutor.__init__ = exec_init

        orig_handle_run = self._orig["Handle._run"]

        def handle_run(hself):
            if not rt.enabled:
                return orig_handle_run(hself)
            tid = _thread.get_ident()
            busy = _LoopBusy(time.monotonic())
            rt._loop_busy[tid] = busy
            try:
                return orig_handle_run(hself)
            finally:
                rt._loop_busy.pop(tid, None)
                dt_ms = (time.monotonic() - busy.t0) * 1000.0
                if dt_ms > rt.loop_ms:
                    rt._record_loop_block(hself, dt_ms, busy)

        asyncio.events.Handle._run = handle_run

    def _unpatch(self) -> None:
        import asyncio.events
        import concurrent.futures

        threading.Lock = self._orig.pop("Lock")
        threading.RLock = self._orig.pop("RLock")
        threading.Condition = self._orig.pop("Condition")
        threading.Thread.__init__ = self._orig.pop("Thread.__init__")
        concurrent.futures.ThreadPoolExecutor.__init__ = self._orig.pop(
            "Executor.__init__"
        )
        asyncio.events.Handle._run = self._orig.pop("Handle._run")

    # -------------------------------------------------------- lock site names

    def _name_site(self, site: _LockSiteInfo, reentrant: bool) -> None:
        key = (site.file, site.line)
        named = self.lock_sites.get(key)
        if named is not None:
            site.name, site.reentrant = named
        else:
            site.name = f"{self._rel(site.file)}:{site.line}"
            site.reentrant = reentrant

    # ------------------------------------------------------- acquire/release

    def _tid_state(self) -> _TState:
        st = self._tstate
        tid = _thread.get_ident()
        if self._tstates.get(tid) is not st:
            # first touch from this thread (a fresh _TState also means a
            # fresh thread reusing an old tid): bump the generation so the
            # (tid, gen) identity is reuse-proof
            self._tstates[tid] = st  # GIL-atomic; watchdog reads best-effort
            gen = self._tid_gen.get(tid, 0) + 1
            self._tid_gen[tid] = gen
            if len(self._gen_thread) > 8192:  # bounded: prune dead entries
                self._gen_thread = {
                    k: w for k, w in self._gen_thread.items() if w() is not None
                }
            self._gen_thread[(tid, gen)] = weakref.ref(threading.current_thread())
        return st

    def _cur_tkey(self) -> tuple[int, int]:
        tid = _thread.get_ident()
        return (tid, self._tid_gen.get(tid, 0))

    def _tkey_alive(self, key: tuple[int, int]) -> bool:
        wr = self._gen_thread.get(key)
        t = wr() if wr is not None else None
        return t is not None and t.is_alive()

    def held_ids(self) -> frozenset[int]:
        return frozenset(self._tid_state().counts)

    def _pre_acquire(self, lock: PsanLock) -> None:
        """Order/self-deadlock checks before a blocking acquire."""
        st = self._tid_state()
        lid = id(lock)
        if lid in st.counts:
            if not (lock._reentrant or lock.site.reentrant):
                site = _caller_site(3)
                f0 = site[0] if site else (lock.site.file, lock.site.line, "?")
                self._emit(
                    "psan-lock-order",
                    f0[0],
                    f0[1],
                    f"non-reentrant lock {lock.site.name} re-acquired by the "
                    f"thread that already holds it (guaranteed self-deadlock); "
                    f"acquired at {_fmt_site(site)}",
                )
            return
        if not st.order:
            return
        after = lock.site.name
        for held in st.order:
            before = held.site.name
            if before == after:
                continue
            self._note_edge(before, after)

    def _note_edge(self, before: str, after: str) -> None:
        key = (before, after)
        with self._state_lock:
            if key in self._edges:
                return
            site = _caller_site(4)
            self._edges[key] = site
            # declared-order contradiction: someone declared `after < before`
            decl = self.declared_order.get((after, before))
            adj = self._adj.setdefault(before, set())
            cycle = self._find_path(after, before)
            adj.add(after)
        if decl is not None:
            drel, dline = decl
            self._emit(
                "psan-lock-order",
                site[0][0] if site else "",
                site[0][1] if site else 0,
                f"runtime acquisition order {before} -> {after} contradicts "
                f"declared `# lock-order: {after} < {before}` ({drel}:{dline}); "
                f"observed at {_fmt_site(site)}",
            )
        elif cycle:
            path = " -> ".join(cycle + [before])
            self._emit(
                "psan-lock-order",
                site[0][0] if site else "",
                site[0][1] if site else 0,
                f"lock-order cycle closed at runtime (potential deadlock): "
                f"{before} -> {path}; observed at {_fmt_site(site)}",
            )

    def _find_path(self, src: str, dst: str) -> list[str] | None:
        """DFS src -> dst over recorded edges; returns the node path."""
        stack: list[tuple[str, list[str]]] = [(src, [src])]
        seen = {src}
        while stack:
            node, path = stack.pop()
            if node == dst:
                return path
            for nxt in self._adj.get(node, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append((nxt, path + [nxt]))
        return None

    def _acquire_with_watchdog(self, lock: PsanLock, timeout: float) -> bool:
        raw_acquire = lock._raw.acquire
        deadline = None if timeout is None or timeout < 0 else time.monotonic() + timeout
        waited = 0.0
        stalled = False
        while True:
            step = self.watchdog_s
            if deadline is not None:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                step = min(step, left)
            if raw_acquire(True, step):
                if stalled:
                    self._stalled.discard(id(lock))
                return True
            waited += step
            if not stalled and waited >= self.watchdog_s:
                stalled = True
                self._stalled.add(id(lock))
                self._record_stall(lock, waited)

    def _record_stall(self, lock: PsanLock, waited: float) -> None:
        site = _caller_site(4)
        lines = [
            f"psan-stall: acquisition of {lock.site.name} blocked "
            f"> {waited:.0f}s at {_fmt_site(site)}; all-thread dump:"
        ]
        frames = sys._current_frames()
        for t in threading.enumerate():
            tid = t.ident
            held = []
            st = self._tstates.get(tid)
            if st is not None:
                held = [w.site.name for w in st.order]
            f = frames.get(tid)
            top = []
            depth = 0
            while f is not None and depth < 8:
                top.append(
                    f"{os.path.basename(f.f_code.co_filename)}:{f.f_lineno}"
                    f"({f.f_code.co_name})"
                )
                f = f.f_back
                depth += 1
            lines.append(
                f"  thread {t.name} (tid={tid}) holds {held or '[]'}: "
                + " <- ".join(top)
            )
        logger.error("\n".join(lines))
        self._emit(
            "psan-stall",
            site[0][0] if site else lock.site.file,
            site[0][1] if site else lock.site.line,
            f"acquisition of {lock.site.name} blocked > {self.watchdog_s:.1f}s "
            f"(thread dump in log); holder set at stall: see log",
        )

    def _note_acquire(self, lock: PsanLock, depth: int = 1) -> None:
        st = self._tid_state()
        lid = id(lock)
        n = st.counts.get(lid)
        if n is None:
            st.counts[lid] = depth
            st.order.append(lock)
        else:
            st.counts[lid] = n + depth

    def _note_release(self, lock: PsanLock) -> None:
        st = self._tstate
        lid = id(lock)
        n = st.counts.get(lid, 0)
        if n <= 1:
            st.counts.pop(lid, None)
            try:
                st.order.remove(lock)
            except ValueError:  # pragma: no cover - release without acquire
                pass
        else:
            st.counts[lid] = n - 1

    def _note_release_all(self, lock: PsanLock) -> int:
        """Full release for Condition.wait; returns the recursion depth."""
        st = self._tstate
        lid = id(lock)
        depth = st.counts.pop(lid, 0)
        try:
            st.order.remove(lock)
        except ValueError:  # pragma: no cover
            pass
        return max(1, depth)

    # -------------------------------------------------------- guarded access

    def install_guard(
        self,
        cls: type,
        attr: str,
        guard_expr: str,
        decl_path: str,
        decl_line: int,
    ) -> None:
        """Install the access-recording descriptor for one guarded attr."""
        prev = cls.__dict__.get(attr)
        had = attr in cls.__dict__
        if isinstance(prev, _GuardedAttr):  # already instrumented
            return
        desc = _GuardedAttr(self, attr, guard_expr, decl_path, decl_line, prev)
        try:
            setattr(cls, attr, desc)
        except (AttributeError, TypeError):  # pragma: no cover - exotic class
            logger.debug("psan: cannot instrument %s.%s", cls.__name__, attr)
            return
        self._guard_undo.append((cls, attr, prev, had))

    def record_access(
        self,
        obj,
        attr: str,
        guard_expr: str,
        write: bool,
        decl_path: str,
        decl_line: int,
    ) -> None:
        if not self.enabled:
            return
        held = self.held_ids()
        tid = self._cur_tkey()
        site = _caller_site(3)
        store = getattr(obj, "__dict__", None)
        with self._state_lock:
            if store is not None:
                states = store.get("#psan")
                if states is None:
                    states = store["#psan"] = {}
                st = states.get(attr)
                if st is None or st.oid != id(obj):
                    st = states[attr] = _VarState(id(obj))
            else:  # pragma: no cover - __slots__ holder
                key = (id(obj), attr)
                st = self._var_fallback.get(key)
                if st is None:
                    st = self._var_fallback[key] = _VarState(id(obj))
            self._track_var(st, tid, held, write, site, obj, attr, guard_expr,
                            decl_path, decl_line)

    def _track_var(
        self, st: _VarState, tid, held, write, site, obj, attr, guard_expr,
        decl_path, decl_line,
    ) -> None:
        V = _VarState
        if st.phase == V.REPORTED:
            return
        if st.phase == V.VIRGIN:
            st.phase = V.EXCLUSIVE
            st.owner = tid
            st.threads.add(tid)
            st.last = (tid, site, write)
            st.last_held = held
            return
        if st.phase == V.EXCLUSIVE:
            if tid == st.owner:
                st.last = (tid, site, write)
                st.last_held = held
                return
            # second thread: unless the old owner terminated (join/publish
            # happens-before), sharing starts and refinement begins
            if not self._tkey_alive(st.owner):
                st.owner = tid
                st.threads = {tid}
                st.last = (tid, site, write)
                st.last_held = held
                return
            # initialization exemption (Eraser): the owner's construction-
            # phase accesses happen-before publication, so the candidate
            # set starts from THIS access's lockset, not intersected with
            # locks (not) held while the object was still thread-private
            st.cands = held
            st.phase = V.MODIFIED if write else V.SHARED
        else:
            st.cands = (st.cands if st.cands is not None else held) & held
            if write:
                st.phase = V.MODIFIED
        st.threads.add(tid)
        prev = st.last
        st.last = (tid, site, write)
        st.last_held = held
        if st.phase == V.MODIFIED and not st.cands:
            # join exemption: if every OTHER thread that ever touched the
            # variable has terminated, their accesses happen-before this one
            # (join/publication) — re-own instead of reporting, the same
            # reasoning as the exclusive-phase owner-death reset above
            if not any(self._tkey_alive(k) for k in st.threads - {tid}):
                st.phase = V.EXCLUSIVE
                st.owner = tid
                st.threads = {tid}
                st.cands = None
                return
            st.phase = V.REPORTED
            prev_desc = (
                f"thread {prev[0][0]} {'wrote' if prev[2] else 'read'} at "
                f"{_fmt_site(prev[1])}"
                if prev
                else "<unknown>"
            )
            cls = type(obj).__name__
            self._emit(
                "psan-race",
                site[0][0] if site else decl_path,
                site[0][1] if site else decl_line,
                f"data race on {cls}.{attr} (declared `# guarded-by: "
                f"{guard_expr}` at {self._rel(decl_path)}:{decl_line}): "
                f"candidate lockset is empty — thread {tid[0]} "
                f"{'wrote' if write else 'read'} at {_fmt_site(site)}; "
                f"previously {prev_desc}",
                locked=True,
            )

    # ----------------------------------------------------------- loop monitor

    def _sample_loop(self) -> None:
        stop = self._sampler_stop
        interval = max(0.005, self.loop_ms / 2000.0)
        while not stop.wait(interval):
            busy = list(self._loop_busy.items())
            if not busy:
                continue
            now = time.monotonic()
            frames = None
            for tid, entry in busy:
                if (now - entry.t0) * 1000.0 < self.loop_ms:
                    continue
                if frames is None:
                    frames = sys._current_frames()
                f = frames.get(tid)
                hit = None
                while f is not None:
                    # the sanitizer's own instrumentation frames never count
                    # as "the offending handler frame"
                    if not f.f_code.co_filename.startswith(
                        _PSAN_DIR
                    ) and _is_watched_frame(f, self.watch_prefixes):
                        hit = (f.f_code.co_filename, f.f_lineno, f.f_code.co_name)
                        break
                    f = f.f_back
                if hit is not None:
                    entry.sampled.append(hit)

    @staticmethod
    def _callback_code(handle):
        """Code object of the callback a Handle will run: the Task's
        coroutine for `Task.__step`, else the plain function's code."""
        cb = getattr(handle, "_callback", None)
        task = getattr(cb, "__self__", None)
        if task is not None and hasattr(task, "get_coro"):
            coro = task.get_coro()
            return getattr(coro, "cr_code", None) or getattr(coro, "gi_code", None)
        return getattr(cb, "__code__", None) if cb is not None else None

    def _record_loop_block(self, handle, dt_ms: float, busy: _LoopBusy) -> None:
        # Who OWNS the blocked callback? A test/bench/script coroutine that
        # calls sync product APIs on its own loop is that caller's choice,
        # not a server defect — only product coroutines and framework-owned
        # callbacks (aiohttp's RequestHandler running our handlers, asyncio
        # plumbing) are held to the no-blocking contract.
        owner = self._callback_code(handle)
        if owner is not None:
            of = owner.co_filename
            if of.startswith(_TEST_DIRS):
                return
        if busy.sampled:
            fn, line, name = busy.sampled[0]
        else:
            # fall back to the callback's own code object (covers callbacks
            # too fast for the sampler but still over threshold); product
            # code only — attributing a loop stall to test frames would
            # just relitigate the owner check above
            if owner is None or (os.sep + "parseable_tpu" + os.sep) not in owner.co_filename:
                return
            fn, line, name = (
                owner.co_filename,
                owner.co_firstlineno,
                owner.co_name,
            )
        self._emit(
            "psan-loop-block",
            fn,
            line,
            f"event-loop callback blocked the loop for {dt_ms:.0f}ms "
            f"(> {self.loop_ms:.0f}ms) in {name}() — move the blocking work "
            f"to run_in_executor / asyncio.sleep",
        )

    # ----------------------------------------------------------- leak checks

    def thread_snapshot(self) -> set[int]:
        return {
            id(t)
            for t in threading.enumerate()
            if getattr(t, "_psan_site", None) is not None
        }

    def executor_snapshot(self) -> set[int]:
        return {id(e) for e in list(self._executors)}

    def check_leaks(self, pre_threads: set[int], pre_executors: set[int]) -> None:
        """Flag watched threads/executors born during the test that survive
        teardown + grace and are not allowlisted daemons."""
        fresh = [
            t
            for t in threading.enumerate()
            if getattr(t, "_psan_site", None) is not None
            and id(t) not in pre_threads
            and t.is_alive()
        ]
        deadline = time.monotonic() + self.leak_grace_ms / 1000.0
        for t in fresh:
            left = deadline - time.monotonic()
            if left > 0:
                t.join(left)
        for t in fresh:
            if not t.is_alive():
                continue
            if (t.name or "").startswith(self.thread_allow):
                continue
            fn, line = t._psan_site
            self._emit(
                "psan-thread-leak",
                fn,
                line,
                f"thread {t.name!r} created here survived test teardown "
                f"({self.test_context or 'session'}) and is not on the "
                f"known-daemon allowlist — join it or register a stop path",
            )
        for e in list(self._executors):
            if id(e) in pre_executors:
                continue
            if getattr(e, "_shutdown", True):
                continue
            threads = [t for t in getattr(e, "_threads", ()) if t.is_alive()]
            if not threads:
                continue
            prefix = getattr(e, "_thread_name_prefix", "") or ""
            if prefix.startswith(self.thread_allow):
                continue
            fn, line = e._psan_site
            self._emit(
                "psan-thread-leak",
                fn,
                line,
                f"ThreadPoolExecutor (prefix {prefix!r}, {len(threads)} live "
                f"workers) created here was never shut down before test "
                f"teardown ({self.test_context or 'session'})",
            )

    # -------------------------------------------------------------- findings

    def _rel(self, path: str) -> str:
        ap = os.path.abspath(path)
        root = self.root.rstrip(os.sep) + os.sep
        if ap.startswith(root):
            return ap[len(root):].replace(os.sep, "/")
        return ap.replace(os.sep, "/")

    def _source(self, path: str) -> SourceFile | None:
        sf = self._sf_cache.get(path, False)
        if sf is not False:
            return sf
        sf = None
        try:
            with open(path, "r", encoding="utf-8") as fh:
                sf = SourceFile(self._rel(path), fh.read())
        except (OSError, SyntaxError, UnicodeDecodeError, ValueError):
            sf = None
        self._sf_cache[path] = sf
        return sf

    def _emit(
        self, rule: str, path: str, line: int, message: str, locked: bool = False
    ) -> None:
        """Record one finding: suppression-checked, deduped, capped."""
        sf = self._source(path) if path else None
        if sf is not None and sf.is_suppressed(rule, line):
            if locked:
                self._suppressed += 1
            else:
                with self._state_lock:
                    self._suppressed += 1
            return
        if self.test_context:
            message += f" [test: {self.test_context}]"
        f = Finding(
            rule=rule,
            path=self._rel(path) if path else "<runtime>",
            line=line,
            message=message,
            context=self.test_context,
            snippet=sf.snippet(line) if sf is not None else "",
        )
        logger.warning("%s", f.render())

        def _store():
            self._counts[rule] = self._counts.get(rule, 0) + 1
            per_rule = sum(
                1 for x in self._findings.values() if x.rule == rule
            )
            if per_rule < self.max_findings_per_rule:
                self._findings.setdefault(f.fingerprint, f)

        if locked:
            _store()
        else:
            with self._state_lock:
                _store()

    def findings(self) -> list[Finding]:
        with self._state_lock:
            return sorted(
                self._findings.values(), key=lambda f: (f.rule, f.path, f.line)
            )

    def remove_findings(self, fingerprints) -> None:
        """Discard specific findings by fingerprint. For the sanitizer's own
        test suite ONLY: a detector test that deliberately provokes a bug
        in product code removes the finding it just asserted on, so the
        session gate judges the tree, not the test's sabotage."""
        fps = set(fingerprints)
        with self._state_lock:
            for fp in fps:
                self._findings.pop(fp, None)

    def stats(self) -> dict:
        with self._state_lock:
            return {
                "raw_hits": dict(sorted(self._counts.items())),
                "suppressed": self._suppressed,
                "lock_order_edges": len(self._edges),
            }


_RUNTIME: PsanRuntime | None = None


def get_runtime() -> PsanRuntime:
    global _RUNTIME
    if _RUNTIME is None:
        _RUNTIME = PsanRuntime()
    return _RUNTIME


# ------------------------------------------------------------- the descriptor


class _GuardedAttr:
    """Data descriptor recording every access to a `# guarded-by:` attr.

    The value lives in the instance `__dict__` under the attribute's own
    name: a *data* descriptor (defines both __get__ and __set__) wins the
    lookup over the instance dict, so every read/write still routes through
    here — while instances constructed *before* instrumentation (module
    singletons created by the contract import itself) keep working, and
    `vars(obj)` / copy / pickle stay faithful. If the class already had a
    descriptor for the attr (a slot), we delegate to it instead."""

    def __init__(self, rt, attr, guard_expr, decl_path, decl_line, wrapped):
        self.rt = rt
        self.attr = attr
        self.guard = guard_expr
        self.decl_path = decl_path
        self.decl_line = decl_line
        self.wrapped = wrapped if hasattr(wrapped, "__get__") else None
        self.fallback = wrapped
        self.key = attr

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        if self.wrapped is not None:
            val = self.wrapped.__get__(obj, objtype)
        else:
            try:
                val = obj.__dict__[self.key]
            except KeyError:
                if self.fallback is not None:
                    return self.fallback
                raise AttributeError(self.attr) from None
        self.rt.record_access(
            obj, self.attr, self.guard, False, self.decl_path, self.decl_line
        )
        return val

    def __set__(self, obj, value):
        if self.wrapped is not None:
            self.wrapped.__set__(obj, value)
        else:
            obj.__dict__[self.key] = value
        self.rt.record_access(
            obj, self.attr, self.guard, True, self.decl_path, self.decl_line
        )

    def __delete__(self, obj):  # pragma: no cover - rare
        if self.wrapped is not None:
            self.wrapped.__delete__(obj)
        else:
            obj.__dict__.pop(self.key, None)
