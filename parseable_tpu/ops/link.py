"""Measured link profile: adaptive host/device dispatch.

The reference trusts DataFusion to keep scans on the CPU that owns the
data (/root/reference/src/query/mod.rs); a TPU engine instead has to
DECIDE whether a cold block is worth shipping, because a slow enough
host<->device link makes a cold scan lose to aggregating on the host. The
engine records every real transfer into EWMAs and routes each
non-resident block by estimated cost:

    ship_cost(bytes)   = h2d latency + bytes / h2d bandwidth
    read_cost(bytes)   = d2h latency + bytes / d2h bandwidth
    cpu_cost(rows)     = rows / measured CPU aggregation rate

Blocks that lose the estimate aggregate on the CPU *and* optionally warm
the device hot set in the background, so the next query runs device-warm
either way. Until a transfer has been measured the profile holds
PLACEHOLDERS (`_DEFAULTS`), not facts about any link: they are chosen
large enough that an unmeasured link never routes work away from the
device, so only observations can teach a bad link. What a directly
attached v5e measures, and whether this routing ever fires there, is not
measured on today's code (ROADMAP D2).

Profiles persist per staging dir (JSON) so short-lived processes inherit
the measured numbers — but only on the device they were measured on: the
file is stamped "<platform>/<device_kind>" and a profile stamped for
another device (a CPU test run's file riding along to a chip) is ignored.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import threading
import time
from pathlib import Path

logger = logging.getLogger(__name__)

# placeholders until the first real transfers (see module docstring): no
# link was measured to get them
_DEFAULTS = {
    "h2d_bw": 8e9,  # bytes/sec
    "h2d_lat": 0.002,  # sec per put
    "d2h_bw": 8e9,
    "d2h_lat": 0.002,
    "cpu_rows_per_sec": 2.0e7,
    "cpu_filter_rows_per_sec": 4.0e7,
}

_SMALL = 256 * 1024  # below this a transfer mostly measures latency
_ALPHA = 0.3  # EWMA weight for new samples


class LinkProfile:
    def __init__(self, path: Path | None = None, device: str | None = None):
        self._lock = threading.Lock()
        self._v = dict(_DEFAULTS)
        self._path = path
        # "<platform>/<device_kind>" of the engine's devices (None until a
        # TPU engine has resolved them): a stored profile loads, merges and
        # saves only under a matching stamp
        self.device = device
        self._dirty = False
        self._last_save = 0.0
        # what we last saw on disk: the merge-on-save baseline (keys that
        # moved on disk since = another process's fresher measurements)
        self._last_disk: dict = {}
        stored = self._read_stored()
        if stored:
            loaded = {k: float(stored[k]) for k in _DEFAULTS if k in stored}
            self._v.update(loaded)
            self._last_disk = loaded

    def _read_stored(self) -> dict:
        """The on-disk profile if it was measured on OUR device, else {}."""
        if self._path is None or self.device is None:
            return {}
        try:
            stored = json.loads(self._path.read_text())
        except (OSError, ValueError):
            return {}
        if not isinstance(stored, dict) or stored.get("device") != self.device:
            return {}
        return stored

    # ------------------------------------------------------------- recording

    def _ewma(self, key: str, value: float) -> None:
        self._v[key] = (1 - _ALPHA) * self._v[key] + _ALPHA * value

    def _record_dir(self, lat_key: str, bw_key: str, nbytes: int, secs: float) -> None:
        with self._lock:
            if nbytes < _SMALL:
                self._ewma(lat_key, secs)
            else:
                # subtract the latency estimate, but never let a transfer
                # faster than it fabricate bandwidth: floor at secs/4
                # (inflation bounded to 4x actual)
                eff = nbytes / max(secs - self._v[lat_key], secs / 4)
                self._ewma(bw_key, eff)
            self._dirty = True
        self._maybe_save()

    def record_h2d(self, nbytes: int, secs: float) -> None:
        if secs > 0:
            self._record_dir("h2d_lat", "h2d_bw", nbytes, secs)

    def record_d2h(self, nbytes: int, secs: float) -> None:
        if secs > 0:
            self._record_dir("d2h_lat", "d2h_bw", nbytes, secs)

    def record_cpu_agg(self, rows: int, secs: float) -> None:
        # floor matches the adaptive gate's routing minimum (1<<16): every
        # routable block feeds back; smaller blocks measure fixed costs
        if secs <= 0 or rows < (1 << 16):
            return
        with self._lock:
            self._ewma("cpu_rows_per_sec", rows / secs)
            self._dirty = True
        self._maybe_save()

    def record_cpu_filter(self, rows: int, secs: float) -> None:
        if secs <= 0 or rows < (1 << 16):
            return
        with self._lock:
            self._ewma("cpu_filter_rows_per_sec", rows / secs)
            self._dirty = True
        self._maybe_save()

    # ------------------------------------------------------------- estimates

    def ship_cost(self, nbytes: int) -> float:
        v = self._v
        return v["h2d_lat"] + nbytes / v["h2d_bw"]

    def ship_cost_per_byte(self, nbytes: int) -> float:
        """Estimated re-ship seconds per resident byte — the hot set's
        eviction score (ops/hotset.py). Amortizing the per-put latency over
        the block size means small blocks on a high-latency link score
        higher than their bandwidth share: evicting them buys back few
        bytes but costs a whole round trip to bring back."""
        return self.ship_cost(nbytes) / max(1, nbytes)

    def read_cost(self, nbytes: int) -> float:
        v = self._v
        return v["d2h_lat"] + nbytes / v["d2h_bw"]

    def cpu_cost(self, rows: int) -> float:
        return rows / self._v["cpu_rows_per_sec"]

    def cpu_filter_cost(self, rows: int) -> float:
        # filters (predicate eval + take) run faster than aggregation;
        # pricing them with the aggregate rate would over-route to CPU
        return rows / self._v["cpu_filter_rows_per_sec"]

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._v)

    def attach_path(self, path: Path) -> None:
        """Adopt a persistence path without dropping in-memory learning
        (current-session measurements outrank a stored profile)."""
        with self._lock:
            self._path = path
            self._dirty = True
        self._maybe_save()

    # ----------------------------------------------------------- persistence

    def _maybe_save(self) -> None:
        if self._path is None or self.device is None:
            return
        now = time.monotonic()
        with self._lock:
            if not self._dirty or now - self._last_save < 5.0:
                return
            self._dirty = False
            self._last_save = now
        self._do_save()

    def flush(self) -> None:
        """Force a save, bypassing the 5s throttle (ADVICE r3 #4: a CLI
        one-off or bench subprocess must not exit without persisting its
        learned measurements). Registered atexit for the global profile;
        errors are swallowed — exit paths must never raise."""
        with self._lock:
            if self._path is None or self.device is None or not self._dirty:
                return
            self._dirty = False
            self._last_save = time.monotonic()
        try:
            self._do_save()
        except Exception:
            logger.debug("link profile flush failed", exc_info=True)

    def _do_save(self) -> None:
        """Merge-on-save: keys another process moved on disk since our
        last read/write average with ours instead of being clobbered
        last-writer-wins; untouched keys take our (fresher) values."""
        try:
            merged = dict(self._v)
            stored = self._read_stored()  # {}: none, invalid, or another device's
            for k in _DEFAULTS:
                if k in stored:
                    sv = float(stored[k])
                    baseline = self._last_disk.get(k)
                    if baseline is None or abs(sv - baseline) > 1e-12:
                        merged[k] = 0.5 * (merged[k] + sv)
            self._path.parent.mkdir(parents=True, exist_ok=True)
            tmp = self._path.with_suffix(f".{os.getpid()}.tmp")
            tmp.write_text(json.dumps({**merged, "device": self.device}))
            os.replace(tmp, self._path)
            with self._lock:
                self._last_disk = dict(merged)
                self._v.update(merged)
        except OSError:
            logger.debug("link profile save failed", exc_info=True)


_GLOBAL: LinkProfile | None = None
_GLOBAL_PATH: Path | None = None
_DEVICE: str | None = None  # set_link_device(): the engine's device stamp


def set_link_device(platform: str, device_kind: str) -> None:
    """Called by the TPU engine at its first device contact
    (executor_tpu.resolve_mesh), before it builds or consults a profile.
    A profile some CPU-engine query already created in this process only
    gains the stamp for its future saves — what it measured stays."""
    global _DEVICE
    _DEVICE = f"{platform}/{device_kind}"
    if _GLOBAL is not None and _GLOBAL.device is None:
        _GLOBAL.device = _DEVICE


def _flush_at_exit() -> None:
    try:
        if _GLOBAL is not None:
            _GLOBAL.flush()
    except Exception:  # noqa: BLE001 - never raise during interpreter exit
        pass


atexit.register(_flush_at_exit)


def get_link(options=None) -> LinkProfile:
    """Process-wide profile, persisted under the staging dir when known.
    A pathless profile that learned first (scan-path callers pass no
    options) keeps its measurements when a path shows up later — it only
    gains persistence."""
    global _GLOBAL, _GLOBAL_PATH
    path: Path | None = None
    if options is not None and getattr(options, "local_staging_path", None) is not None:
        path = Path(options.local_staging_path) / "link_profile.json"
    if _GLOBAL is None:
        _GLOBAL = LinkProfile(path, _DEVICE)
        _GLOBAL_PATH = path
    elif path is not None and _GLOBAL_PATH is None:
        _GLOBAL.attach_path(path)
        _GLOBAL_PATH = path
    elif path is not None and path != _GLOBAL_PATH:
        # a different staging dir is a different deployment
        _GLOBAL = LinkProfile(path, _DEVICE)
        _GLOBAL_PATH = path
    return _GLOBAL


# ------------------------------------------------------- background warming

_WARM_QUEUE = None
_WARM_THREAD: threading.Thread | None = None
_WARM_PENDING: set = set()
_WARM_LOCK = threading.Lock()
_WARM_STOP = object()  # sentinel: drains the warmer loop deterministically


def warm_async(key: tuple, fn) -> bool:
    """Run `fn` (an encode+ship+hotset-put closure) on the warming thread.
    Returns False when the key is already queued or the queue is full.
    A failed warm is logged with its traceback: it is off the query path,
    but a device that cannot take a block is never a quiet event."""
    import queue as _q

    global _WARM_QUEUE, _WARM_THREAD
    with _WARM_LOCK:
        if key in _WARM_PENDING:
            return False
        if _WARM_QUEUE is None:
            _WARM_QUEUE = _q.Queue(maxsize=64)

            def loop(q):
                # the queue rides in as an argument (enccache-writer idiom):
                # shutdown_warmer nulls the global, so the loop must keep
                # draining ITS queue until the stop sentinel arrives
                while True:
                    k, f = q.get()
                    if k is _WARM_STOP:
                        return
                    try:
                        f()
                    except Exception:
                        logger.exception("background warm failed")
                    finally:
                        with _WARM_LOCK:
                            _WARM_PENDING.discard(k)

            _WARM_THREAD = threading.Thread(
                target=loop, args=(_WARM_QUEUE,), name="device-warmer", daemon=True
            )
            _WARM_THREAD.start()
        try:
            _WARM_QUEUE.put_nowait((key, fn))
        except _q.Full:
            return False
        _WARM_PENDING.add(key)
        return True


def shutdown_warmer(timeout: float = 10.0) -> None:
    """Stop and join the device-warmer thread (pool-lifecycle: every thread
    this module starts has a deterministic stop). Queued warms already
    accepted still run before the sentinel; a fresh warm_async afterwards
    starts a new warmer. Idempotent."""
    global _WARM_QUEUE, _WARM_THREAD
    with _WARM_LOCK:
        q, t = _WARM_QUEUE, _WARM_THREAD
        _WARM_QUEUE = None
        _WARM_THREAD = None
        _WARM_PENDING.clear()
    if q is not None:
        try:
            q.put((_WARM_STOP, None), timeout=timeout)
        except Exception:  # queue wedged full: the daemon flag is the backstop
            logger.warning("device-warmer queue full at shutdown; not drained")
            return
    if t is not None:
        t.join(timeout)
