"""Encoded-block cache: device-ready columns on local disk.

The TPU-native hot tier (SURVEY §2 row 43: "hot tier = TPU-VM local NVMe
cache feeding device", VERDICT r2 #1 cold-path work): the expensive half of
a cold scan on a small host is parquet decode + dictionary encode — pure
CPU. This cache persists the *canonical device encoding* (ops/device.py:
narrow-dtype dictionary codes, epoch-2020 int32 seconds, f32 numerics) per
scanned parquet object, so a cold query's data path becomes
file read -> pad -> device_put: transfer-bound instead of encode-bound.

Written at parquet upload time (the converter just produced the bytes —
page-cache warm) and as write-behind whenever a query encodes a block the
cache lacks. Keyed by the scan's content-sensitive source id
(path|size|rows), so a rewritten object can't serve a stale encoding.
Entries can hold several VARIANTS per column ((kind, dtype) pairs): a
numeric column group-by'd by one query stores its dict-codes variant next
to the f32 one.

File format (version PTEC2): magic, u32 header length, JSON header
{num_rows, block_rows, columns: {name: [variant,...]}} with per-variant
buffer offsets, then raw little-endian buffers stored PADDED to
block_rows (pow2) — the loader reads the payload once and slices
frombuffer views, so a cold scan's host cost is one page-cache read +
device_put from contiguous memory (an mmap here measured 75x slower to
ship). Eviction is LRU-by-mtime over a byte budget
(P_TPU_ENC_CACHE_BYTES, default 16 GiB).

Write-behind backpressure: the background writer's queue is bounded
(P_TPU_ENC_QUEUE_DEPTH, default 16). Under sustained ingest a producer
blocks for at most P_TPU_ENC_QUEUE_TIMEOUT_MS (default 250) waiting for
room, then the seed is dropped — COUNTED (`dropped` attr + the
tpu_enccache_dropped_writes counter) and logged, never lost silently; a
queue-depth gauge makes the pressure visible before drops start.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import struct
import threading
from pathlib import Path
from typing import Any

import numpy as np

from parseable_tpu.ops.device import EncodedBatch, EncodedColumn, pow2_block
from parseable_tpu.utils.metrics import ENCCACHE_DROPS, ENCCACHE_QUEUE_DEPTH

logger = logging.getLogger(__name__)

# PTEC3: time columns are int32 ms relative to a per-batch day-aligned
# origin (header `time_origin_ms`); PTEC2 entries (canonical seconds) are
# stale and unlink on sight
_MAGIC = b"PTEC3\n"


def _fname(source_id: bytes) -> str:
    return hashlib.sha1(source_id).hexdigest() + ".enc"


# sentinel telling the write-behind thread to exit (EncodedBlockCache.shutdown)
_WRITER_STOP = object()


class EncodedBlockCache:
    def __init__(self, root: Path, budget_bytes: int | None = None):
        self.root = Path(root)
        from parseable_tpu.config import env_int

        self.budget = budget_bytes or env_int("P_TPU_ENC_CACHE_BYTES", 16 << 30)
        self._lock = threading.Lock()
        self._write_lock = threading.Lock()
        # put() holds the write lock across _put -> _evict_over_budget,
        # which takes the state lock; never acquire them the other way
        # lock-order: EncodedBlockCache._write_lock < EncodedBlockCache._lock
        self._queue: "object" = None  # lazily-started background writer
        self._writer: threading.Thread | None = None
        self.hits = 0
        self.misses = 0
        self.dropped = 0  # write-behind seeds shed after the bounded wait
        # stale tmp files from a previous crash/kill are dead weight, and
        # pre-PTEC3 entries are dead bytes against the budget. Cleanup
        # happens HERE (once, at open) rather than in _read_header: an
        # unlink on the read path would race a concurrent writer's
        # os.replace and could delete a freshly written valid entry.
        try:
            for stale in self.root.glob("*.tmp"):
                stale.unlink(missing_ok=True)
            for f in self.root.glob("*.enc"):
                try:
                    with f.open("rb") as fh:
                        if fh.read(len(_MAGIC)) != _MAGIC:
                            f.unlink(missing_ok=True)
                except OSError:
                    continue
        except OSError:
            pass

    # ------------------------------------------------------------------ put

    def put(self, source_id: bytes, enc: EncodedBatch) -> bool:
        """Persist (merge) a block's encoded columns. Best-effort: failures
        log and return False, never break the query/upload path."""
        try:
            with self._write_lock:
                return self._put(source_id, enc)
        except Exception:
            logger.exception("encoded-cache put failed")
            return False

    def put_async(self, source_id: bytes, enc: EncodedBatch) -> None:
        """Write-behind: snapshot the column references (the caller strips
        host arrays right after) and persist on a background thread — the
        merge re-read/rewrite must not sit on the query's cold path.

        Backpressure is deterministic: when the bounded queue is full the
        producer blocks up to P_TPU_ENC_QUEUE_TIMEOUT_MS for the writer to
        drain, then the seed is dropped — counted and logged (pure cache;
        the next query re-encodes), never lost silently."""
        import queue as _q

        snap_cols = {name: dataclasses.replace(c) for name, c in enc.columns.items()}
        snap = EncodedBatch(
            num_rows=enc.num_rows,
            block_rows=enc.block_rows,
            columns=snap_cols,
            row_mask=enc.row_mask,
            time_origin_ms=enc.time_origin_ms,
        )
        from parseable_tpu.config import env_float, env_int

        with self._lock:
            if self._queue is None:
                self._queue = _q.Queue(
                    maxsize=max(1, env_int("P_TPU_ENC_QUEUE_DEPTH", 16))
                )
                self._writer = threading.Thread(
                    target=self._writer_loop,
                    args=(self._queue,),
                    name="enccache-writer",
                    daemon=True,
                )
                self._writer.start()
            q = self._queue
        timeout = max(0.0, env_float("P_TPU_ENC_QUEUE_TIMEOUT_MS", 250.0)) / 1000.0
        try:
            if timeout > 0:
                q.put((source_id, snap), timeout=timeout)
            else:
                q.put_nowait((source_id, snap))
        except _q.Full:
            with self._lock:
                self.dropped += 1
                dropped = self.dropped
            ENCCACHE_DROPS.inc()
            # first drop warns (the overload signal); the rest stay debug so
            # a sustained storm can't flood the log — the counter carries
            # the rate either way
            log = logger.warning if dropped == 1 else logger.debug
            log(
                "enccache write-behind queue full after %.0fms wait; "
                "dropped seed (%d dropped so far) — next query re-encodes",
                timeout * 1000,
                dropped,
            )
        ENCCACHE_QUEUE_DEPTH.set(q.qsize())

    def _writer_loop(self, q) -> None:
        # the queue is a parameter (not self._queue) so shutdown() can drop
        # the attribute without racing this loop's next get()
        while True:
            item = q.get()
            try:
                if item is _WRITER_STOP:
                    return
                source_id, snap = item
                self.put(source_id, snap)
            finally:
                q.task_done()
                ENCCACHE_QUEUE_DEPTH.set(q.qsize())

    def shutdown(self) -> None:
        """Stop the write-behind thread deterministically (pending writes
        drain first). Idempotent; a later put_async restarts the writer."""
        with self._lock:
            q, w = self._queue, self._writer
            self._queue = None
            self._writer = None
        if w is not None and w.is_alive():
            q.put(_WRITER_STOP)
            w.join(timeout=30)

    def wait_idle(self, timeout: float = 60.0) -> None:
        """Block until queued write-behinds have landed (benchmarks use
        this so a 'cold' run measures the disk-cache path, not a race
        with the writer)."""
        import time as _t

        q = self._queue
        if q is None:
            return
        deadline = _t.monotonic() + timeout
        with q.all_tasks_done:
            while q.unfinished_tasks:
                left = deadline - _t.monotonic()
                if left <= 0:
                    return
                q.all_tasks_done.wait(left)

    def _put(self, source_id: bytes, enc: EncodedBatch) -> bool:
        n = enc.num_rows
        block = enc.block_rows
        path = self.root / _fname(source_id)
        existing = self._read_header(path) if path.exists() else None
        columns: dict[str, list[dict]] = {}
        buffers: list[bytes] = []

        def add_variant(name: str, var: dict, *bufs: bytes) -> None:
            offsets = []
            for b in bufs:
                offsets.append(sum(len(x) for x in buffers))
                buffers.append(b)
            var["offsets"] = offsets
            columns.setdefault(name, []).append(var)

        # carry over existing variants first (their buffers re-read once)
        if (
            existing is not None
            and existing["num_rows"] == n
            and existing["header"].get("block_rows") == block
            and existing["header"].get("time_origin_ms") == enc.time_origin_ms
        ):
            hdr, payload_off = existing["header"], existing["payload_off"]
            with path.open("rb") as f:
                for name, variants in hdr["columns"].items():
                    for v in variants:
                        bufs = []
                        for off, nbytes in zip(v["offsets"], v["nbytes"]):
                            f.seek(payload_off + off)
                            bufs.append(f.read(nbytes))
                        v2 = {k: v[k] for k in v if k not in ("offsets",)}
                        add_variant(name, v2, *bufs)

        changed = False
        for name, col in enc.columns.items():
            if col.values is None or len(col.values) < block:
                continue  # stripped (hot-set) encodings can't be persisted
            key = (col.kind, str(col.values.dtype))
            have = {
                (v["kind"], v["dtype"]) for v in columns.get(name, [])
            }
            if key in have:
                continue
            try:
                dict_json = (
                    json.dumps(col.dictionary) if col.dictionary is not None else None
                )
            except (TypeError, ValueError):
                continue  # unserializable dictionary values: skip variant
            # a dict variant whose values aren't strings came from force_dict
            # on a numeric/bool column — it must not serve non-group-by reads
            forced = col.kind == "dict" and any(
                v is not None and not isinstance(v, str) for v in (col.dictionary or [])
            )
            # store PADDED to block_rows: the loader memmaps zero-copy
            values = np.ascontiguousarray(col.values[:block])
            col_all_valid = bool(col.valid[:n].all()) if len(col.valid) >= n else True
            var: dict[str, Any] = {
                "kind": col.kind,
                "dtype": str(values.dtype),
                "nbytes": [values.nbytes],
                "all_valid": col_all_valid,
                "dictionary": dict_json,
                "forced": forced,
                "vmin": col.vmin,
                "vmax": col.vmax,
            }
            # said only where they differ from the defaults, so that an
            # entry without such a column is the bytes it always was
            if col.origin_ms is not None:
                var["unit_ms"], var["origin_ms"] = col.unit_ms, col.origin_ms
            if col.integral:
                var["integral"] = True
            bufs = [values.tobytes()]
            if not col_all_valid:
                valid = np.ascontiguousarray(col.valid[:block])
                var["nbytes"].append(valid.nbytes)
                bufs.append(valid.tobytes())
            add_variant(name, var, *bufs)
            changed = True
        if not changed:
            return False

        header = json.dumps(
            {
                "num_rows": n,
                "block_rows": block,
                "time_origin_ms": enc.time_origin_ms,
                "columns": columns,
            }
        ).encode()
        # unique tmp per writer: concurrent puts for the same source must
        # not truncate each other mid-write (last os.replace wins whole)
        tmp = path.with_name(f"{path.name}.{os.getpid()}.{threading.get_ident()}.tmp")
        self.root.mkdir(parents=True, exist_ok=True)
        with tmp.open("wb") as f:
            f.write(_MAGIC)
            f.write(struct.pack("<I", len(header)))
            f.write(header)
            for b in buffers:
                f.write(b)
        os.replace(tmp, path)
        self._evict_over_budget()
        return True

    # ------------------------------------------------------------------ get

    def get(
        self,
        source_id: bytes,
        needed: set[str] | None,
        dict_cols: set[str],
    ) -> EncodedBatch | None:
        """Rebuild an EncodedBatch for a query's column requirements, or
        None when any needed column/variant is missing."""
        if needed is None:
            return None  # full-projection scans take the live path
        path = self.root / _fname(source_id)
        try:
            meta = self._read_header(path) if path.exists() else None
        except Exception:
            logger.exception("encoded-cache header read failed")
            return None
        if meta is None:
            self.misses += 1
            return None
        hdr, payload_off = meta["header"], meta["payload_off"]
        n = hdr["num_rows"]
        block = hdr.get("block_rows") or pow2_block(n)
        cols: dict[str, EncodedColumn] = {}
        try:
            # resolve every needed variant from the header FIRST (a miss
            # must cost zero payload I/O), then read each buffer with one
            # contiguous pread. device_put streams a contiguous buffer at
            # link bandwidth, while an mmap'd source can degrade it to
            # page-sized chunks (by how much is not measured on a directly
            # attached chip), and a whole-file read would tax wide
            # streams' unqueried columns.
            picks: dict[str, dict] = {}
            for name in needed:
                variants = hdr["columns"].get(name)
                if not variants:
                    self.misses += 1
                    return None
                want_dict = name in dict_cols
                if want_dict:
                    pick = next((v for v in variants if v["kind"] == "dict"), None)
                else:
                    # prefer the natural (non-dict) variant; a string
                    # column's dict variant also serves, but a FORCED
                    # dict of a numeric column must not
                    pick = next((v for v in variants if v["kind"] != "dict"), None)
                    if pick is None:
                        pick = next(
                            (
                                v
                                for v in variants
                                if v["kind"] == "dict" and not v.get("forced")
                            ),
                            None,
                        )
                if pick is None:
                    self.misses += 1
                    return None
                picks[name] = pick

            fh = path.open("rb")
            try:
                def pread(offset: int, nbytes: int) -> bytes:
                    fh.seek(payload_off + offset)
                    return fh.read(nbytes)

                for name, pick in picks.items():
                    dt = np.dtype(pick["dtype"])
                    values = np.frombuffer(
                        pread(pick["offsets"][0], pick["nbytes"][0]), dtype=dt
                    )
                    dictionary = (
                        json.loads(pick["dictionary"])
                        if pick.get("dictionary") is not None
                        else None
                    )
                    if pick["all_valid"]:
                        valid = np.ones(block, dtype=bool)
                        valid[n:] = False
                    else:
                        valid = np.frombuffer(
                            pread(pick["offsets"][1], pick["nbytes"][1]), dtype=np.bool_
                        )
                    cols[name] = EncodedColumn(
                        name,
                        pick["kind"],
                        values,
                        valid,
                        dictionary,
                        all_valid=bool(pick["all_valid"]) and n == block,
                        vmin=pick.get("vmin"),
                        vmax=pick.get("vmax"),
                        unit_ms=pick.get("unit_ms", 1),
                        origin_ms=pick.get("origin_ms"),
                        integral=bool(pick.get("integral", False)),
                    )
            finally:
                fh.close()
        except Exception:
            logger.exception("encoded-cache read failed")
            return None
        try:
            path.touch()  # LRU freshness
        except OSError:
            pass
        self.hits += 1
        mask = np.zeros(block, dtype=bool)
        mask[:n] = True
        return EncodedBatch(
            num_rows=n,
            block_rows=block,
            columns=cols,
            row_mask=mask,
            time_origin_ms=int(hdr.get("time_origin_ms", 0)),
        )

    def can_serve(
        self, source_id: bytes, needed: set[str] | None, dict_cols: set[str]
    ) -> bool:
        """Header-only check: would get() succeed? Lets the scan layer skip
        the parquet read entirely for cache-resident blocks."""
        if needed is None:
            return False
        path = self.root / _fname(source_id)
        try:
            meta = self._read_header(path) if path.exists() else None
        except Exception:
            return False
        if meta is None:
            return False
        hdr = meta["header"]
        for name in needed:
            variants = hdr["columns"].get(name)
            if not variants:
                return False
            if name in dict_cols:
                if not any(v["kind"] == "dict" for v in variants):
                    return False
            elif not any(
                v["kind"] != "dict" or not v.get("forced") for v in variants
            ):
                return False
        return True

    # ------------------------------------------------------------- internals

    @staticmethod
    def _read_header(path: Path) -> dict | None:
        with path.open("rb") as f:
            magic = f.read(len(_MAGIC))
            if magic != _MAGIC:
                return None
            (hlen,) = struct.unpack("<I", f.read(4))
            header = json.loads(f.read(hlen))
            return {
                "header": header,
                "num_rows": header["num_rows"],
                "payload_off": len(_MAGIC) + 4 + hlen,
            }

    def _evict_over_budget(self) -> None:
        with self._lock:
            try:
                files = [
                    (p.stat().st_mtime, p.stat().st_size, p)
                    for p in self.root.glob("*.enc")
                ]
            except OSError:
                return
            total = sum(s for _, s, _ in files)
            if total <= self.budget:
                return
            for _, size, p in sorted(files):
                try:
                    p.unlink()
                    total -= size
                except OSError:
                    pass
                if total <= self.budget:
                    break


_GLOBAL: EncodedBlockCache | None = None
_GLOBAL_ROOT: Path | None = None


def get_enccache(options=None) -> EncodedBlockCache | None:
    """Process-wide cache rooted in the staging dir; None when disabled
    (P_TPU_ENC_CACHE=0)."""
    from parseable_tpu.config import env_str

    global _GLOBAL, _GLOBAL_ROOT
    if env_str("P_TPU_ENC_CACHE", "1") == "0":
        return None
    root: Path | None = None
    if options is not None and getattr(options, "local_staging_path", None) is not None:
        root = Path(options.local_staging_path) / "encoded_cache"
    if _GLOBAL is None or (root is not None and root != _GLOBAL_ROOT):
        if root is None:
            return _GLOBAL
        if _GLOBAL is not None:
            _GLOBAL.shutdown()
        _GLOBAL = EncodedBlockCache(root)
        _GLOBAL_ROOT = root
    return _GLOBAL


def shutdown_enccache() -> None:
    """Stop the process-wide cache's write-behind thread (server shutdown
    hook). The cache itself (disk entries) stays valid for the next start."""
    if _GLOBAL is not None:
        _GLOBAL.shutdown()
