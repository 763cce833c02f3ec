"""Device-resident hot set: encoded column blocks cached in HBM.

The reference keeps a hot tier of parquet on local NVMe so queries skip
object-store GETs (reference: src/hottier.rs). The TPU-native equivalent
keeps *encoded device arrays* resident in HBM: once a parquet file's columns
have been encoded and shipped, subsequent queries over the same data run with
ZERO host->device transfer and zero parquet decode or dictionary encode
(how large a share of a cold scan those are is not measured on a directly
attached chip).

Entries are keyed by a source id (file path + mtime + size, or a staging
batch fingerprint) plus the column-set signature.

Eviction is cost-aware, not plain LRU. Each entry carries a GDSF-style score

    score = clock + frequency * ship_cost(nbytes) / nbytes

("seconds of re-ship saved per resident byte"), so a cheap-to-refetch block
is evicted before an expensive one of equal heat. `ship_cost` is
`_SHIP_PUT_SECONDS + nbytes / _SHIP_BYTES_PER_SECOND`: two placeholders (2 ms
a put, 8 GB/s) that no eviction has ever tested, since no benchmark cell
evicts (ROADMAP D16). The set is segmented SLRU-style:

- a first touch lands in a *probationary* segment; a re-touch promotes to
  *protected*, capped at 80% of the budget (the weakest protected entry is
  demoted when a hotter one needs the room) — so probation always has
  churn space and eviction pressure stays measurable;
- eviction drains probation first, lowest score, ties broken NEWEST-first:
  a sequential over-budget scan churns one slot instead of rolling the
  whole segment (LRU's cyclic worst case — every warm rep flushes exactly
  the blocks the next rep needs first);
- when probation is empty, admission control applies: a first-touch
  candidate must BEAT the weakest protected score to displace it, so a
  one-shot full scan cannot flush a dashboard working set;
- evicted/rejected keys leave a bounded *ghost* frequency behind: a block
  that keeps coming back re-enters with its earned heat, so a sustained
  shift in the working set displaces stale protected entries — one scan
  does not.

Entries larger than the whole budget are rejected — counted and logged
once per key, never silently dropped. The budget is P_TPU_HOT_BYTES
(default 8 GiB — leaves headroom on a 16 GiB v5e); `get_hotset()` re-roots
the singleton when P_TPU_HOT_BYTES changes, so tests and long-lived servers
can resize without stale state.

Cache contents are the *canonical* encodings (ops/device.py): batch-local
dictionary codes, epoch-2020 int32-second timestamps, f32 numerics. Query-
specific adjustments (global dictionary remaps, predicate LUTs) are small
arrays gathered on device at run time, so a cached block serves any query.
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable

from parseable_tpu.utils.metrics import (
    HOTSET_EVICTIONS,
    HOTSET_REJECTED_OVERSIZE,
    HOTSET_RESIDENT_BYTES,
    QUERY_CACHE_HIT,
)

logger = logging.getLogger(__name__)

# the default ship-cost estimate's two terms (module docstring): placeholders
_SHIP_PUT_SECONDS = 0.002
_SHIP_BYTES_PER_SECOND = 8e9
# protected segment cap as a fraction of the budget: probation always keeps
# at least the rest, so churn (and with it, measurable eviction pressure)
# can never be starved out by promotions
_PROTECTED_FRAC = 0.8
# remembered frequencies for evicted/rejected keys (bounded FIFO)
_GHOST_CAP = 4096


@dataclass
class HotEntry:
    dev: dict[str, Any]  # name -> device array (values; valid where needed)
    meta: Any  # EncodedBatch with .columns values stripped host-side
    nbytes: int


class _Slot:
    """Per-entry policy state: GDSF score + segment."""

    __slots__ = ("entry", "freq", "pri", "probation", "seq")

    def __init__(self, entry: HotEntry):
        self.entry = entry
        self.freq = 1
        self.pri = 0.0
        self.probation = True
        self.seq = 0


def _default_ship_cost(nbytes: int) -> float:
    # seconds to re-ship this block — the per-byte normalization happens in
    # _priority, so a small block, which pays the whole put, scores higher
    return _SHIP_PUT_SECONDS + nbytes / _SHIP_BYTES_PER_SECOND


class DeviceHotSet:
    """Byte-budgeted cache of encoded device blocks: frequency x recency x
    re-ship-cost scoring with a probationary segment, admission control,
    and ghost frequencies (see module docstring)."""

    def __init__(
        self,
        budget_bytes: int | None = None,
        ship_cost: Callable[[int], float] | None = None,
    ):
        from parseable_tpu.config import env_int

        self.budget = budget_bytes or env_int("P_TPU_HOT_BYTES", 8 << 30)
        # ship-cost estimator: the two constants unless injected (tests)
        self._ship_cost = ship_cost or _default_ship_cost
        self._entries: OrderedDict[tuple, _Slot] = OrderedDict()  # guarded-by: self._lock
        self._bytes = 0  # guarded-by: self._lock
        self._protected_bytes = 0  # guarded-by: self._lock
        self._clock = 0.0  # guarded-by: self._lock - GDSF aging term
        self._seq = 0  # guarded-by: self._lock - insertion order
        self._ghost: OrderedDict[tuple, int] = OrderedDict()  # guarded-by: self._lock
        self._oversize_logged: set = set()  # guarded-by: self._lock
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.rejected_oversize = 0
        self.rejected_admission = 0  # first-touch puts that lost to protected heat

    # ------------------------------------------------------------------ score

    def _priority(self, slot: _Slot, clock: float) -> float:
        """clock + freq * ship_cost/byte: higher = more worth keeping.
        Normalizing by size makes the score "seconds of re-ship saved per
        resident byte", so small expensive blocks outrank big cheap ones."""
        nb = max(1, slot.entry.nbytes)
        try:
            cost = self._ship_cost(nb)
        except Exception:  # estimator must never break the cache
            cost = nb / _SHIP_BYTES_PER_SECOND
        return clock + slot.freq * (cost / nb)

    # ------------------------------------------------------------------- get

    def get(self, key: tuple, touch: bool = True) -> HotEntry | None:
        """Fetch an entry. `touch=False` serves it WITHOUT counting reuse —
        the prefetcher's consumer uses this so a background ship + its one
        planned consumption can't masquerade as proven reuse and pollute
        the protected segment."""
        with self._lock:
            slot = self._entries.get(key)
            if slot is None:
                self.misses += 1
                return None
            self.hits += 1
            QUERY_CACHE_HIT.labels("device_hotset").inc()
            entry = slot.entry
        if touch:
            self.touch(key)
        return entry

    def touch(self, key: tuple) -> None:
        """Apply the reuse accounting of a hit: bump recency + frequency,
        and promote a probationary entry with proven reuse into protected.

        Standalone (not fused into `get`) on purpose: the prefetch consumer
        always fetches with `touch=False` and decides AFTERWARDS whether
        the hit was proven reuse (it asks the prefetcher via `consumed()`,
        which answers atomically under its condvar). The old shape — peek
        first, then `get(touch=not prefetched)` — had a window where a ship
        completing between the two calls promoted a planned consumption
        into the protected segment (psan seed: the hotset/prefetch claim()
        interleaving). An entry evicted between a get and its touch is a
        silent no-op."""
        with self._lock:
            slot = self._entries.get(key)
            if slot is None:
                return
            self._entries.move_to_end(key)
            slot.freq += 1
            slot.pri = self._priority(slot, self._clock)
            if slot.probation:
                # re-touch: proven reuse -> promote into protected, capped
                # at _PROTECTED_FRAC of the budget. Over the cap, the
                # weakest protected entry is demoted iff this one is hotter
                # — otherwise the entry stays probation and keeps churning.
                nb = slot.entry.nbytes
                cap = int(self.budget * _PROTECTED_FRAC)
                if self._protected_bytes + nb <= cap:
                    slot.probation = False
                    self._protected_bytes += nb
                else:
                    prot = [s for s in self._entries.values() if not s.probation]
                    if prot:
                        weakest = min(prot, key=lambda s: s.pri)
                        if weakest.pri < slot.pri:
                            weakest.probation = True
                            self._protected_bytes -= weakest.entry.nbytes
                            slot.probation = False
                            self._protected_bytes += nb

    # ------------------------------------------------------------------- put

    def put(self, key: tuple, entry: HotEntry) -> None:
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.entry.nbytes
                if not old.probation:
                    self._protected_bytes -= old.entry.nbytes
            if entry.nbytes > self.budget:
                # would never fit; don't evict others for it — but COUNT it:
                # a silently un-cacheable block re-ships on every query
                self.rejected_oversize += 1
                HOTSET_REJECTED_OVERSIZE.inc()
                if key not in self._oversize_logged:
                    if len(self._oversize_logged) < 1024:
                        self._oversize_logged.add(key)
                    logger.warning(
                        "hot-set entry %r (%d bytes) exceeds the whole budget "
                        "(%d); it will re-ship on every query — raise "
                        "P_TPU_HOT_BYTES or shrink P_TPU_BLOCK_ROWS",
                        key[0] if key else key,
                        entry.nbytes,
                        self.budget,
                    )
                HOTSET_RESIDENT_BYTES.set(self._bytes)
                return
            slot = _Slot(entry)
            # ghost frequency: a key that keeps coming back re-enters with
            # the heat it earned before eviction/rejection
            slot.freq = self._ghost.pop(key, 0) + 1
            if old is not None:
                # replacement (e.g. a refreshed encoding): keep the key's
                # earned heat and segment instead of demoting it
                slot.freq = max(slot.freq, old.freq)
                slot.probation = old.probation
            slot.pri = self._priority(slot, self._clock)
            while self._bytes + entry.nbytes > self.budget and self._entries:
                probation = [
                    (k, s) for k, s in self._entries.items() if s.probation
                ]
                if probation:
                    # scan resistance: probation drains first, so
                    # one-shot blocks churn among themselves. Lowest
                    # score goes (cheap-to-re-ship before expensive);
                    # score ties break NEWEST-first — a sequential
                    # over-budget scan then churns a single slot
                    # instead of rolling the whole segment, which is
                    # LRU's cyclic worst case (every warm rep flushes
                    # exactly what the next rep needs first). Linear
                    # scan: entry counts are O(manifest files).
                    vkey, victim = min(
                        probation, key=lambda kv: (kv[1].pri, -kv[1].seq)
                    )
                    self._entries.pop(vkey)
                    # NO clock inflation here: intra-probation churn
                    # must keep score ties exact or the MRU tie-break
                    # degenerates back to rolling LRU
                else:
                    # every resident has proven reuse. Admission
                    # control: a first-touch candidate must BEAT the
                    # weakest protected score to displace it, so a
                    # one-shot full scan cannot flush the dashboard
                    # working set. The rejected key's ghost frequency
                    # still grows, so a genuine sustained shift in heat
                    # wins after a few recurrences.
                    vkey, victim = min(
                        self._entries.items(), key=lambda kv: kv[1].pri
                    )
                    if slot.probation and slot.pri <= victim.pri:
                        self.rejected_admission += 1
                        self._ghost[key] = slot.freq
                        self._ghost.move_to_end(key)
                        if len(self._ghost) > _GHOST_CAP:
                            self._ghost.popitem(last=False)
                        HOTSET_RESIDENT_BYTES.set(self._bytes)
                        return
                    self._entries.pop(vkey)
                    self._protected_bytes -= victim.entry.nbytes
                    # aging: future scores start from the evicted
                    # protected score, so long-resident-but-idle
                    # entries decay relative to new heat
                    if victim.pri > self._clock:
                        self._clock = victim.pri
                self._bytes -= victim.entry.nbytes
                self.evictions += 1
                HOTSET_EVICTIONS.inc()
                self._ghost[vkey] = victim.freq
                self._ghost.move_to_end(vkey)
                if len(self._ghost) > _GHOST_CAP:
                    self._ghost.popitem(last=False)
            self._seq += 1
            slot.seq = self._seq
            self._entries[key] = slot
            self._bytes += entry.nbytes
            if not slot.probation:
                self._protected_bytes += entry.nbytes
            HOTSET_RESIDENT_BYTES.set(self._bytes)

    # ----------------------------------------------------------------- peeks

    def contains(self, key: tuple) -> bool:
        """Peek without touching recency/frequency or hit/miss counters
        (the prefetcher asks before and after it ships a block)."""
        with self._lock:
            return key in self._entries

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._ghost.clear()
            self._bytes = 0
            self._protected_bytes = 0
            self._clock = 0.0
            HOTSET_RESIDENT_BYTES.set(0)

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats_snapshot(self) -> dict:
        """One consistent read of the cache's state (stats.stages.hotset)."""
        with self._lock:
            return {
                "budget_bytes": self.budget,
                "resident_bytes": self._bytes,
                "protected_bytes": self._protected_bytes,
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "rejected_oversize": self.rejected_oversize,
                "rejected_admission": self.rejected_admission,
            }


_GLOBAL_HOTSET: DeviceHotSet | None = None
_HOTSET_LOCK = threading.Lock()


def get_hotset() -> DeviceHotSet:
    """Process-wide hot set; re-roots (drops the old instance, device
    arrays freed by GC) when P_TPU_HOT_BYTES changes — same pattern as
    get_scan_scheduler, so tests and long-lived servers can resize the
    budget without stale singletons."""
    from parseable_tpu.config import env_int

    global _GLOBAL_HOTSET
    budget = env_int("P_TPU_HOT_BYTES", 8 << 30)
    with _HOTSET_LOCK:
        hs = _GLOBAL_HOTSET
        if hs is None or hs.budget != budget:
            _GLOBAL_HOTSET = DeviceHotSet(budget_bytes=budget)
        return _GLOBAL_HOTSET
