"""TPU query executor: predicate + group-by aggregation on device.

This is the "TPU execution backend" the whole build centers on (SURVEY §7
step 5). Per scanned block:

1. columns encode host-side once (ops/device.py): numerics -> f32, strings ->
   batch-local dictionary codes, timestamps -> canonical int32 epoch-2020
   seconds — and the encoded block can then live in the **device hot set**
   (ops/hotset.py), so repeated queries over hot data ship zero bytes;
2. the WHERE tree compiles to a device boolean mask; string predicates become
   dictionary LUT gathers (the regex runs once per unique value, not per
   row), numeric/time predicates are branchless compares;
3. group keys combine into one dense int32 id: dict codes go through a
   per-batch device-side remap (batch-local -> global dictionary), time bins
   are epoch-aligned; capacities are powers of two so XLA sees few shapes;
4. ONE jitted program per (plan, layout, block-shape) runs mask + remap +
   group ids + `fused_groupby_block` in a single dispatch per block, folding
   into a device accumulator; the host syncs once per flush and accumulates
   G-sized partials in float64;
5. past `DENSE_G_MAX` groups the fold is block-local (`jit_executor_local`,
   each block on its own dictionary codes) and the cross-block merge is the
   host's: `partials.merge_partials`, f64 across blocks. A plain GROUP BY
   hands it every block's nonzero groups. A top-K over one aggregate
   (`ORDER BY <aggregate> LIMIT k`) leaves the blocks' partials on the
   device, where `jit_executor_merge` sorts, totals and ranks them with an
   error margin, and hands it only the per-block rows of the groups that
   can be in the answer (`_merge_program`; `RouteStats` says which
   way a query went, and why).

The single-dispatch + async + resident-data design assumes per-query
host<->device traffic — not FLOPs — is the budget; neither the kernel's
rate nor the cost of a device round trip is measured on today's code.

What the device path does not run is a declared `UnsupportedOnDevice`,
and the list is short. At plan time, which hands the whole query to the CPU
engine: an aggregate whose argument is not a column or arithmetic over
numeric columns (a function call, CASE, a string, boolean or timestamp
operand, a divisor that is not a nonzero constant), a percentile or a
distinct count over an expression, an unknown aggregate. Per block, which
folds that block on the CPU engine into the same aggregator, so results stay
complete and exact: a column ops/device.py declines (a nested type, a
timestamp with sub-millisecond residue, a timestamp column no whole unit
holds in int32), an integer division (it truncates on the CPU engine), a
numeric aggregate over a string or timestamp column, `date_bin` with a
custom origin or sub-millisecond bins, a time bin over a timestamp column
that is off the block's origin in a unit that does not divide the bin (an
hour bin over ship dates held in whole days), `p_timestamp` itself off that
origin under the request's time bounds, exact distinct or percentile state
beyond its budget. That is the ONLY way
work leaves the device: every table or block so handed is counted in
`route_stats["cpu_fallback"]` (a plan-time rejection with its reason under
`cpu_fallback_reason`), every declined encoding in `encode_declined` and
`parseable_tpu_encode_declined_total{reason}`.
Any other exception from building or running a device program (a compiler
refusal, an HBM OOM) propagates and fails the query — a broken device path
must not answer from the CPU and look healthy.

Aggregates over arithmetic (`sum(price * (1 - discount))`: `+ - * /`, unary
minus, CAST between numerics, over numeric columns and literals) fold inside
the same programs: `AggExprCompiler` traces each tree in f32 under the scope
`fold/expr` into one more value row, a shared subtree once, validity the
AND of the operands'; `expr_aggs_device` / `expr_aggs_host` say where a
request's expressions were evaluated.

Time bins over event time (`date_bin` / `date_trunc` over a column that is
not the partition timestamp: rows backfilled, replayed or bulk-loaded carry a
time years from the minute they were ingested in). Such a column keeps a
day-aligned `origin_ms` and a `unit_ms` of its own (ops/device.py), and
wherever `bin_ms` is a whole multiple of that unit the bin of a row is
`(origin_ms // unit_ms + rel) // (bin_ms // unit_ms)`, exact in whole numbers,
computed in the column's own steps under the scope `keys`. Shift, offset and
divisor are the block's runtime scalars among the packed operands
(`_time_args`), so a text compiles once, not once a block origin or unit;
its group window is sized once from the text's own bounds on the column
(`_where_window_ms`), as `scan_time_hint` sizes the partition timestamp's.
A time-bin key over any column but `p_timestamp` keeps the last slot of its
capacity for the NULL time's group, as a dict key does (`KeySpec.null_slot`).
`timebin_offorigin_device_blocks` / `timebin_offorigin_host_blocks` say where
the bins of such a column were computed, `fold_minmax_scatter_blocks` how
many blocks folded a min or max (by scatter: `fold/segment_minmax`).

Precision: per-block reductions run in f32 (blocks <= 2^22 rows keep counts
exact; sums carry ~1e-5 relative error vs the CPU engine's f64); cross-block
accumulation is f64 on host. Device timestamps encode as exact int32
milliseconds relative to the block origin (see ops/device.py), so EVERY
comparison op — `<`, `>=`, `>`, `<=`, `=`, `!=`, including sub-second
literals — evaluates exactly on device with no second-granularity fallback;
sub-millisecond literals floor to ms, matching the CPU engine's coercion
(the two engines agree row-for-row). A timestamp column further than 12.4
days from the block's origin (an order's ship date) is held in the coarsest
whole unit that divides its values, from an origin of its own, and a
literal is turned into that unit by its operator (`_time_lit`): still exact.
Columns with sub-ms residue, or that no unit holds, decline device encoding
and take the CPU path instead, counted.
"""

from __future__ import annotations

import logging
import math
import re
import time as _time
from dataclasses import dataclass, field as dc_field
from datetime import UTC, datetime, timedelta
from typing import Any, Callable, Iterator

import numpy as np
import pyarrow as pa

from parseable_tpu.config import Options
from parseable_tpu.ops import kernels
from parseable_tpu.ops.device import (
    EncodedBatch,
    EncodedColumn,
    encode_table,
    note_engine_devices,
)
from parseable_tpu.ops.hotset import HotEntry, get_hotset
from parseable_tpu.query import sql as S
from parseable_tpu.query.executor import (
    AggSpec,
    HashAggregator,
    QueryExecutor,
)
from parseable_tpu.query.planner import LogicalPlan
from parseable_tpu.query.sketch import BINS as PCT_BINS
from parseable_tpu.query.sketch import DEVICE_NB, LOG_HI, LOG_LO
from parseable_tpu.query.sketch import _SCALE as PCT_SCALE
from parseable_tpu.utils.metrics import (
    DEVICE_BYTES_TO_DEVICE,
    DEVICE_EXECUTE_TIME,
    DEVICE_EXPR_AGGREGATES,
    DEVICE_JIT_PROGRAMS,
    DEVICE_MERGES,
    DEVICE_PHASE_SECONDS,
    DEVICE_RECOMPILES,
    DEVICE_TIMEBIN_OFFORIGIN,
)
from parseable_tpu.utils.telemetry import TRACER
from parseable_tpu.utils.timeutil import parse_duration, parse_rfc3339

logger = logging.getLogger(__name__)

SOURCE_ID_META = b"ptpu_source_id"
# pow2_block's ceiling: tables beyond this split before encoding
MAX_BLOCK_ROWS = 1 << 22
STUB_META = b"ptpu_hot_stub"

# High-cardinality group-by (VERDICT r2 #2): past this dense global group
# space the executor switches to block-local two-phase aggregation — the
# device folds each block on its OWN dictionary codes (already dense). What
# happens to the blocks' partials depends on the plan. A top-K over one
# aggregate (`ORDER BY <aggregate> LIMIT k`) keeps them on the device:
# `jit_executor_merge` sorts, totals and ranks them there with a margin and
# hands the host only the groups that can be in the answer, some tens of
# rows. Every other plan reads each block's dense partial back, extracts its
# nonzero groups as a partial table, and ONE vectorized pyarrow group_by
# merges all partials at finalize — the same `partials.merge_partials` that
# the survivors of a top-K go through, so values and order are made in one
# place. No capacity epochs, no global remap on the device (whose LUT
# transfer grows with the dictionary), no per-group Python — a 1M-distinct
# GROUP BY degrades gracefully instead of falling off a cliff (DataFusion
# hash-aggregate parity: /root/reference/src/query/mod.rs:212-276).
DENSE_G_MAX = 1 << 19
# per-block group-space ceiling in local mode (beyond -> that block folds
# on the CPU; multi-key blocks with two 1M-card keys can't product-combine)
LOCAL_G_MAX = 1 << 22
# device percentile budget: one [G, DEVICE_NB] f32 histogram per
# approx_percentile spec (64 MB at the default 2049-slot sketch layout);
# beyond it the scan stays host-side with exact sketches
PCT_MAX_ELEMS = 1 << 24
# Device merge of a block-local top-K: the most entries (sum of the kept
# blocks' G_block) that `jit_executor_merge` is given. Per entry the device
# holds, while the program runs: the kept partial (R f32 rows, 3 for a
# count + sum: 12 B) and key lanes (4 B a key) twice, as the blocks' own
# arrays and stacked as the program's arguments; the sort's operands and
# results (keys, place, the one or two rows ranked: 4 B each, in and out);
# the scanned lanes with a shifted copy each and the scores. With two keys
# and three rows the chip's compiler counts 24 B of arguments and 45 B of
# temporaries an entry (402 MB + 756 MB at 2^24 entries), 89 B with the
# blocks' own arrays. 2^25 entries are 3.0 GB beside a hot set whose
# budget (P_TPU_HOT_BYTES, 8 GB) is half of a v5e chip's 16 GB; 2^26 would
# be 6 GB and leave the folds' own temporaries and the dense paths of
# concurrent queries too little. Past it the kept partials go through the
# host merge: exact, and slow.
MERGE_DEVICE_MAX_ENTRIES = 1 << 25
# survivors gathered at most, whatever k asks for (k' = max(4k, 64) rounded
# up to a power of two, capped here); more survivors than k' (mass ties at
# the k-th value) also means the host merge
SURVIVORS_MAX = 1 << 14
# key lanes of the device merge: a NULL key's global code (what
# GlobalDict.absorb gives a null or a padding slot), and the code of a slot
# that holds no group, which sorts last. Real codes and time-bin offsets
# stay under 2^30 in size.
_LANE_NULL = np.int32(1 << 30)
_LANE_DEAD = np.int32(2**31 - 1)


class UnsupportedOnDevice(Exception):
    pass


def dict_group_columns(select: S.Select) -> set[str]:
    """Group-by columns that device-encode as dictionaries (plain columns)."""
    out = set()
    for g in select.group_by:
        e = g.expr if isinstance(g, S.Cast) else g
        if isinstance(e, S.Column):
            out.add(e.name)
    return out


def hot_key(source_id: bytes, needed: set[str] | None, dict_cols: set[str]) -> tuple:
    return (
        source_id,
        tuple(sorted(needed)) if needed is not None else None,
        tuple(sorted(dict_cols)),
    )


def is_stub(table: pa.Table) -> bool:
    return (table.schema.metadata or {}).get(STUB_META) is not None


def make_stub(source_id: bytes, num_rows: int) -> pa.Table:
    """Zero-copy placeholder for a device-resident block."""
    return pa.table({}).replace_schema_metadata(
        {SOURCE_ID_META: source_id, STUB_META: str(num_rows).encode()}
    )


def _pow2(n: int, minimum: int = 8) -> int:
    p = minimum
    while p < n:
        p <<= 1
    return p


# The dense block loop's small operands (predicate LUTs, time scalars, key
# and distinct remaps) cross to the device packed: one flat host buffer per
# dtype for a whole dispatch group, one transfer each, sliced apart again
# inside the program at offsets that follow from the operands' signature.


def _operand_sig(arrays) -> tuple:
    """(dtype, shape) of each operand: all the packed layout depends on."""
    return tuple((a.dtype.str, a.shape) for a in arrays)


def _pack_operands(blocks: list[tuple]) -> tuple[np.ndarray, ...]:
    """The operands of a group's blocks (a tuple of operand tuples a block),
    flattened block by block in their own order into one buffer per dtype,
    the buffers in sorted dtype order."""
    by: dict[str, list[np.ndarray]] = {}
    for ops in blocks:
        for part in ops:
            for a in part:
                by.setdefault(a.dtype.str, []).append(a.reshape(-1))
    return tuple(np.concatenate(by[d]) for d in sorted(by))


def _operand_dtypes(sig: tuple) -> list[str]:
    """The packed buffers' dtypes, in their order, for one block's `sig`
    (its `_operand_sig` a part)."""
    return sorted({d for part in sig for d, _ in part})


def _unpack_operands(packed: tuple, sig: tuple, n_blocks: int) -> list[tuple]:
    """`_pack_operands` undone inside a trace: every block's operand tuples
    as static slices of the packed buffers, values, dtypes and shapes as
    they were on the host."""
    bufs = dict(zip(_operand_dtypes(sig), packed))
    offs = dict.fromkeys(bufs, 0)
    blocks = []
    for _ in range(n_blocks):
        parts = []
        for part in sig:
            arrs = []
            for d, shape in part:
                n = math.prod(shape)
                arrs.append(bufs[d][offs[d] : offs[d] + n].reshape(shape))
                offs[d] += n
            parts.append(tuple(arrs))
        blocks.append(tuple(parts))
    return blocks


# ------------------------------------------------------------- global dicts


class GlobalDict:
    """Union of per-batch dictionaries for one column, plus device remaps.

    Absorb is vectorized (VERDICT r2: the per-value Python loop capped the
    engine at small dictionaries): known values resolve through ONE
    `pc.index_in` C++ hash probe against the accumulated dictionary; only
    genuinely new values take the Python append. A 100k-entry batch
    dictionary costs one hash-table probe pass, not 100k dict lookups.
    """

    def __init__(self) -> None:
        self.values: list[Any] = []
        self._chunks: list[pa.Array] = []  # same values, arrow-side

    def absorb(self, batch_dict: list[Any], batch_arr: pa.Array | None = None) -> np.ndarray:
        """Register a batch dictionary; return the batch->global int32 remap,
        padded to pow2 with a large sentinel (nulls + padding decode as the
        null group). `batch_arr` is the same dictionary as an arrow array,
        where the caller has one cached."""
        card = len(batch_dict)
        lut = np.full(_pow2(card + 1), np.int32(2**30), dtype=np.int32)
        if card == 0:
            return lut
        import pyarrow.compute as pc

        if self.values and not self._chunks:
            # a previous batch fell back to slow mode; the arrow-side view
            # is stale, so stay on the slow path for dictionary consistency
            return self._absorb_slow(batch_dict, lut)
        if batch_arr is None:
            try:
                batch_arr = pa.array(batch_dict)
            except (pa.ArrowInvalid, pa.ArrowTypeError):
                return self._absorb_slow(batch_dict, lut)
        if self._chunks:
            value_set: pa.Array | pa.ChunkedArray = (
                self._chunks[0]
                if len(self._chunks) == 1
                else pa.chunked_array(self._chunks)
            )
            try:
                idx = pc.index_in(batch_arr, value_set=value_set)
            except (pa.ArrowInvalid, pa.ArrowTypeError, pa.ArrowNotImplementedError):
                return self._absorb_slow(batch_dict, lut)
            known = idx.fill_null(-1).to_numpy(zero_copy_only=False).astype(np.int64)
        else:
            known = np.full(card, -1, dtype=np.int64)
        valid = np.asarray(pc.is_valid(batch_arr).to_numpy(zero_copy_only=False), bool)
        new_mask = (known < 0) & valid
        new_pos = np.nonzero(new_mask)[0]
        if len(new_pos):
            base = len(self.values)
            new_vals = batch_arr.take(pa.array(new_pos))
            # batch dictionaries hold unique values, so bulk-append is safe
            self.values.extend(new_vals.to_pylist())
            self._chunks.append(new_vals)
            known[new_pos] = base + np.arange(len(new_pos))
        lut[: len(known)][valid & (known >= 0)] = known[valid & (known >= 0)].astype(
            np.int32
        )
        return lut

    def _absorb_slow(self, batch_dict: list[Any], lut: np.ndarray) -> np.ndarray:
        """Mixed-type dictionaries arrow can't hash: per-value fallback."""
        index = {v: i for i, v in enumerate(self.values)}
        for i, v in enumerate(batch_dict):
            if v is None:
                continue
            gi = index.get(v)
            if gi is None:
                gi = len(self.values)
                self.values.append(v)
                index[v] = gi
            lut[i] = gi
        self._chunks = []  # arrow-side view no longer tracks .values
        return lut

    def __len__(self) -> int:
        return len(self.values)


# --------------------------------------------------------------- group keys


@dataclass
class KeySpec:
    kind: str  # "dict" | "timebin"
    column: str
    expr: S.Expr
    bin_ms: int = 0  # timebin only
    gdict: GlobalDict | None = None  # dict only
    capacity: int = 1  # current stride capacity (pow2)
    origin_rel: int | None = None  # timebin only: origin *bin index*
    # timebin only: code `capacity - 1` is the NULL time's group, as a dict
    # key's is. The partition timestamp is never NULL and keeps every slot
    # for a bin; an event-time column (`ev`, backfilled) may hold one
    null_slot: bool = False

    def epoch_values(self) -> list[Any]:
        """dict only: the values this capacity epoch's codes can name. Code
        `capacity - 1` is the epoch's null slot (capacity > len(gdict) when
        the epoch opens), and the dictionary may have absorbed the NEXT
        block's values by the time the epoch is flushed — so the decode
        stops at capacity - 1, never at the dictionary's current length."""
        return self.gdict.values[: self.capacity - 1]


def _like_to_regex(pattern: str) -> str:
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == "\\" and i + 1 < len(pattern) and pattern[i + 1] in ("%", "_", "\\"):
            # backslash-escaped wildcard is a literal (matches Arrow's
            # pc.match_like semantics on the CPU path)
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return "^" + "".join(out) + "$"


def _interval_ms(e: S.Expr) -> int | None:
    if isinstance(e, S.IntervalLit):
        return int(parse_duration(e.text).total_seconds() * 1000)
    if isinstance(e, S.Literal) and isinstance(e.value, str):
        try:
            return int(parse_duration(e.value).total_seconds() * 1000)
        except ValueError:
            return None
    return None


_TRUNC_MS = {
    "second": 1000,
    "minute": 60_000,
    "hour": 3_600_000,
    "day": 86_400_000,
}


def _may_be_null(time_column: str) -> bool:
    from parseable_tpu import DEFAULT_TIMESTAMP_KEY

    return time_column != DEFAULT_TIMESTAMP_KEY


def classify_group_expr(e: S.Expr) -> KeySpec:
    """Map a GROUP BY expression onto a device key kind, or raise."""
    if isinstance(e, S.Column):
        return KeySpec("dict", e.name, e, gdict=GlobalDict())
    if isinstance(e, S.FunctionCall) and e.name == "date_bin" and len(e.args) >= 2:
        if len(e.args) > 2:
            # custom bin origin: device bins are epoch-aligned only
            raise UnsupportedOnDevice("date_bin with explicit origin")
        ms = _interval_ms(e.args[0])
        col = e.args[1]
        # any >=1ms bin maps exactly; the upper bound keeps the device-side
        # shift (origin % bin_ms + rel) inside int32
        if ms and ms <= (1 << 30) and isinstance(col, S.Column):
            return KeySpec("timebin", col.name, e, bin_ms=ms, null_slot=_may_be_null(col.name))
        raise UnsupportedOnDevice("sub-millisecond or >12-day date_bin")
    if isinstance(e, S.FunctionCall) and e.name == "date_trunc" and len(e.args) == 2:
        unit = e.args[0].value if isinstance(e.args[0], S.Literal) else None
        col = e.args[1]
        ms = _TRUNC_MS.get(str(unit).lower()) if unit else None
        if ms and isinstance(col, S.Column):
            return KeySpec("timebin", col.name, e, bin_ms=ms, null_slot=_may_be_null(col.name))
    if isinstance(e, S.Cast):
        return classify_group_expr(e.expr)
    raise UnsupportedOnDevice(f"group expression not device-mappable: {S.expr_name(e)}")


# ------------------------------------------------------------ mask compiler


class PredicateCompiler:
    """Compile a WHERE tree into device ops, in two phases per batch:

    - `collect_luts(e, enc)` (host): evaluate string predicates over the
      *batch* dictionary into boolean LUTs, padded to pow2. Cached on the
      EncodedBatch (lifetime == dictionary lifetime), so for hot-set-resident
      blocks the regex work happens exactly once per (pattern, block).
    - `trace(e, enc, dev, luts)` (traced or eager): emit jnp ops, consuming
      the LUT arrays positionally. Runs identically under jax.jit (LUTs as
      runtime args) and eagerly.
    """

    # ---------------------------------------------------------- phase A

    def collect_luts(self, e: S.Expr | None, enc: EncodedBatch) -> list[np.ndarray]:
        out: list[np.ndarray] = []
        if e is not None:
            self._walk_collect(e, enc, out)
        return out

    def _walk_collect(self, e: S.Expr, enc: EncodedBatch, out: list[np.ndarray]) -> None:
        if isinstance(e, S.BinaryOp):
            if e.op in ("and", "or"):
                self._walk_collect(e.left, enc, out)
                self._walk_collect(e.right, enc, out)
                return
            if e.op in ("=", "!=", "<", "<=", ">", ">="):
                col, op, lit = self._cmp_parts(e, enc)
                if col.kind == "dict":
                    out.append(self._dict_lut(enc, col, op, lit))
                elif col.kind == "time":
                    # per-block rel-ms literal as a runtime scalar: rides
                    # the LUT channel so one compiled program serves every
                    # block regardless of its time origin
                    out.append(self._time_lit(enc, col, op, lit))
                return
            if e.op in ("like", "ilike", "not_like", "not_ilike"):
                col = self._column_of(e.left, enc)
                raw = str(self._literal_of(e.right))
                out.append(
                    self._regex_lut(
                        enc,
                        col,
                        _like_to_regex(raw),
                        re.IGNORECASE if "ilike" in e.op else 0,
                        e.op.startswith("not_"),
                    )
                )
                return
        if isinstance(e, S.UnaryOp) and e.op == "not":
            self._walk_collect(e.operand, enc, out)
            return
        if isinstance(e, S.Between):
            self._walk_collect(S.BinaryOp(">=", e.expr, e.low), enc, out)
            self._walk_collect(S.BinaryOp("<=", e.expr, e.high), enc, out)
            return
        if isinstance(e, S.InList):
            col = self._column_of(e.expr, enc)
            if col.kind == "dict":
                out.append(self._in_lut(enc, e, col))
            return
        if isinstance(e, S.FunctionCall) and e.name in ("regexp_match", "regexp_like"):
            col = self._column_of(e.args[0], enc)
            out.append(self._regex_lut(enc, col, str(self._literal_of(e.args[1])), 0, False))
            return
        if isinstance(e, (S.IsNull, S.Literal)):
            return
        raise UnsupportedOnDevice(f"predicate not device-mappable: {type(e).__name__}")

    # ---------------------------------------------------------- phase B

    def trace(self, e: S.Expr | None, enc: EncodedBatch, dev: dict, luts: list):
        import jax.numpy as jnp

        if e is None:
            return dev["__ones"] if "__ones" in dev else jnp.ones(enc.block_rows, bool)
        it = iter(luts)
        return self._visit(e, enc, dev, it)

    def _visit(self, e: S.Expr, enc: EncodedBatch, dev, luts):
        import jax.numpy as jnp

        if isinstance(e, S.BinaryOp):
            if e.op == "and":
                return jnp.logical_and(
                    self._visit(e.left, enc, dev, luts), self._visit(e.right, enc, dev, luts)
                )
            if e.op == "or":
                return jnp.logical_or(
                    self._visit(e.left, enc, dev, luts), self._visit(e.right, enc, dev, luts)
                )
            if e.op in ("=", "!=", "<", "<=", ">", ">="):
                return self._cmp(e, enc, dev, luts)
            if e.op in ("like", "ilike", "not_like", "not_ilike"):
                col = self._column_of(e.left, enc)
                if col.kind != "dict":
                    raise UnsupportedOnDevice("string predicate on non-string column")
                lut = next(luts)
                return jnp.logical_and(lut[_as_index(dev[col.name])], dev[f"{col.name}__valid"])
        if isinstance(e, S.UnaryOp) and e.op == "not":
            return jnp.logical_not(self._visit(e.operand, enc, dev, luts))
        if isinstance(e, S.Between):
            m = jnp.logical_and(
                self._cmp(S.BinaryOp(">=", e.expr, e.low), enc, dev, luts),
                self._cmp(S.BinaryOp("<=", e.expr, e.high), enc, dev, luts),
            )
            return jnp.logical_not(m) if e.negated else m
        if isinstance(e, S.InList):
            return self._in_list(e, enc, dev, luts)
        if isinstance(e, S.IsNull):
            col = self._column_of(e.expr, enc)
            valid = dev[f"{col.name}__valid"]
            return valid if e.negated else jnp.logical_not(valid)
        if isinstance(e, S.FunctionCall) and e.name in ("regexp_match", "regexp_like"):
            col = self._column_of(e.args[0], enc)
            if col.kind != "dict":
                raise UnsupportedOnDevice("regex on non-string column")
            lut = next(luts)
            return jnp.logical_and(lut[_as_index(dev[col.name])], dev[f"{col.name}__valid"])
        if isinstance(e, S.Literal) and isinstance(e.value, bool):
            # size from the device array, not enc.block_rows: under
            # shard_map this trace sees the per-device row shard
            return jnp.full(dev["__ones"].shape[0], e.value)
        raise UnsupportedOnDevice(f"predicate not device-mappable: {type(e).__name__}")

    # ---------------------------------------------------------- shared bits

    def _column_of(self, e: S.Expr, enc: EncodedBatch) -> EncodedColumn:
        if isinstance(e, S.Cast):
            return self._column_of(e.expr, enc)
        if not isinstance(e, S.Column):
            raise UnsupportedOnDevice("expected a column operand")
        col = enc.columns.get(e.name)
        if col is None:
            raise UnsupportedOnDevice(f"column {e.name} not encoded")
        return col

    def _literal_of(self, e: S.Expr) -> Any:
        if isinstance(e, S.Literal):
            return e.value
        if isinstance(e, S.Cast):
            return self._literal_of(e.expr)
        if isinstance(e, S.FunctionCall) and e.name == "to_timestamp" and e.args:
            return self._literal_of(e.args[0])
        raise UnsupportedOnDevice("expected a literal operand")

    def _cmp_parts(self, e: S.BinaryOp, enc: EncodedBatch):
        left_is_col = isinstance(e.left, (S.Column, S.Cast)) and not isinstance(e.left, S.Literal)
        if left_is_col:
            return self._column_of(e.left, enc), e.op, self._literal_of(e.right)
        flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
        return self._column_of(e.right, enc), flip.get(e.op, e.op), self._literal_of(e.left)

    def _cmp(self, e: S.BinaryOp, enc: EncodedBatch, dev, luts):
        import jax.numpy as jnp

        col, op, lit = self._cmp_parts(e, enc)
        valid = dev[f"{col.name}__valid"]
        values = dev[col.name]
        if col.kind == "dict":
            lut = next(luts)
            mask = lut[_as_index(values)]
        elif col.kind == "time":
            # values are exact int32 ms rel to the block origin, so every
            # comparison op (incl. =, !=, <=, > and sub-second literals)
            # is exact — no more second-floor fallbacks
            mask = _num_cmp(values, op, next(luts)[0])
        elif col.kind in ("num", "bool"):
            if not isinstance(lit, (int, float, bool)):
                raise UnsupportedOnDevice("numeric compared to non-numeric literal")
            mask = _num_cmp(values, op, float(lit))
        else:
            raise UnsupportedOnDevice(f"cannot compare column kind {col.kind}")
        return jnp.logical_and(mask, valid)

    @staticmethod
    def _time_lit(enc: EncodedBatch, col: EncodedColumn, op: str, lit: Any) -> np.ndarray:
        """Literal in the column's own int32 steps, shipped as a runtime scalar.

        Sub-ms literals FLOOR to ms — matching the CPU engine, whose
        comparisons coerce the literal to the (ms) column type via
        pa.scalar(..., type=t) (executor.py _coerce/_bounds_filter); the
        two engines must agree row-for-row, and device rows are
        ms-quantized anyway (encode declines columns with sub-ms residue).

        A column off the batch origin (ops/device.py: `origin_ms`, whole
        steps of `unit_ms`) holds no value between two steps, so the ms
        literal is turned into steps toward the side that changes no row:
        ceiling for `<` and `>=`, floor for `<=` and `>`; between two
        steps `=` can hold for no row and `!=` holds for every one.

        Out-of-range literals clamp to just inside int32: encoded rel
        values are bounded by TIME_REL_SPAN (< 2^30), so a clamped bound
        compares uniformly true/false against every row — exactly the
        semantics of a literal beyond the block's representable window —
        and can never equal a live value."""
        if isinstance(lit, str):
            lit_dt = parse_rfc3339(lit)
        elif isinstance(lit, datetime):
            lit_dt = lit if lit.tzinfo else lit.replace(tzinfo=UTC)
        else:
            raise UnsupportedOnDevice("timestamp compared to non-time literal")
        origin = enc.time_origin_ms if col.origin_ms is None else col.origin_ms
        rel, between = divmod(_dt_to_us(lit_dt) // 1000 - origin, col.unit_ms)
        if between and op in ("=", "!="):
            rel = 2**31 - 2  # no live value
        elif between and op in ("<", ">="):
            rel += 1
        rel = max(-(2**31) + 2, min(2**31 - 2, rel))
        return np.asarray([rel], dtype=np.int32)

    def _in_list(self, e: S.InList, enc: EncodedBatch, dev, luts):
        import jax.numpy as jnp

        col = self._column_of(e.expr, enc)
        valid = dev[f"{col.name}__valid"]
        if col.kind == "dict":
            lut = next(luts)
            return jnp.logical_and(lut[_as_index(dev[col.name])], valid)
        if col.kind in ("num", "bool"):
            lits = [self._literal_of(i) for i in e.items]
            mask = jnp.zeros_like(valid)
            for v in lits:
                mask = jnp.logical_or(mask, dev[col.name] == float(v))
            if e.negated:
                mask = jnp.logical_not(mask)
            return jnp.logical_and(mask, valid)
        raise UnsupportedOnDevice("IN on unsupported column kind")

    # ---------------------------------------------------------- LUT builders
    # LUTs are built over the BATCH dictionary (codes index it directly) and
    # cached on the EncodedBatch so hot blocks never re-evaluate a predicate.

    @staticmethod
    def _batch_cache(enc: EncodedBatch) -> dict:
        cache = getattr(enc, "lut_cache", None)
        if cache is None:
            cache = {}
            enc.lut_cache = cache
        return cache

    def _padded(self, lut: np.ndarray) -> np.ndarray:
        n = _pow2(len(lut))
        if n == len(lut):
            return lut
        out = np.zeros(n, dtype=bool)
        out[: len(lut)] = lut
        return out

    def _dict_lut(self, enc: EncodedBatch, col: EncodedColumn, op: str, lit: Any) -> np.ndarray:
        cache = self._batch_cache(enc)
        key = (col.name, op, repr(lit))
        hit = cache.get(key)
        if hit is not None:
            return hit
        import operator as _op

        values = col.dictionary[:-1]
        fns = {"=": _op.eq, "!=": _op.ne, "<": _op.lt, "<=": _op.le, ">": _op.gt, ">=": _op.ge}
        f = fns[op]
        lut = np.zeros(len(values) + 1, dtype=bool)  # +1 null slot -> False
        for i, v in enumerate(values):
            if v is None:
                continue
            try:
                lut[i] = bool(f(v, lit))
            except TypeError:
                lut[i] = False
        lut = self._padded(lut)
        cache[key] = lut
        return lut

    def _regex_lut(
        self, enc: EncodedBatch, col: EncodedColumn, pattern: str, flags: int, negate: bool
    ) -> np.ndarray:
        if col.kind != "dict":
            raise UnsupportedOnDevice("string predicate on non-string column")
        cache = self._batch_cache(enc)
        key = (col.name, pattern, flags, negate)
        hit = cache.get(key)
        if hit is not None:
            return hit
        rx = re.compile(pattern, flags)
        values = col.dictionary[:-1]
        lut = np.zeros(len(values) + 1, dtype=bool)
        for i, v in enumerate(values):
            if isinstance(v, str):
                m = rx.search(v) is not None
                lut[i] = (not m) if negate else m
        lut = self._padded(lut)
        cache[key] = lut
        return lut

    def _in_lut(self, enc: EncodedBatch, e: S.InList, col: EncodedColumn) -> np.ndarray:
        cache = self._batch_cache(enc)
        lits = {self._literal_of(i) for i in e.items}
        key = (col.name, "in", repr(sorted(map(repr, lits))), e.negated)
        hit = cache.get(key)
        if hit is not None:
            return hit
        values = col.dictionary[:-1]
        lut = np.zeros(len(values) + 1, dtype=bool)
        for i, v in enumerate(values):
            inside = v in lits
            lut[i] = (not inside) if e.negated else inside
        lut = self._padded(lut)
        cache[key] = lut
        return lut


def _as_index(a):
    """Dictionary codes ship in the narrowest dtype (int8/int16) but index
    LUTs whose SIZE may exceed that dtype's range — JAX gathers materialize
    the array size in the index dtype, so upcast to int32 in-program (XLA
    fuses the convert; transfer stays narrow)."""
    import jax.numpy as jnp

    return a if a.dtype == jnp.int32 else a.astype(jnp.int32)


_EPOCH_UTC = datetime(1970, 1, 1, tzinfo=UTC)


def _dt_to_us(dt: datetime) -> int:
    """Exact integer epoch-microseconds (float .timestamp() wobbles at
    2024-era magnitudes; datetime precision is exactly us)."""
    return (dt - _EPOCH_UTC) // timedelta(microseconds=1)


def _require_on_origin(col: EncodedColumn | None) -> None:
    """The request's time bounds are arithmetic in ms from the block's
    origin: a column that keeps an origin and a unit of its own
    (ops/device.py) does not take them on the device."""
    if col is not None and col.origin_ms is not None:
        raise UnsupportedOnDevice(f"time column {col.name} is off the block's origin")


def _time_base(enc: EncodedBatch, col: EncodedColumn) -> tuple[int, int]:
    """(origin_ms, unit_ms) of a time column: its int32 values are whole
    `unit_ms` steps from `origin_ms`, the block's origin in ms unless the
    column keeps a day-aligned one of its own (ops/device.py)."""
    return (enc.time_origin_ms, 1) if col.origin_ms is None else (col.origin_ms, col.unit_ms)


def _bin_steps(ks: KeySpec, col: EncodedColumn) -> int:
    """A time bin in the column's own steps. An origin of its own is
    day-aligned, so a whole multiple of every unit ops/device.py takes: the
    bin of a row is then `(origin_ms // unit_ms + rel) // steps`, exact in
    whole numbers. A bin the unit does not divide has no such form."""
    steps, rest = divmod(ks.bin_ms, col.unit_ms)
    if rest:
        raise UnsupportedOnDevice(
            f"date_bin of {ks.bin_ms} ms over {col.name}, held in steps of {col.unit_ms} ms"
        )
    return steps


def _bin_range(ks: KeySpec, enc: EncodedBatch, col: EncodedColumn) -> tuple[int, int]:
    """The absolute bins (epoch ms // bin_ms) of the column's least and
    greatest live value in this block."""
    origin_ms, unit_ms = _time_base(enc, col)
    return (
        (origin_ms + col.vmin * unit_ms) // ks.bin_ms,
        (origin_ms + col.vmax * unit_ms) // ks.bin_ms,
    )


def _off_origin_keys(enc: EncodedBatch, key_specs: list[KeySpec]) -> tuple[bool, ...]:
    """Per group key: a time bin over a column that is off the block's
    origin. Its divisor ships as a third runtime scalar (`_time_args`), so it
    is part of what a program is traced for and of its cache key."""
    return tuple(
        ks.kind == "timebin"
        and getattr(enc.columns.get(ks.column), "origin_ms", None) is not None
        for ks in key_specs
    )


def _num_cmp(values, op: str, threshold):
    import jax.numpy as jnp

    t = jnp.asarray(threshold, dtype=values.dtype)
    return {
        "=": values == t,
        "!=": values != t,
        "<": values < t,
        "<=": values <= t,
        ">": values > t,
        ">=": values >= t,
    }[op]


# --------------------------------------------- aggregates over expressions

_INT_CASTS = ("int", "integer", "bigint")
_FLOAT_CASTS = ("float", "double", "real")


class AggExprCompiler:
    """Aggregate arguments that are arithmetic over numeric columns
    (`sum(price * (1 - discount))`): trees of `+ - * /`, unary minus and
    CAST between numerics over `num` columns and numeric literals.

    An expression becomes one more value row of the fold that is there: it
    is named by its canonical text (`name`), the layouts carry that name
    like a column's (so it is part of every program's cache key), and
    `trace` computes it in f32 from the block's column arrays inside the
    program, its validity the AND of its operands' (a NULL operand makes a
    NULL, which no aggregate takes, as pyarrow's arithmetic does on the CPU
    engine). A subtree that two expressions share is traced once.

    What stays a declared `UnsupportedOnDevice`: a function call or CASE, a
    string, boolean or timestamp operand, a divisor that is not a nonzero
    constant (x / 0 is +-inf on the CPU engine, and one non-finite addend
    would reach every group of the one-hot fold), and a division of two
    integers (which truncates there)."""

    PREFIX = "__expr:"

    @classmethod
    def name(cls, e: S.Expr) -> str:
        return cls.PREFIX + cls._text(e)

    @classmethod
    def _text(cls, e: S.Expr) -> str:
        if isinstance(e, S.Column):
            return e.name
        if isinstance(e, S.Literal):
            return repr(e.value)
        if isinstance(e, S.UnaryOp):
            return f"({e.op}{cls._text(e.operand)})"
        if isinstance(e, S.BinaryOp):
            return f"({cls._text(e.left)} {e.op} {cls._text(e.right)})"
        if isinstance(e, S.Cast):
            return f"cast({cls._text(e.expr)} as {e.type_name})"
        raise UnsupportedOnDevice(f"aggregate over expression: {S.expr_name(e)}")

    @classmethod
    def constant(cls, e: S.Expr):
        """The subtree's value where it names no column (folded as the CPU
        engine folds it: python arithmetic), else None."""
        from parseable_tpu.query.executor import _python_binop

        if isinstance(e, S.Literal):
            v = e.value
            return v if isinstance(v, (int, float)) and not isinstance(v, bool) else None
        if isinstance(e, S.UnaryOp) and e.op == "-":
            v = cls.constant(e.operand)
            return None if v is None else -v
        if isinstance(e, S.BinaryOp) and e.op in ("+", "-", "*", "/"):
            a, b = cls.constant(e.left), cls.constant(e.right)
            if a is None or b is None or (e.op == "/" and b == 0):
                return None
            return _python_binop(e.op, a, b)
        if isinstance(e, S.Cast):
            v = cls.constant(e.expr)
            if v is None or e.type_name not in _INT_CASTS + _FLOAT_CASTS:
                return None
            return int(v) if e.type_name in _INT_CASTS else float(v)
        return None

    @classmethod
    def check(cls, e: S.Expr, enc: EncodedBatch | None = None) -> str:
        """The tree's type, "int" or "float", as the CPU engine would have
        it; raises `UnsupportedOnDevice` for what the device does not run.
        Without a block only the tree's shape is checked (plan time);
        with one, its columns' kinds and the integer divisions too."""
        c = cls.constant(e)
        if c is not None:
            return "int" if isinstance(c, int) else "float"
        if isinstance(e, S.Column):
            if enc is None:
                return "float"
            col = enc.columns.get(e.name)
            if col is None:
                raise UnsupportedOnDevice(f"aggregate column {e.name} missing")
            if col.kind != "num":
                raise UnsupportedOnDevice(f"expression over a {col.kind} column: {e.name}")
            return "int" if col.integral else "float"
        if isinstance(e, S.UnaryOp) and e.op == "-":
            return cls.check(e.operand, enc)
        if isinstance(e, S.Cast) and e.type_name in _INT_CASTS + _FLOAT_CASTS:
            cls.check(e.expr, enc)
            return "int" if e.type_name in _INT_CASTS else "float"
        if isinstance(e, S.BinaryOp) and e.op in ("+", "-", "*", "/"):
            a, b = cls.check(e.left, enc), cls.check(e.right, enc)
            if e.op == "/":
                if not cls.constant(e.right):
                    raise UnsupportedOnDevice(f"division by other than a nonzero constant: {cls._text(e)}")
                if enc is not None and a == b == "int":
                    raise UnsupportedOnDevice(f"integer division: {cls._text(e)}")
            return "int" if a == b == "int" else "float"
        raise UnsupportedOnDevice(f"aggregate over expression: {S.expr_name(e)}")

    @classmethod
    def nodes(cls, exprs: tuple) -> int:
        """Arithmetic nodes `trace` makes a row for `exprs`, by the tree
        alone (a CAST to a float type makes none)."""
        seen: set[str] = set()

        def visit(e: S.Expr) -> None:
            if cls.constant(e) is not None or isinstance(e, S.Column):
                return
            if isinstance(e, S.Cast) and e.type_name in _FLOAT_CASTS:
                return visit(e.expr)
            seen.add(cls._text(e))
            for child in (getattr(e, k, None) for k in ("operand", "expr", "left", "right")):
                if child is not None:
                    visit(child)

        for _, tree in exprs:
            visit(tree)
        return len(seen)

    @classmethod
    def trace(cls, dev: dict, exprs: tuple) -> dict:
        """`dev` with one value column and one validity column more for
        each of `exprs` ((name, tree) pairs), shared subtrees traced once.
        Runs under jit and shard_map alike: it only reads the block's
        arrays."""
        import jax.numpy as jnp

        seen: dict[str, tuple] = {}

        def both(a, b):
            return b if a is None else a if b is None else jnp.logical_and(a, b)

        def visit(e: S.Expr) -> tuple:
            c = cls.constant(e)
            if c is not None:
                return jnp.float32(c), None
            if isinstance(e, S.Column):
                return dev[e.name].astype(jnp.float32), dev[f"{e.name}__valid"]
            text = cls._text(e)
            if text in seen:
                return seen[text]
            if isinstance(e, S.UnaryOp):
                v, ok = visit(e.operand)
                out = (-v, ok)
            elif isinstance(e, S.Cast):
                v, ok = visit(e.expr)
                if e.type_name in _FLOAT_CASTS:
                    return v, ok  # f32 already: no node
                out = (jnp.trunc(v), ok)
            else:
                (a, ok_a), (b, ok_b) = visit(e.left), visit(e.right)
                v = {"+": jnp.add, "-": jnp.subtract, "*": jnp.multiply, "/": jnp.divide}[e.op](a, b)
                out = (v, both(ok_a, ok_b))
            seen[text] = out
            return out

        out = dict(dev)
        for name, tree in exprs:
            out[name], out[f"{name}__valid"] = visit(tree)
        return out


# ------------------------------------------------------------ dense agg state


@dataclass(frozen=True)
class AccLayout:
    """Row arithmetic of the packed device accumulator.

    Kernel stacking order (one f32 row per entry; built from the AggSpec
    list once per query):

      sums:  [sum/avg cols] [stddev/var cols: x]
      mins:  [min cols] [percentile cols (exact per-group vmin)]
      maxs:  [max cols] [percentile cols (exact per-group vmax)]
      cnts:  [count(col) cols]

    Validity rows mirror the same order (percentile dup rows are NaN-aware
    so sketch counts match the host path, which drops NaN). Accumulator
    rows: [0] count(*) mask hits | [1, 1+n_allk) per-agg counts | n_sum
    sums | n_sq sum(x) | n_sq M2 | n_mink mins | n_maxk maxs, and on the
    device, after them, one carry row for each count row (`n_counts`).

    stddev/var keep CENTERED second moments (M2 = sum((x - mean_g)^2), the
    per-block per-group mean), merged across blocks/devices with Chan's
    parallel update — raw f32 sum-of-squares cancels catastrophically when
    mean >> stddev; M2 magnitudes stay ~variance*n, so f32 holds. Finalize
    is M2/(n-1) (DataFusion's sample-variance semantics, ref
    query/mod.rs:212-276); host merges reconstruct raw sumsq = M2 +
    sum^2/n in f64.

    Device percentiles additionally keep one flat [G * DEVICE_NB] f32
    histogram per spec (additive, psum-able — see query/sketch.py layout).
    """

    sum_idx: tuple[int, ...]  # spec indices: sum/avg
    sq_idx: tuple[int, ...]  # stddev/var
    min_idx: tuple[int, ...]
    max_idx: tuple[int, ...]
    countcol_idx: tuple[int, ...]
    pct_idx: tuple[int, ...]  # percentile (approx_percentile_cont/median)
    distinct_idx: tuple[int, ...] = ()

    # ------------------------------------------------------------- section sizes

    @property
    def n_sum(self) -> int:
        return len(self.sum_idx)

    @property
    def n_sq(self) -> int:
        return len(self.sq_idx)

    @property
    def n_pct(self) -> int:
        return len(self.pct_idx)

    @property
    def n_sumk(self) -> int:  # acc sum-section rows: sums + sq(x) + sq(M2)
        return self.n_sum + 2 * self.n_sq

    @property
    def n_mink(self) -> int:  # kernel min rows: mins + pct vmin
        return len(self.min_idx) + self.n_pct

    @property
    def n_maxk(self) -> int:
        return len(self.max_idx) + self.n_pct

    @property
    def n_allk(self) -> int:  # validity / per-agg-count rows (kernel)
        return (
            self.n_sum + self.n_sq + self.n_mink + self.n_maxk
            + len(self.countcol_idx)
        )

    @property
    def n_rows(self) -> int:  # total packed accumulator rows
        return 1 + self.n_allk + self.n_sumk + self.n_mink + self.n_maxk

    @property
    def n_counts(self) -> int:
        """The count rows ([0] and the per-agg counts). On the device each
        has a carry row after the packed rows (`n_rows + i`): the f32 count
        row holds the rounded running total and the carry row what each
        add's rounding left out (TwoSum), so that a group's count stays
        exact past 2^24 rows, where f32 holds even numbers only.
        `_read_counts_exact` adds the two on the host in f64."""
        return 1 + self.n_allk

    # -------------------------------------------------- absolute acc row index

    def pac_row(self, si: int) -> int:
        """Per-agg non-null count row for spec `si` (pct specs use their
        min-dup validity row; their exact count comes from the histogram)."""
        base = self.n_sum + self.n_sq  # kernel sum rows (x only, no M2)
        if si in self.sum_idx:
            return 1 + self.sum_idx.index(si)
        if si in self.sq_idx:
            return 1 + self.n_sum + self.sq_idx.index(si)
        if si in self.min_idx:
            return 1 + base + self.min_idx.index(si)
        if si in self.pct_idx:
            return 1 + base + len(self.min_idx) + self.pct_idx.index(si)
        if si in self.max_idx:
            return 1 + base + self.n_mink + self.max_idx.index(si)
        return 1 + base + self.n_mink + self.n_maxk + self.countcol_idx.index(si)

    def sum_row(self, si: int) -> int:
        return 1 + self.n_allk + self.sum_idx.index(si)

    def sqx_row(self, si: int) -> int:  # stddev/var sum(x)
        return 1 + self.n_allk + self.n_sum + self.sq_idx.index(si)

    def sqm2_row(self, si: int) -> int:  # stddev/var centered M2
        return 1 + self.n_allk + self.n_sum + self.n_sq + self.sq_idx.index(si)

    def min_row(self, si: int) -> int:
        return 1 + self.n_allk + self.n_sumk + self.min_idx.index(si)

    def pct_min_row(self, si: int) -> int:
        return (
            1 + self.n_allk + self.n_sumk + len(self.min_idx)
            + self.pct_idx.index(si)
        )

    def max_row(self, si: int) -> int:
        return 1 + self.n_allk + self.n_sumk + self.n_mink + self.max_idx.index(si)

    def pct_max_row(self, si: int) -> int:
        return (
            1 + self.n_allk + self.n_sumk + self.n_mink + len(self.max_idx)
            + self.pct_idx.index(si)
        )

    @classmethod
    def from_specs(cls, specs: list[AggSpec]) -> "AccLayout":
        """Classify specs into packed sections; raises UnsupportedOnDevice
        for aggregates the device path cannot express."""
        sum_idx: list[int] = []
        sq_idx: list[int] = []
        min_idx: list[int] = []
        max_idx: list[int] = []
        countcol_idx: list[int] = []
        pct_idx: list[int] = []
        distinct_idx: list[int] = []
        for i, spec in enumerate(specs):
            if spec.func == "count_star":
                continue
            if not isinstance(spec.arg, S.Column):
                if spec.func not in ("sum", "avg", "stddev", "var", "min", "max", "count"):
                    raise UnsupportedOnDevice(
                        f"{spec.func} over expression: {S.expr_name(spec.arg)}"
                    )
                if AggExprCompiler.constant(spec.arg) is not None:
                    raise UnsupportedOnDevice(f"aggregate over a constant: {S.expr_name(spec.arg)}")
                AggExprCompiler.check(spec.arg)
            if spec.func in ("sum", "avg"):
                sum_idx.append(i)
            elif spec.func in ("stddev", "var"):
                sq_idx.append(i)
            elif spec.func == "min":
                min_idx.append(i)
            elif spec.func == "max":
                max_idx.append(i)
            elif spec.func == "count":
                countcol_idx.append(i)
            elif spec.func == "percentile":
                pct_idx.append(i)
            elif spec.func in ("count_distinct", "approx_distinct"):
                # both ride the flat [G * cap] segment_max machinery:
                # exact as presence bitmaps over the global dictionary,
                # approx as HLL register files (cap = HLL_M, value = rank)
                distinct_idx.append(i)
            else:
                raise UnsupportedOnDevice(f"aggregate {spec.func}")
        return cls(
            sum_idx=tuple(sum_idx),
            sq_idx=tuple(sq_idx),
            min_idx=tuple(min_idx),
            max_idx=tuple(max_idx),
            countcol_idx=tuple(countcol_idx),
            pct_idx=tuple(pct_idx),
            distinct_idx=tuple(distinct_idx),
        )


@dataclass
class PlanLayout:
    """Everything that shapes the device program for one capacity epoch."""

    key_specs: list[KeySpec]
    caps: tuple[int, ...]
    origins: tuple[int, ...]
    sum_cols: list[str]
    min_cols: list[str]
    max_cols: list[str]
    stacked_cols: list[str]
    distinct_cols: list[str] = dc_field(default_factory=list)
    distinct_caps: tuple[int, ...] = ()
    # True per distinct col when it is an approx_distinct HLL register
    # file (dremap = [2, N] idx/rank LUT; update value = rank, not 1)
    distinct_sketch: tuple[bool, ...] = ()
    sq_cols: list[str] = dc_field(default_factory=list)  # stddev/var inputs
    pct_cols: list[str] = dc_field(default_factory=list)  # percentile inputs
    cnt_cols: list[str] = dc_field(default_factory=list)  # count(col) inputs
    # aggregate arguments that are expressions: (name, tree) pairs. The col
    # lists above hold the names (the tree's canonical text), so the
    # program keys that hold those lists hold the expressions too
    exprs: tuple = ()
    # per group key: a time bin over a column off the block's origin
    # (`_off_origin_keys`); empty where the layout has no keys to fold
    off_origin: tuple[bool, ...] = ()


def _kernel_stacks(dev: dict, layout: "PlanLayout", local_rows: int):
    """Build fused_groupby_block inputs per the AccLayout kernel stacking.

    sums rows:  sum_cols | sq_cols (x — M2 rows are computed separately)
    mins rows:  min_cols | pct_cols (exact vmin)
    maxs rows:  max_cols | pct_cols (exact vmax)
    valid rows mirror that order then append cnt_cols; percentile dup rows
    get NaN-aware validity (host sketches drop NaN, so must the device
    count/min/max).

    Returns (sum_values, min_values, max_values, valid, n_sumk, n_mink,
    n_maxk) — all jnp arrays shaped [rows, local_rows].
    """
    import jax.numpy as jnp

    def col(n):
        return dev[n].astype(jnp.float32)

    def valid_of(n):
        return dev[f"{n}__valid"]

    def nn_valid(n):  # NaN-aware (percentile rows)
        return jnp.logical_and(valid_of(n), ~jnp.isnan(col(n)))

    def stack(rows, dtype=jnp.float32):
        if not rows:
            return jnp.zeros((0, local_rows), dtype)
        return jnp.stack(rows)

    sum_rows = [col(n) for n in layout.sum_cols + layout.sq_cols]
    min_rows = [col(n) for n in layout.min_cols + layout.pct_cols]
    max_rows = [col(n) for n in layout.max_cols + layout.pct_cols]
    valid_rows = (
        [valid_of(n) for n in layout.sum_cols + layout.sq_cols]
        + [valid_of(n) for n in layout.min_cols]
        + [nn_valid(n) for n in layout.pct_cols]
        + [valid_of(n) for n in layout.max_cols]
        + [nn_valid(n) for n in layout.pct_cols]
        + [valid_of(n) for n in layout.cnt_cols]
    )
    return (
        stack(sum_rows),
        stack(min_rows),
        stack(max_rows),
        stack(valid_rows, bool),
        len(sum_rows),
        len(min_rows),
        len(max_rows),
    )


def _block_m2(dev, layout, ids, mask, pac, sums, kernel_groups):
    """Per-group CENTERED second moments for each stddev/var column of one
    block: M2_g = sum over the block's rows of (x - mean_g)^2, with mean_g
    from this block's own sums/counts (two segment passes). Returns
    ([n_sq, G] m2, [n_sq, G] n, [n_sq, G] sum) — the latter two are views
    into the kernel outputs for the Chan merge."""
    import jax
    import jax.numpy as jnp

    n_sum = len(layout.sum_cols)
    m2_rows = []
    n_rows = []
    s_rows = []
    for qi, colname in enumerate(layout.sq_cols):
        with jax.named_scope("m2"):
            n_b = pac[n_sum + qi]
            s_b = sums[n_sum + qi]
            mean_g = s_b / jnp.maximum(n_b, 1.0)
            v = dev[colname].astype(jnp.float32)
            vm = jnp.logical_and(mask, dev[f"{colname}__valid"])
            centered = jnp.where(vm, v - mean_g[ids], 0.0)
            m2_rows.append(
                jax.ops.segment_sum(centered * centered, ids, num_segments=kernel_groups)
            )
        n_rows.append(n_b)
        s_rows.append(s_b)
    return m2_rows, n_rows, s_rows


def _psum_m2(m2_loc, m2_n, m2_s, sq_cols):
    """Combine per-device-shard centered moments into block totals over the
    mesh `data` axis: Chan's two-psum form — psum counts/sums first, then
    psum each shard's M2 re-centered against the block-total mean. Returns
    (m2_tot, n_tot, s_tot) lists."""
    import jax
    import jax.numpy as jnp

    m2_tot, n_tot, s_tot = [], [], []
    for qi in range(len(sq_cols)):
        with jax.named_scope("m2"):
            n_t = jax.lax.psum(m2_n[qi], "data")
            s_t = jax.lax.psum(m2_s[qi], "data")
            mean_t = s_t / jnp.maximum(n_t, 1.0)
            mean_l = m2_s[qi] / jnp.maximum(m2_n[qi], 1.0)
            d = mean_l - mean_t
            m2_tot.append(jax.lax.psum(m2_loc[qi] + m2_n[qi] * d * d, "data"))
        n_tot.append(n_t)
        s_tot.append(s_t)
    return m2_tot, n_tot, s_tot


def _chan_merge_m2(acc_n, acc_s, acc_m2, b_n, b_s, b_m2):
    """Chan's parallel variance update: combine (n, sum, M2) partials
    without forming raw sums of squares. Guarded for empty sides."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("m2"):
        tot = acc_n + b_n
        both = jnp.logical_and(acc_n > 0, b_n > 0)
        delta = acc_s / jnp.maximum(acc_n, 1.0) - b_s / jnp.maximum(b_n, 1.0)
        corr = jnp.where(
            both, delta * delta * acc_n * b_n / jnp.maximum(tot, 1.0), 0.0
        )
        return acc_m2 + b_m2 + corr


# Jitted programs cached process-wide: two identical queries (or two
# executors in one query lifetime) reuse the compiled XLA executable.
_PROGRAM_CACHE: dict[tuple, Callable] = {}  # jit-cache: executor

# Every (program-family, cache-key) ever built. A rebuild of an identical
# key is a recompile — impossible while the cache holds the entry, so the
# recompile counter reads 0 in steady state; nonzero means eviction or
# key churn. PROGRAM_BUILDS is the plain testable total (warm-query
# regression tests assert it does not move on a second run).
_PROGRAM_KEYS_BUILT: set = set()
PROGRAM_BUILDS = [0]


def _note_program_build(program: str, key: tuple, stats: dict | None = None) -> None:
    """Account one call-time program build for `program` under cache `key`:
    the tpu_jit_programs gauge, the per-query route_stats counters the
    stages.programs entry reads, and — when this exact key was already
    built once — the tpu_recompiles_total{program} family the dlint
    tripwire budgets."""
    PROGRAM_BUILDS[0] += 1
    DEVICE_JIT_PROGRAMS.inc()
    if stats is not None:
        stats["programs_built"] = stats.get("programs_built", 0) + 1
    try:
        marker = (program, hash(key))
    except TypeError:
        marker = (program, repr(key))
    if marker in _PROGRAM_KEYS_BUILT:
        DEVICE_RECOMPILES.labels(program).inc()
        if stats is not None:
            stats["recompiles"] = stats.get("recompiles", 0) + 1
    else:
        _PROGRAM_KEYS_BUILT.add(marker)


# The phases of `stats.stages.execute`, in the order a block meets them. Each
# brackets host code that stands where it stood; none adds a wait.
PHASES = (
    "encode",  # _encoded_block: hot-set look-up; on a miss encode + enccache + _transfer
    "prepare",  # LUTs, gdict remaps, _host_codes + np.unique, global key lanes, puts of arguments
    "dispatch",  # program look-up and the call into it up to its return (enqueue); the merge program's too
    "device_wait",  # the wait for pending compute that _timed_readback makes
    "readback",  # the np.asarray after it
    "partial",  # dense arrays -> partial / interim tables
    "merge",  # _merge_partials
    "finalize",  # finalize_from_interim / finalize_aggregate
)


class RouteStats(dict):
    """One query's route counters (the items: what `device_routes` and
    `stages.programs` publish) and, as attributes, its execute-phase clock
    (what `stages.execute` publishes): `ns[phase]` monotonic nanoseconds,
    `blocks` and `readbacks` counts, `expr_nodes` (arithmetic nodes traced a
    row for the aggregates over expressions), and two time stamps on the same clock,
    `first_dispatch_ns` (the first program call returned) and
    `last_readback_ns` (the last readback ended), 0 where none happened.

    One phase runs at a time: `enter` closes the running one and opens the
    next, so phases never overlap and their sum is at most the wall time.
    A nested bracket hands the clock back with the value `enter` returned."""

    __slots__ = ("ns", "blocks", "readbacks", "expr_nodes", "first_dispatch_ns", "last_readback_ns", "_phase", "_since")

    def __init__(self) -> None:
        super().__init__(
            device_warm=0,  # hot-set resident: zero bytes shipped
            device_cold=0,  # encoded + shipped this query
            # constant 0 since the link-adaptive routing went (PR 32): kept
            # because the benchmark's warm_block_share sums the four routes
            # and drops a response that lacks one (ROADMAP M8)
            cpu_adaptive=0,
            cpu_fallback=0,  # declared UnsupportedOnDevice (incl. budgets)
            h2d_bytes=0,
            d2h_bytes=0,
            # host-to-device transfers of small operands (LUTs, time
            # scalars, remaps) the dense block loop made: one a dtype a
            # dispatch group
            operand_puts=0,
            # the additive reduction's form in each block a device program
            # folded (kernels.fold_route of the rows a device holds, the
            # group count and the backend): the plain one-hot dot, the
            # factored one-hot product, the scatter-add. Ticked on the host
            # from shapes derived there, not read off the device: what ties
            # it to the traced program is tests/test_fold_routes.py (the
            # kernel asks the same function the same thing). A block the
            # Pallas twin folds (P_TPU_USE_PALLAS, off) counts as one-hot
            fold_onehot_blocks=0,
            fold_factored_blocks=0,
            fold_scatter_blocks=0,
            # blocks whose device program folded at least one min or max
            # (a percentile's exact bounds too), by the route that fold took:
            # `segment_min` / `segment_max`, a scatter, on every backend and
            # at every group count today (kernels.fused_groupby_block, scope
            # `fold/segment_minmax`); another route gets a key of its own
            fold_minmax_scatter_blocks=0,
            # blocks whose text bins (date_bin / date_trunc) a time column
            # that is off the block's origin (ops/device.py `origin_ms`):
            # binned inside the device program, in the column's own steps, or
            # by host code (the CPU engine for a block that was declared, a
            # bin its unit does not divide among them; numpy for a block-local
            # fold on host-compacted pair codes)
            timebin_offorigin_device_blocks=0,
            timebin_offorigin_host_blocks=0,
            # program-cache traffic (stages.programs reads these): builds
            # this query, cache hits this query, rebuilds of a key that
            # was already built once (0 in steady state)
            programs_built=0,
            programs_reused=0,
            recompiles=0,
            # a block-local GROUP BY's cross-block merge: ran on the device
            # (jit_executor_merge; the host merged the survivors only) or
            # took every partial row through the host; entries the device
            # sorted, and groups that survived its ranking. A host merge
            # says why under `merge_host_reason` (a string, so no sum
            # takes it): plan | aggregate | mesh | host_partials | budget |
            # survivors
            merge_device=0,
            merge_host=0,
            merge_entries=0,
            merge_survivors=0,
            # aggregate outputs whose argument is an arithmetic expression:
            # folded inside the device program / evaluated by the CPU engine
            # (a plan-time rejection, or a block it folded). A plan-time
            # rejection says why under `cpu_fallback_reason` (a string)
            expr_aggs_device=0,
            expr_aggs_host=0,
            # blocks whose encoding ops/device.py declined for a column
            encode_declined=0,
        )
        self.ns = dict.fromkeys(PHASES, 0)
        self.blocks = self.readbacks = self.expr_nodes = 0
        self.first_dispatch_ns = self.last_readback_ns = 0
        self._phase: str | None = None
        self._since = 0

    def enter(self, phase: str | None) -> str | None:
        now = _time.perf_counter_ns()
        prev = self._phase
        if prev is not None:
            self.ns[prev] += now - self._since
        self._phase, self._since = phase, now
        return prev

    def dispatched(self, then: str | None) -> None:
        """A program call has returned: on to `then`."""
        self.enter(then)
        if not self.first_dispatch_ns:
            self.first_dispatch_ns = self._since

    def read_back(self, then: str | None) -> None:
        """A readback has ended: on to `then`."""
        self.enter(then)
        self.readbacks += 1
        self.last_readback_ns = self._since


# the ONE declared d2h readback — waits out pending compute, then prices
# the copy's wire bytes into route_stats
# sync-boundary: every hot-path device->host read must flow through here
def _timed_readback(x, stats: dict | None = None, dtype=np.float64) -> np.ndarray:
    """Device->host readback with byte accounting. Pending compute is
    waited out BEFORE the copy, so a `RouteStats` clocks the wait under
    `device_wait` and the copy alone under `readback`. `stats` (a
    route_stats dict) gets the wire bytes added for EXPLAIN ANALYZE
    observability.

    `dtype` is the HOST-side representation (np.float64 for f32
    accumulators headed into host arithmetic; None keeps the device
    dtype — int32 indices, bool masks). Wire bytes are priced at the
    DEVICE dtype's width capped at 4: the device layer is f32/int32/bool
    end to end, so a float64 host target still crossed the link as f32."""
    if isinstance(x, np.ndarray):
        return np.asarray(x) if dtype is None else np.asarray(x, dtype)
    clock = stats if isinstance(stats, RouteStats) else None
    prev = clock.enter("device_wait") if clock is not None else None
    # wait for pending compute FIRST so the `readback` phase is the copy
    # alone. An async device failure surfaces here, at the declared
    # readback, and fails the query.
    x.block_until_ready()
    if clock is not None:
        clock.enter("readback")
    arr = np.asarray(x) if dtype is None else np.asarray(x, dtype)
    if stats is not None:
        stats["d2h_bytes"] += arr.size * min(x.dtype.itemsize, 4)
    if clock is not None:
        clock.read_back(prev)
    return arr


def _read_counts_exact(arr: np.ndarray, lay: "AccLayout") -> np.ndarray:
    """A dense accumulator (or columns gathered from one) as read back, f64:
    the count rows made exact by their carry rows (`AccLayout.n_counts`),
    the carry rows dropped, so every reader sees the packed rows alone (an
    array of the packed rows alone is returned as it is)."""
    if arr.shape[0] > lay.n_rows:
        arr[: lay.n_counts] += arr[lay.n_rows :]
    return arr[: lay.n_rows]


def _f32_order(x):
    """f32 -> int32 whose order is the floats' (-inf..+inf maps onto
    [-2139095040, 2139095040]; -0.0 and +0.0 meet at 0). NaN has no place in
    it: callers rank NaN apart, as `_run_topk_program` does."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits >= 0, bits, jnp.int32(-2147483648) - bits)


# the int32 scores below every real key, in nulls-last order (the classes of
# `_run_topk_program`): a NaN key, a group whose aggregate is NULL, no group
_SCORE_NAN = -2139095339
_SCORE_NULL = -2147483647
_SCORE_EMPTY = -2147483648
_SCORE_INF = 2139095040


def _merge_program(
    n_pad: int,
    run_max: int,
    n_rows: int,
    nkeys: int,
    kind: str,
    t_row: int,
    pac_row: int,
    desc: bool,
    k: int,
    k_out: int,
    stats: dict | None = None,
) -> Callable:
    """`jit_executor_merge`: the cross-block half of a block-local top-K, on
    the device. Arguments of the program: the kept blocks' `[R, G_block]` f32
    partials (outputs of `jit_executor_local`, untouched) stacked side by side
    in block order and padded to `[R, n_pad]`, and their int32 global key
    lanes `[nkeys, n_pad]` likewise. A group has at most `run_max` entries
    (one a block; a power of two, as `n_pad`, so that few shapes compile). It

    - `merge/sort`: sorts the entries by the key lanes (stable: a group's
      entries lie together, in block order), carrying along the entry's
      place and the one or two rows the ranking reads: a gather through the
      sorted places afterwards costs more than the sort (PERF.md, PR 28);
    - `merge/scan`: totals each run without a scatter: log2(run_max) rounds
      of "add the entry d places back if its keys are equal";
    - `merge/topk`: ranks the groups by the ordering aggregate `kind`
      ("sum" | "count" | "min" | "max", row `t_row`, non-null count row
      `pac_row`) WITH A MARGIN, and gathers the per-block partials of at most
      `k_out` survivors.

    The margin is what makes the selection a superset of the answer. The
    host's value of group g is S_g, the f64 sum of its blocks' f32 partials;
    here T_g is an f32 sum of the same numbers, so |T_g - S_g| <= e_g with
    e_g = (B + 2) * 2^-23 * A_g, A_g the sum of their absolute values (B - 1
    roundings of 2^-24 relative to A_g at most in any order of summation; the
    rest covers the roundings of A_g, T_g -/+ e_g themselves). Counts are
    integers that f32 adds exactly below 2^24 (e = 0 there), min and max are
    exact. tau is the k-th best lower bound; every group whose upper bound
    reaches tau survives. A group of the true top k has S at least the k-th
    best S, which is at least the k-th best lower bound, and its upper bound
    is at least its S: it survives. A group holding a non-finite partial is
    bounded by (-inf, +inf): it always survives and never raises tau.

    Returns (`[R, k_out * run_max]` f32: survivor-major, block order within
    a survivor, count 0 where a survivor has no entry there; `[nkeys + 1,
    k_out]` int32: the survivors' key lanes, then the TRUE number of
    survivors in every slot of the last row: more than `k_out` means the
    gather is not the whole superset and the host must not use it).
    Programs are cached process-wide like the executor's others; `stats`
    (a route_stats dict) counts the build or the reuse."""
    key = ("merge", n_pad, run_max, n_rows, nkeys, kind, t_row, pac_row, desc, k, k_out)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        if stats is not None:
            stats["programs_reused"] += 1
        return cached

    import jax
    import jax.numpy as jnp

    err = np.float32((run_max + 2) * 2.0**-23)
    i32 = jnp.int32

    def back(x, d: int, fill):  # out[i] = x[i - d]
        return jnp.concatenate([jnp.full((d,), fill, x.dtype), x[:-d]])

    def kth_best(score):
        """The k-th largest of `score` (int32), bit by bit from the top: 32
        counting passes, where a `top_k` over 2^24 entries is a full sort."""
        u = jax.lax.bitcast_convert_type(score, jnp.uint32) ^ jnp.uint32(0x80000000)

        def bit(i, found):
            cand = found | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
            return jnp.where(jnp.sum(u >= cand, dtype=i32) >= k, cand, found)

        found = jax.lax.fori_loop(0, 32, bit, jnp.uint32(0))
        return jax.lax.bitcast_convert_type(found ^ jnp.uint32(0x80000000), i32)

    def merge(v, ln):
        with jax.named_scope("merge"):
            # a slot no row fell into (padding to a power of two, a group the
            # WHERE emptied) is no group: it sorts last and joins no run
            ln = jnp.where(v[0] > 0, ln, _LANE_DEAD)
            with jax.named_scope("sort"):
                carried = [jax.lax.iota(i32, n_pad), v[t_row]]
                if kind != "count":
                    carried.append(v[pac_row])
                out = jax.lax.sort(
                    (*(ln[i] for i in range(nkeys)), *carried), num_keys=nkeys, is_stable=True
                )
                sk, perm, t = out[:nkeys], out[nkeys], out[nkeys + 1]
            with jax.named_scope("scan"):
                if kind == "sum":
                    lanes_ = [(t, jnp.add, 0.0), (jnp.abs(t), jnp.add, 0.0)]
                elif kind == "count":
                    lanes_ = [(t, jnp.add, 0.0)]
                else:
                    comb, ident = (
                        (jnp.minimum, 3.4e38) if kind == "min" else (jnp.maximum, -3.4e38)
                    )
                    bad = (~jnp.isfinite(t)).astype(jnp.float32)
                    lanes_ = [(t, comb, ident), (bad, jnp.maximum, 0.0)]
                if kind != "count":
                    lanes_.append((out[nkeys + 2], jnp.add, 0.0))
                d = 1
                while d < run_max:
                    same = None
                    for key in sk:
                        eq = key == back(key, d, _LANE_DEAD)
                        same = eq if same is None else same & eq
                    lanes_ = [
                        (op(x, jnp.where(same, back(x, d, ident), ident)), op, ident)
                        for x, op, ident in lanes_
                    ]
                    d *= 2
                last = None
                for key in sk:
                    ne = key != jnp.concatenate([key[1:], jnp.full((1,), _LANE_DEAD, i32)])
                    last = ne if last is None else last | ne
                group = last & (sk[0] != _LANE_DEAD)
                total = lanes_[0][0]
                if kind == "sum":
                    a = lanes_[1][0]
                    e = a * err
                    unsure = ~jnp.isfinite(a)
                elif kind == "count":
                    e = jnp.where(total < 16777216.0, 0.0, total * err)
                    unsure = jnp.zeros_like(group)
                else:
                    e = jnp.zeros_like(total)
                    unsure = lanes_[1][0] > 0
                notnull = lanes_[-1][0] > 0 if kind != "count" else jnp.ones_like(group)
            with jax.named_scope("topk"):
                val = total if desc else -total
                lo = jnp.where(unsure, i32(_SCORE_NAN), _f32_order(val - e))
                hi = jnp.where(unsure, i32(_SCORE_INF), _f32_order(val + e))
                lo = jnp.where(group, jnp.where(notnull, lo, i32(_SCORE_NULL)), i32(_SCORE_EMPTY))
                hi = jnp.where(group, jnp.where(notnull, hi, i32(_SCORE_NULL)), i32(_SCORE_EMPTY))
                surv = group & (hi >= kth_best(lo))
                # the j-th survivor ends its run where the running count first reads j
                upto = jnp.cumsum(surv.astype(i32))
                n_surv = upto[n_pad - 1]
                j = jnp.arange(1, k_out + 1, dtype=i32)
                pos = jnp.minimum(jnp.searchsorted(upto, j, side="left").astype(i32), n_pad - 1)
                # a survivor's run is at most run_max entries long
                idx = pos[:, None] - jnp.arange(run_max - 1, -1, -1, dtype=i32)[None, :]
                at = jnp.maximum(idx, 0)
                mine = (idx >= 0) & (j <= n_surv)[:, None]
                for key in sk:
                    mine = mine & (key[at] == key[pos][:, None])
                got = v[:, perm[at].reshape(-1)]
                got = jnp.concatenate([jnp.where(mine.reshape(-1), got[0], 0.0)[None, :], got[1:]])
                meta = jnp.stack([key[pos] for key in sk] + [jnp.full((k_out,), n_surv, i32)])
            return got, meta

    merge.__name__ = merge.__qualname__ = "executor_merge"  # XLA module jit_executor_merge
    # no donate_argnums: the outputs are a few KB, so no input's buffer could
    # serve one; `v` is read to the program's last gather
    program = jax.jit(merge)  # jit-cache: executor.merge
    _note_program_build("executor.merge", key, stats)
    _PROGRAM_CACHE[key] = program
    return program


class _KeptPartials:
    """One query's block-local partials while they stay on the device for
    `jit_executor_merge`: decided once, when block-local mode begins, from
    what the executor can see (the plan, the mesh, partials the host already
    made). `reason` says why the host merges instead, once it is known that
    it will; `blocks` then is empty and nothing more is kept."""

    def __init__(self, topk: tuple | None, nkeys: int, reason: str | None) -> None:
        self.si, self.desc, self.k = topk if topk is not None else (0, False, 0)
        self.reason = reason
        # (out_dev [R, G_block], lanes_dev [nkeys, G_block], keyinfo, composite_vals)
        self.blocks: list[tuple] = []
        self.entries = 0
        # per time-bin key: the absolute bin that lane value 0 stands for
        self.bin_origin: list[int | None] = [None] * nkeys

    @property
    def active(self) -> bool:
        return self.reason is None


# how many programs were built with a mesh (shard_map psum path) — the
# stable signal tests/bench use to assert distributed execution happened
# (cache-key positions are an implementation detail); the second counter
# tracks programs whose ACCUMULATOR sharded over the 2D `groups` axis
MESH_PROGRAMS_BUILT = 0
GROUP_SHARDED_PROGRAMS_BUILT = 0


# ------------------------------------------------------------------- the mesh
# The reference scales queries by fanning results across querier/ingestor
# nodes and merging JSON host-side (cluster/mod.rs:1785-1964,
# stream_schema_provider.rs:566-585). Here the same reduction is a psum tree
# over the chip mesh's `data` axis (parallel/mesh.py): row blocks shard
# across devices, each device folds its shard with the same fused kernel,
# and partials combine over ICI inside the jitted program.

_MESH_CACHE: dict[str, Any] = {}


def resolve_mesh(options: Options | None = None):
    """Device mesh for distributed aggregation, or None (single chip).

    `P_TPU_MESH`: "off" disables; "data:N" / "N" pins a 1D data axis;
    "NxM" (e.g. "4x2") builds the 2D (data x groups) layout where the
    group space ALSO shards — each device owns G/M accumulator buckets,
    so giant group spaces scale past one chip's HBM (parallel/mesh.py
    distributed_groupby_2d design). Empty auto-shards a 1D data axis over
    the largest power-of-two prefix of the visible devices.

    An explicit shape is honoured or refused: a malformed value, an axis
    that is not a power of two (row blocks and group capacities are, so
    another size could never divide them) or more devices than are visible
    raise ValueError, as does any failure to build the mesh — "single
    chip" is never a fallback for a mesh that was asked for.
    """
    shape = (options.mesh_shape if options is not None else "").strip().lower()
    if shape in _MESH_CACHE:
        return _MESH_CACHE[shape]
    import jax

    # first device contact of the engine: from here on this process owns
    # its chip(s) and /metrics may report their memory gauges
    devices = jax.local_devices()
    note_engine_devices(devices)
    mesh = None
    if shape != "off":
        from parseable_tpu.parallel.mesh import make_mesh, make_mesh_2d

        n_avail = jax.device_count()
        m = re.fullmatch(r"(?:data:)?(\d+)|(\d+)x(\d+)", shape)
        if shape and m is None:
            raise ValueError(
                f"P_TPU_MESH={shape!r} is malformed (want 'off', 'N', 'data:N' or 'NxM')"
            )
        if m is None:  # auto: every visible device, pow2-clamped
            n_data, n_groups = 1 << (n_avail.bit_length() - 1), 1
        elif m.group(1) is not None:
            n_data, n_groups = int(m.group(1)), 1
        else:
            n_data, n_groups = int(m.group(2)), int(m.group(3))
        for axis in (n_data, n_groups):
            if axis < 1 or axis & (axis - 1):
                raise ValueError(
                    f"P_TPU_MESH={shape!r}: axis size {axis} is not a power of two"
                )
        if n_data * n_groups > n_avail:
            raise ValueError(
                f"P_TPU_MESH={shape!r} needs {n_data * n_groups} devices, "
                f"{n_avail} visible"
            )
        if n_groups > 1:
            mesh = make_mesh_2d(n_data, n_groups)
        elif n_data > 1:
            mesh = make_mesh(n_data)
    _MESH_CACHE[shape] = mesh
    return mesh


def device_summary(options: Options | None = None) -> dict:
    """What the TPU engine of this process runs on, as JAX reports it, plus
    the mesh `resolve_mesh` settles on ("data:4", "data:4,groups:2", or
    None single-chip). The server logs it at start-up and serves it next to
    `queryEngine` in /api/v1/about, so "engine tpu" on a CPU backend is
    visible instead of silent. Initialises the backend on first use."""
    import jax

    mesh = resolve_mesh(options)
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": (
            ",".join(f"{axis}:{n}" for axis, n in mesh.shape.items())
            if mesh is not None
            else None
        ),
    }


def _group_shards_of(mesh, num_groups: int) -> int:
    """Over how many shards of the mesh's `groups` axis (absent on 1D
    meshes) a dense accumulator of `num_groups` splits: the axis' size
    where the group space divides by it, else 1 (the axis idles: inputs
    replicated over it, the fold identical in each shard)."""
    n = mesh.shape.get("groups", 1) if mesh is not None else 1
    return n if n > 1 and num_groups % n == 0 and num_groups >= n else 1


def _mesh_shardings(mesh):
    """(row-sharded, replicated) placement specs for a data-axis mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P("data")), NamedSharding(mesh, P())


def _expr_fingerprint(e: S.Expr | None) -> str:
    return repr(e)  # dataclass repr is structural and stable


# device-resident all-true masks per (block size, mesh): one allocation
# shared by every null-free column of every block, instead of a dispatch
# (and a block-sized buffer) per batch
_ONES_CACHE: dict[tuple, Any] = {}


def _device_ones(block_rows: int, mesh=None):
    import jax.numpy as jnp

    key = (block_rows, None if mesh is None else id(mesh))
    ones = _ONES_CACHE.get(key)
    if ones is None:
        ones = np.ones(block_rows, dtype=bool)
        if mesh is not None:
            import jax

            row_s, _ = _mesh_shardings(mesh)
            # cached once per (rows, mesh): not a data-sized ship —
            # link-priced: amortized across every block that reuses it
            ones = jax.device_put(ones, row_s)
        else:
            ones = jnp.asarray(ones)
        _ONES_CACHE[key] = ones
    return ones


class TpuQueryExecutor(QueryExecutor):
    """Device-accelerated aggregation; CPU fallback only where a plan or
    block is declared `UnsupportedOnDevice` (counted in route_stats)."""

    def __init__(self, plan: LogicalPlan, options: Options | None = None):
        super().__init__(plan)
        self.options = options or Options()
        self.mesh = resolve_mesh(self.options)
        # per-query route observability (EXPLAIN ANALYZE surfaces this —
        # VERDICT r3 #10): how every scanned block was dispatched, plus
        # the transfer bytes each direction actually cost, and the phase
        # clock stages.execute is read from
        self.route_stats = RouteStats()
        # query-aware prefetch (ops/prefetch.py): built lazily on the first
        # source-id'd block, once the scan has published its ordered stub
        # list; closed in execute()'s finally on every exit path
        self._prefetcher = None
        self._prefetch_tried = False

    # ------------------------------------------------------------------ main

    def execute(self, tables: Iterator[pa.Table]) -> pa.Table:
        try:
            if self.plan.is_aggregate:
                try:
                    return self._execute_aggregate_tpu(tables)
                except UnsupportedOnDevice as e:
                    # plan-time rejection: the iterator is untouched;
                    # materialize any hot stubs for the CPU engine, and
                    # count every table it answers
                    logger.info("TPU path unsupported (%s); falling back to CPU", e)
                    rs = self.route_stats
                    rs["cpu_fallback_reason"] = str(e)
                    n_expr = sum(
                        s.arg is not None and not isinstance(s.arg, (S.Column, S.Star))
                        for s in self.build_aggregator()[0].specs
                    )
                    if n_expr:
                        rs["expr_aggs_host"] = n_expr
                        DEVICE_EXPR_AGGREGATES.labels("host").inc(n_expr)

                    def counted() -> Iterator[pa.Table]:
                        for t in tables:
                            rs["cpu_fallback"] += 1
                            yield self._materialize(t)

                    return super()._execute_aggregate(counted())
            return self._execute_select_tpu(tables)
        finally:
            self._close_prefetcher()
            # once a query, from the finished clock: never once a block
            for phase, ns in self.route_stats.ns.items():
                if ns:
                    DEVICE_PHASE_SECONDS.labels(phase).inc(ns / 1e9)

    # ------------------------------------------------- select (mask on device)

    def _execute_select_tpu(self, tables: Iterator[pa.Table]) -> pa.Table:
        """Plain SELECT: compute the WHERE mask on device, filter host-side.

        Wrapped per-table so unsupported predicates degrade to CPU eval."""
        sel = self.plan.select

        from parseable_tpu import DEFAULT_TIMESTAMP_KEY
        from parseable_tpu.query.planner import referenced_columns

        # the device only evaluates the WHERE mask here, so encode (and
        # cache) just the predicate's columns, not the whole projection
        mask_needed = referenced_columns(sel.where) | {DEFAULT_TIMESTAMP_KEY}

        def filtered() -> Iterator[pa.Table]:
            # bounds filtering happens once, in the inner executor's loop
            from parseable_tpu.query.executor import _arr, evaluate

            compiler = PredicateCompiler()
            for table in tables:  # device-hot: per-block filter dispatch
                if sel.where is None:
                    yield table
                    continue
                try:
                    enc, dev = self._encoded_block(table, mask_needed, set())
                    import jax.numpy as jnp

                    luts = [jnp.asarray(l) for l in compiler.collect_luts(sel.where, enc)]
                    mask = compiler.trace(sel.where, enc, dev, luts)
                    # bool-mask readback rides the declared, priced
                    # _timed_readback boundary (host-sync discipline)
                    mask_np = _timed_readback(mask, self.route_stats, dtype=None)[
                        : enc.num_rows
                    ]
                    # materialize defensively: projection needs row values,
                    # which a hot stub doesn't carry (selects don't receive
                    # stubs today — session gates use_hot_stubs on
                    # aggregates — but this branch must not depend on that)
                    yield self._materialize(table).filter(pa.array(mask_np))
                except UnsupportedOnDevice:
                    # evaluate against the captured (un-stripped) WHERE
                    self.route_stats["cpu_fallback"] += 1
                    mask = _arr(evaluate(sel.where, table), table)
                    yield table.filter(mask)

        # reuse CPU projection/order/limit over pre-filtered tables
        inner = QueryExecutor(self.plan)
        inner.plan.select = _strip_where(sel)
        try:
            return inner._execute_select(filtered())
        finally:
            inner.plan.select = sel

    # ----------------------------------------------------------- block cache

    # set by the session: re-reads a source when a stubbed block got evicted
    # between the provider's hot check and execution
    source_loader: Callable[[bytes], pa.Table] | None = None
    # set by the session: the StreamScan whose `prefetchable` list (ordered
    # enccache-servable stub sources) drives the query-aware prefetcher
    prefetch_scan = None

    def _ensure_prefetcher(self, needed: set[str] | None, dict_cols: set[str]) -> None:
        """Build the prefetcher once the scan has published its ordered
        stub list (first source-id'd block => the list is complete)."""
        if self._prefetch_tried or self._prefetcher is not None:
            return
        self._prefetch_tried = True
        scan = self.prefetch_scan
        sources = list(getattr(scan, "prefetchable", ()) or ())
        depth = getattr(self.options, "tpu_prefetch_depth", 2)
        if len(sources) < 2 or depth <= 0:
            return
        from parseable_tpu.ops.prefetch import ScanPrefetcher

        def ship(source_id: bytes) -> tuple | None:
            return self._prefetch_ship(source_id, needed, dict_cols)

        self._prefetcher = ScanPrefetcher(sources, ship, depth=depth)

    def _prefetch_ship(
        self, source_id: bytes, needed: set[str] | None, dict_cols: set[str]
    ) -> tuple | None:
        """Worker-thread half of the prefetcher: enccache -> device -> hot
        set. Returns the hot key on a completed ship, None when skipped."""
        from parseable_tpu.ops.enccache import get_enccache

        hotset = get_hotset()
        key = hot_key(source_id, needed, dict_cols)
        if hotset.contains(key):
            return None
        enccache = get_enccache(self.options)
        if enccache is None:
            return None
        enc = enccache.get(source_id, needed, dict_cols)
        if enc is None:
            return None
        est = sum(
            c.values.nbytes + (0 if c.all_valid else c.valid.nbytes)
            for c in enc.columns.values()
        )
        if est > hotset.budget:
            return None  # could never be admitted; don't ship it
        dev, nbytes = _transfer(enc, self.mesh)
        self.route_stats["h2d_bytes"] += nbytes
        _strip_host_values(enc)
        hotset.put(key, HotEntry(dev=dev, meta=enc, nbytes=nbytes))
        # admission control may have refused the put (probation empty,
        # candidate colder than every protected entry): only report a
        # completed ship when the entry is actually resident
        return key if hotset.contains(key) else None

    def _close_prefetcher(self) -> None:
        pf, self._prefetcher = self._prefetcher, None
        if pf is None:
            return
        counters = pf.close()
        self.route_stats.update(counters)

    def _materialize(self, table: pa.Table) -> pa.Table:
        """Real rows for a table (loads the source when it's a hot stub)."""
        if not is_stub(table):
            return table
        source = (table.schema.metadata or {})[SOURCE_ID_META]
        if self.source_loader is None:
            raise UnsupportedOnDevice("stub block without a source loader")
        return self.source_loader(source)

    def _encoded_block(
        self, table: pa.Table, needed: set[str] | None, dict_cols: set[str]
    ) -> tuple[EncodedBatch, dict]:
        """Encode a table (or fetch its device-resident encoding), on the
        `encode` phase's clock.

        Resolution order per source-id'd block: device hot set (zero
        transfer) -> encoded-block disk cache (zero parquet decode /
        dictionary encode; ops/enccache.py) -> live encode, which
        writes-behind into the disk cache. Staging data (no source id)
        always encodes live.
        """
        prev = self.route_stats.enter("encode")
        try:
            return self._resolve_block(table, needed, dict_cols)
        finally:
            self.route_stats.enter(prev)

    def _resolve_block(
        self, table: pa.Table, needed: set[str] | None, dict_cols: set[str]
    ) -> tuple[EncodedBatch, dict]:
        hotset = get_hotset()
        meta = table.schema.metadata or {}
        source = meta.get(SOURCE_ID_META)
        key = None
        enccache = None
        if source is not None:
            key = hot_key(source, needed, dict_cols)
            # kick the lookahead BEFORE resolving this block: while it
            # encodes/ships/aggregates, the next blocks ship in background
            self._ensure_prefetcher(needed, dict_cols)
            pf = self._prefetcher
            if pf is not None:
                pf.on_block(source)
            # fetch untouched, then let the PREFETCHER decide (atomically,
            # under its condvar) whether this hit was its own ship's one
            # planned consumption — only a non-prefetch hit is proven reuse
            # and touches. The old peek-then-get(touch=...) pair had a race:
            # a ship completing between the two calls promoted prefetch
            # cargo into the protected segment (psan seed candidate).
            entry = hotset.get(key, touch=False)
            if entry is None and pf is not None and pf.claim(source):
                # the prefetcher was mid-ship on exactly this block: it
                # finished — re-check instead of shipping a second copy
                entry = hotset.get(key, touch=False)
            if entry is not None:
                if pf is None or not pf.consumed(key):
                    hotset.touch(key)
                self.route_stats["device_warm"] += 1
                return entry.meta, entry.dev
            from parseable_tpu.ops.enccache import get_enccache

            enccache = get_enccache(self.options)
            if enccache is not None:
                enc = enccache.get(source, needed, dict_cols)
                if enc is not None:
                    dev, nbytes = _transfer(enc, self.mesh)
                    self.route_stats["device_cold"] += 1
                    self.route_stats["h2d_bytes"] += nbytes
                    _strip_host_values(enc)
                    hotset.put(key, HotEntry(dev=dev, meta=enc, nbytes=nbytes))
                    return enc, dev
        table = self._materialize(table)
        enc = encode_table(table, needed, dict_columns=dict_cols)
        if enc is None:
            self.route_stats["encode_declined"] += 1
            raise UnsupportedOnDevice("unencodable column in batch")
        dev, nbytes = _transfer(enc, self.mesh)
        self.route_stats["device_cold"] += 1
        self.route_stats["h2d_bytes"] += nbytes
        if key is not None:
            if enccache is not None:
                # snapshot-by-reference then persist off the query path
                enccache.put_async(source, enc)
            _strip_host_values(enc)
            hotset.put(key, HotEntry(dev=dev, meta=enc, nbytes=nbytes))
        return enc, dev

    # -------------------------------------------------------------- aggregate

    def _execute_aggregate_tpu(self, tables: Iterator[pa.Table]) -> pa.Table:
        import time as _t

        import jax.numpy as jnp

        sel = self.plan.select
        rs = self.route_stats
        agg, rewritten, group_names = self.build_aggregator()
        specs = agg.specs

        key_specs = [classify_group_expr(g) for g in sel.group_by]
        lay = AccLayout.from_specs(specs)
        sum_idx = list(lay.sum_idx)
        sq_idx = list(lay.sq_idx)
        min_idx = list(lay.min_idx)
        max_idx = list(lay.max_idx)
        countcol_idx = list(lay.countcol_idx)
        pct_idx = list(lay.pct_idx)
        distinct_idx = list(lay.distinct_idx)
        stacked_idx = sum_idx + sq_idx + min_idx + max_idx + countcol_idx

        def arg_name(i: int) -> str:
            """The value row spec `i` reads: its column, or the name its
            expression is traced under (AggExprCompiler)."""
            arg = specs[i].arg
            return arg.name if isinstance(arg, S.Column) else AggExprCompiler.name(arg)

        expr_idx = [i for i in stacked_idx if not isinstance(specs[i].arg, S.Column)]
        exprs = tuple({arg_name(i): specs[i].arg for i in expr_idx}.items())

        # count(distinct y): y dict-encodes like a group key; per block a
        # segment_max ORs presence bits into a [G, Vcap] device bitmap
        # (masked_distinct_bitmap design, ops/kernels.py). Exact — flush
        # decodes present codes back to values and merges them into the
        # same sets CPU-fallback blocks fill, so mixed paths stay correct.
        # approx_distinct(y) instead maxes HLL RANKS into a fixed [G,
        # HLL_M] register file (ops/hll_sketch.py): per-block dictionary
        # values hash once on host into (idx, rank) LUTs, no global
        # dictionary ever materializes, and high-cardinality distinct
        # stays on device end-to-end (VERDICT r4 #5).
        from parseable_tpu.ops.hll_sketch import HLL_M

        dkeys = [
            KeySpec("dict", specs[i].arg.name, specs[i].arg, gdict=GlobalDict())
            for i in distinct_idx
        ]
        dk_sketch = [specs[i].func == "approx_distinct" for i in distinct_idx]
        for dk, sk in zip(dkeys, dk_sketch):
            if sk:
                dk.capacity = HLL_M

        compiler = PredicateCompiler()
        dict_cols = {ks.column for ks in key_specs if ks.kind == "dict"}
        dict_cols |= {dk.column for dk in dkeys}

        acc = None  # device-resident packed accumulator (R, G) f32
        dacc: list = []  # per-distinct [G * Vcap] f32 presence bitmaps
        pacc: list = []  # per-percentile [G * DEVICE_NB] f32 histograms
        acc_groups = 0

        def new_acc(num_groups: int):
            """Packed accumulator rows (AccLayout): count | per-agg counts |
            sums (incl. stddev x and x^2) | mins (incl. pct vmin) | maxs."""
            parts = [
                np.zeros((1 + lay.n_allk + lay.n_sumk, num_groups), np.float32),
                np.full((lay.n_mink, num_groups), np.float32(3.4e38)),
                np.full((lay.n_maxk, num_groups), np.float32(-3.4e38)),
                np.zeros((lay.n_counts, num_groups), np.float32),  # the counts' carry rows
            ]
            host = np.concatenate(parts, axis=0)
            if self.mesh is not None:
                import jax

                _, rep_s = _mesh_shardings(self.mesh)
                # priced: the zeroed accumulator ships once per query
                self.route_stats["h2d_bytes"] += int(host.nbytes)
                DEVICE_BYTES_TO_DEVICE.labels("acc").inc(host.nbytes)
                return jax.device_put(host, rep_s)
            return jnp.asarray(host)

        def new_flat(size: int):
            host = np.zeros(size, np.float32)
            if self.mesh is not None:
                import jax

                _, rep_s = _mesh_shardings(self.mesh)
                # priced: once-per-query sparse accumulator ship
                self.route_stats["h2d_bytes"] += int(host.nbytes)
                DEVICE_BYTES_TO_DEVICE.labels("acc").inc(host.nbytes)
                return jax.device_put(host, rep_s)
            return jnp.asarray(host)

        def flush(acc_dev, num_groups: int) -> None:
            """ONE device->host readback per accumulator, folded into the
            sparse agg (distinct presence bitmaps and percentile histograms
            decode alongside)."""
            arr = _read_counts_exact(_timed_readback(acc_dev, self.route_stats), lay)
            dists = [
                (
                    si,
                    dk,
                    _timed_readback(d, self.route_stats, dtype=None).reshape(
                        num_groups, dk.capacity
                    ),
                )
                for si, dk, d in zip(distinct_idx, dkeys, dacc)
            ]
            pcts = [
                (si, self._read_hist(h, num_groups))
                for si, h in zip(pct_idx, pacc)
            ]
            prev = rs.enter("partial")
            self._flush_state(arr, key_specs, agg, specs, lay, dists, pcts)
            rs.enter(prev)

        # Coalesce scan tables into larger device blocks: dispatch latency is
        # the budget, so fewer/bigger blocks win (Options.device_block_rows).
        # Tables carrying a source id stay un-coalesced so their encodings
        # are reusable across queries via the hot set.
        target_rows = max(1 << 16, self.options.device_block_rows)

        max_block_rows = MAX_BLOCK_ROWS

        def blocks(src: Iterator[pa.Table]) -> Iterator[pa.Table]:
            buf: list[pa.Table] = []
            rows = 0
            for t in src:
                if t.num_rows > max_block_rows:
                    # split oversized tables (giant parquet/arrow inputs);
                    # slices lose hot-set identity (a partial block must
                    # not serve future full-block reads)
                    if buf:
                        yield _concat_tables(buf)
                        buf, rows = [], 0
                    bare = t.replace_schema_metadata(None)
                    for off in range(0, t.num_rows, max_block_rows):
                        yield bare.slice(off, max_block_rows)
                    continue
                if (t.schema.metadata or {}).get(SOURCE_ID_META) is not None:
                    yield t
                    continue
                buf.append(t)
                rows += t.num_rows
                if rows >= target_rows:
                    yield _concat_tables(buf)
                    buf, rows = [], 0
            if buf:
                yield _concat_tables(buf)

        # Blocks with identical shape signatures batch into one dispatch
        # group of up to GROUP_N: one program call of that many unrolled
        # folds, and one transfer a dtype for all their small operands
        # (`_pack_operands`), where a put costs the host a quarter of a
        # millisecond whatever it carries (0.57 ms replicated over four
        # chips). The trade of eight against compile time and against the
        # first program's start is not measured.
        GROUP_N = 8
        pending: list[tuple] = []  # (table, enc, dev, operands, row_mask)
        pending_sig: tuple | None = None
        if self.mesh is not None:
            import jax

            rep_s = _mesh_shardings(self.mesh)[1]

            def put_rep(a: np.ndarray):
                # priced: these ride outside _transfer's packed payload, so
                # the byte accounting must see them here
                rs["h2d_bytes"] += a.nbytes
                DEVICE_BYTES_TO_DEVICE.labels("lut").inc(a.nbytes)
                return jax.device_put(a, rep_s)
        else:
            put_rep = jnp.asarray

        def fold_pending_on_cpu() -> None:
            """The plan layout was declared UnsupportedOnDevice at program
            build: aggregate the buffered blocks' source tables on the CPU."""
            self.route_stats["cpu_fallback"] += len(pending)
            if any(_off_origin_keys(pending[0][1], key_specs)):
                self._note_offorigin_bins("host", len(pending))
            for x in pending:
                t = self._bounds_filter(self._materialize(x[0]))
                agg.update(t, self._where_mask(t))
            pending.clear()

        def dispatch_pending() -> None:
            nonlocal acc, dacc, pacc
            if not pending:
                return
            enc0 = pending[0][1]
            layout = PlanLayout(
                key_specs=key_specs,
                caps=tuple(ks.capacity for ks in key_specs),
                origins=tuple(ks.origin_rel or 0 for ks in key_specs),
                sum_cols=[arg_name(i) for i in sum_idx],
                min_cols=[arg_name(i) for i in min_idx],
                max_cols=[arg_name(i) for i in max_idx],
                stacked_cols=[arg_name(i) for i in stacked_idx],
                distinct_cols=[dk.column for dk in dkeys],
                distinct_caps=tuple(dk.capacity for dk in dkeys),
                distinct_sketch=tuple(dk_sketch),
                sq_cols=[arg_name(i) for i in sq_idx],
                pct_cols=[arg_name(i) for i in pct_idx],
                cnt_cols=[arg_name(i) for i in countcol_idx],
                exprs=exprs,
                off_origin=_off_origin_keys(enc0, key_specs),
            )
            prev = rs.enter("dispatch")
            try:
                program = self._get_program(
                    enc0,
                    layout,
                    acc_groups,
                    pending_sig[1],
                    n_blocks=len(pending),
                    dev_keys=tuple(sorted(pending[0][2].keys())),
                )
                # only a group that has its program ships its operands
                rs.enter("prepare")
                packed = tuple(put_rep(b) for b in _pack_operands([x[3] for x in pending]))
                rs.enter("dispatch")
                acc, dacc_out, pacc_out = program(
                    acc,
                    tuple(dacc),
                    tuple(pacc),
                    tuple(x[2] for x in pending),
                    packed,
                    tuple(x[4] for x in pending),
                )
                rs.dispatched(prev)
                rs["operand_puts"] += len(packed)  # of a group that went
                self._note_fold_route(
                    enc0.block_rows, acc_groups // _group_shards_of(self.mesh, acc_groups), len(pending), lay
                )
                if any(layout.off_origin):
                    self._note_offorigin_bins("device", len(pending))
                dacc = list(dacc_out)
                pacc = list(pacc_out)
                pending.clear()
            except UnsupportedOnDevice as e:
                rs.enter(None)  # the CPU's fold is no phase of the device path
                logger.debug("pending blocks on CPU (%s)", e)
                fold_pending_on_cpu()
                rs.enter(prev)

        # block-local (two-phase) state: partial-format tables awaiting the
        # vectorized host merge (high-cardinality group spaces)
        local_mode = False
        partials: list[pa.Table] = []
        # set when local mode begins: what is kept on the device for the
        # device merge, or why nothing is (_KeptPartials)
        keep: _KeptPartials | None = None
        local_layout = PlanLayout(
            key_specs=key_specs,
            caps=(),
            origins=(),
            sum_cols=[arg_name(i) for i in sum_idx],
            min_cols=[arg_name(i) for i in min_idx],
            max_cols=[arg_name(i) for i in max_idx],
            stacked_cols=[arg_name(i) for i in stacked_idx],
            sq_cols=[arg_name(i) for i in sq_idx],
            cnt_cols=[arg_name(i) for i in countcol_idx],
            exprs=exprs,
        )

        from parseable_tpu.query.partials import (
            partial_from_block,
            specs_partializable,
        )

        partializable = bool(sel.group_by) and specs_partializable(specs)

        def cpu_block(table: pa.Table) -> None:
            """Aggregate one block on the host, into partials when the
            specs allow (vectorized; a 1M-group block must not hit the
            per-group Python aggregator)."""
            if keep is not None:
                # a partial made on the host: the device cannot rank it
                self._spill_kept(keep, partials, specs, lay, "host_partials")
            t = self._bounds_filter(self._materialize(table))
            mask = self._where_mask(t)
            if partializable:
                if mask is not None:
                    t = t.filter(mask)
                pt = partial_from_block(t, sel.group_by, specs)
                if pt is not None:
                    partials.append(pt)
            else:
                agg.update(t, mask)

        t_start = _t.monotonic()
        # set when the scan discovers device percentiles/distincts can't fit
        # this query's group space: stop paying encode+transfer per block
        # just to rediscover it — the rest of the scan is host-side
        force_cpu_rest = False
        with TRACER.span("execute.blocks") as sp_blocks:
            for table in blocks(tables):  # device-hot: per-block agg dispatch
                rs.blocks += 1
                self._check_deadline()
                if force_cpu_rest:
                    self.route_stats["cpu_fallback"] += 1
                    cpu_block(table)
                    continue
                bins_off_origin = False  # known once the block is encoded
                try:
                    enc, dev = self._encoded_block(table, self.plan.needed_columns, dict_cols)
                    rs.enter("prepare")
                    off_origin = _off_origin_keys(enc, key_specs)
                    bins_off_origin = any(off_origin)
                    for i in stacked_idx + pct_idx:
                        if i in expr_idx:
                            AggExprCompiler.check(specs[i].arg, enc)
                            continue
                        col = enc.columns.get(specs[i].arg.name)
                        if col is None:
                            raise UnsupportedOnDevice(f"aggregate column {specs[i].arg.name} missing")
                        if col.kind in ("dict", "time") and i not in countcol_idx:
                            raise UnsupportedOnDevice(f"numeric aggregate over {col.kind} column")
                    luts = compiler.collect_luts(sel.where, enc)
                    if local_mode:
                        self._local_block(
                            partials, enc, dev, luts, key_specs, specs, local_layout, lay, keep,
                        )
                        continue
                    remaps = [
                        ks.gdict.absorb(enc.columns[ks.column].dictionary)
                        if ks.kind == "dict" and ks.column in enc.columns
                        else None
                        for ks in key_specs
                    ]
                    if any(r is None and ks.kind == "dict" for r, ks in zip(remaps, key_specs)):
                        raise UnsupportedOnDevice("group key column missing from batch")
                    dremaps_np = []
                    for dk, sk in zip(dkeys, dk_sketch):
                        col = enc.columns.get(dk.column)
                        if col is None or col.kind != "dict":
                            raise UnsupportedOnDevice(f"distinct column {dk.column} not dict-encoded")
                        if sk:
                            # HLL (idx, rank) LUT over THIS block's dictionary:
                            # no global dictionary grows, cached per batch
                            dremaps_np.append(self._hll_lut(enc, col))
                        else:
                            dremaps_np.append(dk.gdict.absorb(col.dictionary))

                    layouts = [self._required_layout(ks, enc) for ks in key_specs]
                    caps = tuple(c for _, c in layouts)
                    origins = tuple(o for o, _ in layouts)
                    dlayouts = [
                        (0, HLL_M) if sk else self._required_layout(dk, enc)
                        for dk, sk in zip(dkeys, dk_sketch)
                    ]
                    dcaps = tuple(c for _, c in dlayouts)
                    new_groups = 1
                    for c in caps:
                        new_groups *= c
                    new_groups = max(new_groups, 1)
                    # presence bitmaps are device-resident [G, Vcap] f32 each —
                    # bound the footprint, else fall back (exact) to the CPU.
                    # HLL register files have a FIXED cap (HLL_M) so they get a
                    # larger budget (1<<27 slots = 512 MB f32 -> G up to 32k):
                    # group count, not value cardinality, is their only axis
                    if any(
                        new_groups * c > ((1 << 27) if sk else (1 << 24))
                        for c, sk in zip(dcaps, dk_sketch)
                    ):
                        # caps only grow (gdict.absorb is monotonic; the group
                        # space only widens): no later block can fit either,
                        # so stop paying encode+transfer
                        force_cpu_rest = True
                        raise UnsupportedOnDevice(
                            "distinct state exceeds device budget (G*V too large)"
                        )
                    # percentile histograms are [G, DEVICE_NB] f32 each; past
                    # the footprint budget the whole scan aggregates host-side
                    # (exact sketches) rather than thrashing device HBM
                    if pct_idx and new_groups * DEVICE_NB > PCT_MAX_ELEMS:
                        force_cpu_rest = True
                        raise UnsupportedOnDevice(
                            "percentile histogram exceeds device budget (G too large)"
                        )
                    if new_groups > DENSE_G_MAX:
                        # the dense global group space outgrew the device budget:
                        # switch to block-local two-phase aggregation for the
                        # rest of the scan (exact; no capacity-epoch churn)
                        if dkeys or pct_idx:
                            force_cpu_rest = True
                            raise UnsupportedOnDevice(
                                "high-cardinality group space with sketch/set state"
                            )
                        dispatch_pending()
                        if acc is not None:
                            pt = self._dense_to_partial(
                                acc, acc_groups, key_specs, specs, lay,
                            )
                            if pt is not None:
                                partials.append(pt)
                            acc = None
                            dacc = []
                        local_mode = True
                        keep = self._plan_device_merge(
                            rewritten, specs, len(key_specs), bool(partials or agg.groups)
                        )
                        logger.info(
                            "group space %d exceeds dense budget; block-local two-phase mode, merge on the %s",
                            new_groups,
                            "device" if keep.active else f"host ({keep.reason})",
                        )
                        self._local_block(
                            partials, enc, dev, luts, key_specs, specs, local_layout, lay, keep,
                        )
                        continue
                    current = tuple((ks.origin_rel or 0, ks.capacity) for ks in key_specs)
                    dcurrent = tuple(dk.capacity for dk in dkeys)
                    if acc is None or tuple(zip(origins, caps)) != current or dcaps != dcurrent:
                        dispatch_pending()  # under the old epoch's layout
                        if acc is not None:
                            if distinct_idx or pct_idx:
                                # distinct bitmaps / percentile histograms
                                # decode through the sparse agg
                                flush(acc, acc_groups)
                            else:
                                # vectorized epoch flush: no per-group Python
                                pt = self._dense_to_partial(
                                    acc, acc_groups, key_specs, specs, lay,
                                )
                                if pt is not None:
                                    partials.append(pt)
                        for ks, (o, c) in zip(key_specs, layouts):
                            ks.capacity = c
                            ks.origin_rel = o if ks.kind == "timebin" else None
                        for dk, c in zip(dkeys, dcaps):
                            dk.capacity = c
                        acc_groups = new_groups
                        acc = new_acc(acc_groups)
                        dacc = [new_flat(acc_groups * c) for c in dcaps]
                        pacc = [new_flat(acc_groups * DEVICE_NB) for _ in pct_idx]

                    # per-block time scalars (bin shift/offset + bounds) append
                    # after the predicate LUTs; the fold consumes them from the
                    # tail so one compiled program serves every block origin
                    luts = luts + self._time_args(
                        enc,
                        key_specs,
                        tuple(ks.origin_rel or 0 for ks in key_specs),
                        self._bounds_ms(),
                    )
                    kinds = tuple(sorted((n, c.kind) for n, c in enc.columns.items()))
                    # the small operands stay on the host until their group
                    # goes: dispatch_pending packs and ships them together
                    operands = (
                        tuple(luts),
                        tuple(r for r in remaps if r is not None),
                        tuple(dremaps_np),
                    )
                    sig = (
                        (enc.block_rows, kinds, "__rowmask" in dev, off_origin),
                        tuple(_operand_sig(part) for part in operands),
                    )
                    if pending and sig != pending_sig:
                        dispatch_pending()
                    pending_sig = sig
                    row_mask = dev.get("__rowmask", dev["__ones"])
                    pending.append((table, enc, dev, operands, row_mask))
                    if len(pending) >= GROUP_N:
                        dispatch_pending()
                except UnsupportedOnDevice as e:
                    rs.enter(None)  # the CPU's fold is no phase of the device path
                    logger.debug("batch on CPU (%s)", e)
                    self.route_stats["cpu_fallback"] += 1
                    if bins_off_origin:
                        self._note_offorigin_bins("host", 1)
                    if keep is not None:
                        self._spill_kept(keep, partials, specs, lay, "host_partials")
                    t = self._bounds_filter(self._materialize(table))
                    agg.update(t, self._where_mask(t))
                finally:
                    # the clock stands while the scan produces the next block
                    rs.enter(None)

            dispatch_pending()
            sp_blocks["rows"] = rs.blocks
            sp_blocks["expr_aggs"] = len(expr_idx)
            for k in ("fold_minmax_scatter_blocks", "timebin_offorigin_device_blocks", "timebin_offorigin_host_blocks"):
                sp_blocks[k] = rs[k]
        if expr_idx:
            # where the expressions were evaluated: in the device program for
            # the blocks it folded, by the CPU engine for the blocks that did
            on_cpu = rs["cpu_fallback"]
            if rs.blocks > on_cpu:
                rs["expr_aggs_device"] = len(expr_idx)
                rs.expr_nodes = AggExprCompiler.nodes(exprs)
                DEVICE_EXPR_AGGREGATES.labels("device").inc(len(expr_idx))
            if on_cpu:
                rs["expr_aggs_host"] = len(expr_idx)
                DEVICE_EXPR_AGGREGATES.labels("host").inc(len(expr_idx))

        def finish(interim: pa.Table | None) -> pa.Table:
            """Every exit: the group-by's host wall time up to here (scan,
            dispatch, device waits, readbacks and host merge, all of it),
            then the projection / HAVING / ORDER BY over the interim table,
            or over the sparse aggregator where there is none."""
            DEVICE_EXECUTE_TIME.labels("groupby").observe(_t.monotonic() - t_start)
            with TRACER.span("execute.finalize") as sp:
                prev = rs.enter("finalize")
                try:
                    if interim is None:
                        out = self.finalize_aggregate(agg, rewritten, group_names)
                    else:
                        out = self.finalize_from_interim(interim, rewritten)
                finally:
                    rs.enter(prev)
                sp["rows"] = out.num_rows
                return out

        kept = keep is not None and bool(keep.blocks)
        if kept or partials or (local_mode and (acc is not None or agg.groups)):
            # two-phase finalize: dense epoch + device block partials +
            # CPU-fallback groups all merge through ONE pyarrow group_by.
            # Blocks kept on the device (a top-K; then nothing else is
            # here) reach it as the one small table of their survivors.
            if acc is not None:
                pt = self._dense_to_partial(
                    acc, acc_groups, key_specs, specs, lay,
                )
                if pt is not None:
                    partials.append(pt)
                acc = None
            prev = rs.enter("partial")
            apt = self._agg_groups_to_partial(agg, specs, len(key_specs))
            rs.enter(prev)
            if apt is not None:
                partials.append(apt)
            with TRACER.span("execute.merge") as sp:
                if kept:
                    sp["rows"] = keep.entries
                    self._device_merge(keep, partials, key_specs, specs, lay)
                    sp["survivors"] = rs["merge_survivors"]
                if not rs["merge_device"]:
                    sp["rows"] = sum(p.num_rows for p in partials)
                    if local_mode:
                        rs["merge_host"] = 1
                        rs["merge_host_reason"] = keep.reason
                        DEVICE_MERGES.labels("host").inc()
                interim = None
                if partials:
                    prev = rs.enter("merge")
                    try:
                        interim = self._merge_partials(partials, specs, len(key_specs))
                    finally:
                        rs.enter(prev)
            return finish(interim)
        # vectorized dense finalize: when the run stayed fully on device
        # (no CPU-fallback partials, no distinct sets), skip the per-group
        # Python fold entirely — at G=32k the sparse path is ~80% of query
        # time (VERDICT Weak#5)
        if acc is not None and not agg.groups and not distinct_idx:
            # the K-gather reads only the packed accumulator; percentile
            # histograms live beside it, so top-K pushdown requires a
            # histogram gather too — not worth it, take the full readback
            topk_req = (
                self._device_topk_plan(rewritten)
                if sel.group_by and not pct_idx
                else None
            )
            if (
                topk_req is not None
                and acc_groups >= self.TOPK_MIN_GROUPS
                and topk_req[2] < acc_groups
            ):
                tsi, tdesc, tk = topk_req
                with TRACER.span("execute.topk", rows=tk):
                    arr_k, ids = self._run_topk_program(
                        acc, tsi, tdesc, tk, lay, specs,
                    )
                prev = rs.enter("partial")
                interim = self._dense_interim(
                    arr_k, acc_groups, key_specs, specs, lay,
                    group_ids=ids,
                )
                rs.enter(prev)
                return finish(interim)
            pcts = [
                (si, self._read_hist(h, acc_groups))
                for si, h in zip(pct_idx, pacc)
            ]
            with TRACER.span("execute.readback", rows=acc_groups) as sp:
                before = rs["d2h_bytes"]
                arr = _read_counts_exact(_timed_readback(acc, rs), lay)
                sp["bytes"] = rs["d2h_bytes"] - before
            prev = rs.enter("partial")
            interim = self._dense_interim(
                arr, acc_groups, key_specs, specs, lay, pcts=pcts,
            )
            rs.enter(prev)
            if interim.num_rows == 0 and not sel.group_by:
                return finish(None)
            return finish(interim)
        if acc is not None:
            flush(acc, acc_groups)
        return finish(None)

    def _dense_interim(
        self,
        arr: np.ndarray,
        num_groups: int,
        key_specs: list[KeySpec],
        specs: list[AggSpec],
        lay: AccLayout,
        group_ids: np.ndarray | None = None,
        pcts: list[tuple[int, np.ndarray]] | None = None,
    ) -> pa.Table:
        """Dense device accumulator -> interim table (__g/__agg columns),
        fully vectorized: key decode by divmod over capacities, aggregate
        finalize by numpy masking (stddev/var from the packed sum/sumsq
        rows; percentiles via the vectorized histogram walk). One readback,
        zero per-group Python.

        With `group_ids`, `arr` is a device-side top-K GATHER (R, K) and
        group_ids[j] is column j's global group index — the readback is
        K-sized instead of G-sized (ORDER BY <agg> LIMIT pushdown)."""
        count = arr[0]
        if group_ids is None:
            idxs = np.nonzero(count > 0)[0]
            sel_pos = idxs
        else:
            sel_pos = np.nonzero(count > 0)[0]  # positions into the K gather
            idxs = group_ids[sel_pos]  # global ids, for key decode

        cols: dict[str, pa.Array] = {}
        rem = idxs.copy()
        for i, ks in enumerate(key_specs):
            codes = rem % ks.capacity
            rem = rem // ks.capacity
            if ks.kind == "dict":
                live = ks.epoch_values()
                values = np.empty(len(live) + 1, dtype=object)
                values[:-1] = live
                values[-1] = None  # null / overflow slot
                cols[f"__g{i}"] = pa.array(values[np.minimum(codes, len(live))].tolist())
            else:
                abs_ms = ((ks.origin_rel or 0) + codes) * ks.bin_ms
                cols[f"__g{i}"] = pa.array(
                    abs_ms.astype("datetime64[ms]"), pa.timestamp("ms"),
                    mask=codes == ks.capacity - 1 if ks.null_slot else None,
                )
        pct_hists = dict(pcts or [])
        for si, spec in enumerate(specs):
            if spec.func == "count_star":
                cols[f"__agg{si}"] = pa.array(count[sel_pos].astype(np.int64))
                continue
            pac = arr[lay.pac_row(si)][sel_pos]
            seen = pac > 0
            if spec.func == "count":
                cols[f"__agg{si}"] = pa.array(pac.astype(np.int64))
            elif spec.func in ("sum", "avg"):
                v = arr[lay.sum_row(si)][sel_pos]
                if spec.func == "avg":
                    v = np.divide(v, pac, out=np.zeros_like(v), where=seen)
                cols[f"__agg{si}"] = pa.array(v, mask=~seen)
            elif spec.func in ("stddev", "var"):
                n = pac
                m2 = arr[lay.sqm2_row(si)][sel_pos]
                ok = n >= 2
                var = np.divide(m2, n - 1, out=np.zeros_like(m2), where=ok)
                var = np.maximum(var, 0.0)  # guard f.p. negatives
                v = np.sqrt(var) if spec.func == "stddev" else var
                cols[f"__agg{si}"] = pa.array(v, mask=~ok)
            elif spec.func == "percentile":
                from parseable_tpu.query.sketch import hist_quantile

                hist = pct_hists[si][idxs]
                vmins = arr[lay.pct_min_row(si)][sel_pos]
                vmaxs = arr[lay.pct_max_row(si)][sel_pos]
                v, ok = hist_quantile(
                    hist, vmins, vmaxs,
                    spec.param if spec.param is not None else 0.5,
                )
                cols[f"__agg{si}"] = pa.array(v, mask=~ok)
            elif spec.func == "min":
                v = arr[lay.min_row(si)][sel_pos]
                cols[f"__agg{si}"] = pa.array(v, mask=~seen)
            elif spec.func == "max":
                v = arr[lay.max_row(si)][sel_pos]
                cols[f"__agg{si}"] = pa.array(v, mask=~seen)
        if not cols:
            return pa.table({"__dummy": pa.array([None] * len(idxs))})
        return pa.table(cols)

    # --------------------------------------------- ORDER BY <agg> LIMIT K

    TOPK_MIN_GROUPS = 1 << 13  # below this the full readback is cheap
    TOPK_MAX_K = 4096

    def _device_topk_plan(self, rewritten: list[S.SelectItem]) -> tuple | None:
        """(spec_index, desc, k) when the query's ORDER BY/LIMIT can run as
        a device top_k over the dense accumulator: single ORDER BY key that
        resolves to one of the aggregates, LIMIT (+OFFSET) small, no HAVING
        (DataFusion's TopK pushdown; reference planner gets it from
        /root/reference/src/query/mod.rs:212-276)."""
        sel = self.plan.select
        if (
            len(sel.order_by) != 1
            or sel.limit is None
            or getattr(self, "_having", None) is not None
        ):
            return None
        if any(S.contains_window(i.expr) for i in sel.items):
            # a window over the aggregate output (rank() OVER, percent-of-
            # total) must see ALL groups, not the K gathered ones
            return None
        k = (sel.offset or 0) + sel.limit
        if k <= 0 or k > self.TOPK_MAX_K:
            return None
        o = sel.order_by[0]
        for item, ritem in zip(sel.items, rewritten):
            if not (
                isinstance(ritem.expr, S.Column) and ritem.expr.name.startswith("__agg")
            ):
                continue
            alias_match = (
                isinstance(o.expr, S.Column)
                and o.expr.table is None
                and ritem.alias == o.expr.name
            )
            if alias_match or repr(item.expr) == repr(o.expr):
                return int(ritem.expr.name[5:]), o.desc, k
        return None

    def _run_topk_program(
        self,
        acc,
        si: int,
        desc: bool,
        k: int,
        lay: AccLayout,
        specs: list[AggSpec],
    ) -> tuple[np.ndarray, np.ndarray]:
        """Select the top-k groups by one aggregate ON DEVICE and read back
        only the (R, k) gather + k group ids — the G-sized accumulator
        never crosses the link (what that saves is not measured on a
        directly attached chip)."""
        import jax
        import jax.numpy as jnp

        spec = specs[si]
        kind = spec.func
        pac_row = lay.pac_row(si) if kind != "count_star" else 0
        if kind in ("sum", "avg"):
            val_row = lay.sum_row(si)
        elif kind in ("stddev", "var"):
            val_row = lay.sqx_row(si)  # variance computed in-program
        elif kind == "min":
            val_row = lay.min_row(si)
        elif kind == "max":
            val_row = lay.max_row(si)
        else:  # count / count_star
            val_row = pac_row
        sq_row = lay.sqm2_row(si) if kind in ("stddev", "var") else 0
        key = ("topk", acc.shape, kind, val_row, pac_row, sq_row, desc, k)
        prev = self.route_stats.enter("dispatch")
        program = _PROGRAM_CACHE.get(key)
        if program is None:

            def run(a):
                with jax.named_scope("topk"):
                    count = a[0]
                    pacv = a[pac_row]
                    if kind == "avg":
                        keyv = a[val_row] / jnp.maximum(pacv, 1.0)
                    elif kind in ("stddev", "var"):
                        n = jnp.maximum(pacv, 2.0)
                        keyv = jnp.maximum(a[sq_row] / (n - 1.0), 0.0)
                        if kind == "stddev":
                            keyv = jnp.sqrt(keyv)
                    else:
                        keyv = a[val_row]
                    if kind in ("sum", "avg", "min", "max"):
                        notnull = pacv > 0
                    elif kind in ("stddev", "var"):
                        notnull = pacv > 1  # n < 2 -> NULL variance
                    else:
                        notnull = count > 0
                    occupied = count > 0
                    live = occupied & notnull
                    # Exact composite order in int32 (ADVICE r3 #1: a finite
                    # f32 sentinel let -inf/-3.4e38 real keys be displaced by
                    # NULL groups). The f32 bit pattern maps to a monotonic
                    # int32 whose range [-2139095040, 2139095040] (-inf..+inf)
                    # leaves headroom below for NaN keys, NULL-agg groups and
                    # empty slots — in that (nulls-last) order. top_k over the
                    # int32 scores is then a true three-class lexicographic
                    # sort with zero collisions against real keys.
                    kf = keyv.astype(jnp.float32)
                    nan = jnp.isnan(kf)
                    u = _f32_order(kf)
                    o = u if desc else jnp.where(
                        u == jnp.int32(-2147483648), jnp.int32(2147483647), -u
                    )
                    score = jnp.where(
                        live & ~nan,
                        o,
                        jnp.where(
                            live, jnp.int32(_SCORE_NAN),  # NaN key: below reals
                            jnp.where(
                                occupied, jnp.int32(_SCORE_NULL),  # NULL agg
                                jnp.int32(_SCORE_EMPTY),  # empty slot
                            ),
                        ),
                    )
                    _, idx = jax.lax.top_k(score, k)
                    return a[:, idx], idx

            run.__name__ = run.__qualname__ = "executor_topk"  # XLA module jit_executor_topk
            # no donate_argnums: `acc` outlives the top-k (the flush path
            # reads it); see the executor.dense note in _get_program
            program = jax.jit(run)  # jit-cache: executor.topk
            _note_program_build("executor.topk", key, self.route_stats)
            _PROGRAM_CACHE[key] = program
        else:
            self.route_stats["programs_reused"] += 1
        gathered, idx = program(acc)
        self.route_stats.dispatched(prev)
        return (
            _read_counts_exact(_timed_readback(gathered, self.route_stats), lay),
            _timed_readback(idx, self.route_stats, dtype=None),
        )

    def _note_fold_route(self, block_rows: int, kernel_groups: int, blocks: int, lay: AccLayout) -> None:
        """`blocks` blocks went through a device program: count the route
        their additive reduction took, by the function the kernel itself
        branches on and with what it sees (under a mesh a device holds
        its share of the block's rows), and the route of their min / max
        fold where the layout has one."""
        rows = block_rows // (self.mesh.shape["data"] if self.mesh is not None else 1)
        self.route_stats[f"fold_{kernels.fold_route(rows, kernel_groups)}_blocks"] += blocks
        if lay.n_mink or lay.n_maxk:
            self.route_stats["fold_minmax_scatter_blocks"] += blocks

    def _note_offorigin_bins(self, path: str, blocks: int) -> None:
        """`blocks` blocks whose text bins a time column off the block's
        origin were binned on `path` (device | host)."""
        if blocks:
            self.route_stats[f"timebin_offorigin_{path}_blocks"] += blocks
            DEVICE_TIMEBIN_OFFORIGIN.labels(path).inc(blocks)

    # ----------------------------------------------- high-card (block-local)

    def _local_block(
        self,
        partials: list[pa.Table],
        enc: EncodedBatch,
        dev: dict,
        luts: list[np.ndarray],
        key_specs: list[KeySpec],
        specs: list[AggSpec],
        layout: PlanLayout,
        lay: AccLayout,
        keep: _KeptPartials,
    ) -> None:
        """Two-phase step: fold one block on its OWN dictionary codes (no
        global remap on the device). While `keep` is active (a top-K whose
        merge runs on the device) the dense [R, G_block] partial stays
        there and only the block's global key lanes are shipped; else it
        is read back and its nonzero groups become a partial-format table."""
        import jax.numpy as jnp

        rs = self.route_stats
        prev = rs.enter("prepare")
        off_origin = _off_origin_keys(enc, key_specs)
        caps: list[int] = []
        origins: list[int] = []
        keyinfo: list[tuple] = []
        for ks in key_specs:
            col = enc.columns.get(ks.column)
            if col is None:
                raise UnsupportedOnDevice(f"group key column {ks.column} missing")
            if ks.kind == "dict":
                if col.kind != "dict":
                    raise UnsupportedOnDevice(f"group key {ks.column} not dict-encoded")
                cap = _pow2(max(2, len(col.dictionary)))
                caps.append(cap)
                origins.append(0)
                keyinfo.append(("dict", list(col.dictionary), cap))
            else:
                if col.vmin is None or col.vmax is None:
                    raise UnsupportedOnDevice("time-bin key over all-null column")
                _bin_steps(ks, col)
                lo_bin, hi_bin = _bin_range(ks, enc, col)
                span = int(hi_bin - lo_bin + 1 + ks.null_slot)
                cap = _pow2(max(2, span))
                if cap > LOCAL_G_MAX:
                    raise UnsupportedOnDevice("time-bin span exceeds device capacity")
                caps.append(cap)
                origins.append(int(lo_bin))
                keyinfo.append(("timebin", int(lo_bin), ks.bin_ms, cap - 1 if ks.null_slot else None, cap))
        num_groups = 1
        for c in caps:
            num_groups *= c

        if self.mesh is not None:
            import jax

            row_s, rep_s = _mesh_shardings(self.mesh)

            def put_rep(a, _s=rep_s, _jax=jax):
                # priced: local-fold LUT ships bypass _transfer's packed
                # payload, so the link accounting happens at the ship
                n = int(getattr(a, "nbytes", 0))
                self.route_stats["h2d_bytes"] += n
                DEVICE_BYTES_TO_DEVICE.labels("lut").inc(n)
                return _jax.device_put(a, _s)

            def put_row(a, _s=row_s, _jax=jax):
                n = int(getattr(a, "nbytes", 0))
                self.route_stats["h2d_bytes"] += n
                DEVICE_BYTES_TO_DEVICE.labels("lut").inc(n)
                return _jax.device_put(a, _s)
        else:
            put_rep = jnp.asarray
            put_row = jnp.asarray
        row_mask = dev.get("__rowmask", dev["__ones"])

        composite_vals: np.ndarray | None = None
        if num_groups > LOCAL_G_MAX:
            # cap product exceeds the budget, but the block's ACTUAL key
            # combos can't exceed its rows: compact (c0..ck) tuples with one
            # np.unique and fold on dense pair codes instead
            comp = None
            for ks, cap, origin in zip(key_specs, caps, origins):
                vals = self._host_codes(enc, dev, ks.column, rs)
                if ks.kind == "dict":
                    codes = np.minimum(vals.astype(np.int64), cap - 1)
                else:
                    col = enc.columns[ks.column]
                    origin_ms, unit_ms = _time_base(enc, col)
                    abs_ms = vals.astype(np.int64) * unit_ms + origin_ms
                    codes = np.clip(abs_ms // ks.bin_ms - origin, 0, cap - 1 - ks.null_slot)
                    if ks.null_slot and not col.all_valid:
                        live = col.valid if len(col.valid) else _timed_readback(dev[f"{ks.column}__valid"], rs, dtype=None)
                        codes = np.where(live, codes, cap - 1)
                comp = codes if comp is None else comp * cap + codes
            uniq, inv = np.unique(comp, return_inverse=True)
            num_groups = _pow2(max(2, len(uniq)))
            if num_groups > LOCAL_G_MAX:
                raise UnsupportedOnDevice(
                    "distinct key combos exceed the device group budget"
                )
            composite_vals = uniq
            dev = dict(dev)
            dev["__pairkey"] = put_row(inv.astype(np.int32))

        if composite_vals is None:
            # a key over the partition timestamp, on the block's origin, is
            # the three it was; any other says what its program differs by
            key_sig = tuple(
                (ks.kind, ks.column, ks.bin_ms, *((off, ks.null_slot) if off or ks.null_slot else ()))
                for ks, off in zip(key_specs, off_origin)
            )
            full_luts = luts + self._time_args(enc, key_specs, origins, self._bounds_ms())
        else:
            key_sig = (("pair", "__pairkey", 0),)
            full_luts = luts + self._time_args(enc, [], (), self._bounds_ms())
        dev_luts = tuple(put_rep(l) for l in full_luts)
        if keep.active and keep.entries + num_groups > MERGE_DEVICE_MAX_ENTRIES:
            self._spill_kept(keep, partials, specs, lay, "budget")

        rs.enter("dispatch")
        program = self._get_local_program(
            enc,
            tuple(caps),
            tuple(origins),
            key_sig,
            layout,
            tuple(l.shape for l in full_luts),
            tuple(sorted(dev.keys())),
            num_groups,
        )
        out_dev = program(dev, dev_luts, row_mask)
        self._note_fold_route(enc.block_rows, num_groups, 1, lay)
        if any(off_origin):
            # the pair codes' bins were numpy's (`_host_codes` above)
            self._note_offorigin_bins("device" if composite_vals is None else "host", 1)
        if keep.active:
            # the fold runs while the host makes the lanes; nothing waits on it
            rs.dispatched("prepare")
            lanes = self._key_lanes(
                enc, key_specs, caps, origins, composite_vals, num_groups, keep,
            )
            if lanes is not None:
                keep.blocks.append((out_dev, jnp.asarray(lanes), keyinfo, composite_vals))
                keep.entries += num_groups
                rs.enter(prev)
                return
            # a time-bin lane that 31 bits do not hold
            self._spill_kept(keep, partials, specs, lay, "budget")
            rs.enter("partial")
        else:
            rs.dispatched("partial")
        out = _timed_readback(out_dev, rs)
        pt = self._partial_from_arrays(
            out, lay, keyinfo, specs, composite_vals=composite_vals,
        )
        rs.enter(prev)
        if pt is not None:
            partials.append(pt)

    def _plan_device_merge(
        self, rewritten: list[S.SelectItem], specs: list[AggSpec], nkeys: int, host_partials: bool
    ) -> _KeptPartials:
        """Whether this query's block-local partials stay on the device for
        `jit_executor_merge`, from what the executor sees as local mode
        begins: the plan is a top-K over one aggregate that has a simple
        error bound, one device, and no partial made on the host so far."""
        topk = self._device_topk_plan(rewritten)
        if topk is None:
            reason = "plan"
        elif specs[topk[0]].func not in ("sum", "count", "count_star", "min", "max"):
            reason = "aggregate"  # avg / stddev / var: no simple bound, the host ranks them
        elif self.mesh is not None:
            reason = "mesh"
        elif host_partials:
            reason = "host_partials"
        else:
            reason = None
        return _KeptPartials(topk, nkeys, reason)

    def _spill_kept(
        self,
        keep: _KeptPartials,
        partials: list[pa.Table],
        specs: list[AggSpec],
        lay: AccLayout,
        reason: str,
    ) -> None:
        """The device merge is off for this query (`reason`, unless one was
        given before): what was kept is read back and turned into partial
        tables by the code that does it per block otherwise, in block order."""
        if keep.reason is None:
            keep.reason = reason
        rs = self.route_stats
        for out_dev, _lanes, keyinfo, composite_vals in keep.blocks:
            out = _timed_readback(out_dev, rs)
            prev = rs.enter("partial")
            pt = self._partial_from_arrays(out, lay, keyinfo, specs, composite_vals=composite_vals)
            rs.enter(prev)
            if pt is not None:
                partials.append(pt)
        keep.blocks = []
        keep.entries = 0

    @staticmethod
    def _dict_arrow(enc: EncodedBatch, col: EncodedColumn) -> pa.Array | None:
        """A block's dictionary as an arrow array, cached on the batch as
        `_hll_lut` is (a hot block's dictionary is converted once, not once
        a query); None where arrow cannot hold it."""
        cache = PredicateCompiler._batch_cache(enc)
        key = ("__arrow", col.name, len(col.dictionary))
        if key not in cache:
            try:
                cache[key] = pa.array(col.dictionary)
            except (pa.ArrowInvalid, pa.ArrowTypeError):
                cache[key] = None
        return cache[key]

    def _key_lanes(
        self,
        enc: EncodedBatch,
        key_specs: list[KeySpec],
        caps: list[int],
        origins: list[int],
        composite_vals: np.ndarray | None,
        num_groups: int,
        keep: _KeptPartials,
    ) -> np.ndarray | None:
        """[nkeys, num_groups] int32: for each slot of a block's dense
        partial the GLOBAL code of each of its keys, which is what makes
        entries of different blocks comparable on the device. A dict key's
        is its place in the query's GlobalDict (`_LANE_NULL` for the null
        key), a time-bin key's the absolute bin less the first kept
        block's first bin. Slots past the block's groups get `_LANE_DEAD`.
        None where a time-bin offset leaves the lanes' 2^30.

        Slot -> local codes as `_partial_from_arrays` decodes them (the
        capacities are powers of two): the stride layout has the first key
        minor, the np.unique compaction the first key major."""
        shifts = [cap.bit_length() - 1 for cap in caps]
        if composite_vals is None:
            rem = np.arange(num_groups, dtype=np.int64)
            codes = []
            for cap, sh in zip(caps, shifts):
                codes.append(rem & (cap - 1))
                rem = rem >> sh
        else:
            rem = composite_vals
            codes = []
            for cap, sh in zip(reversed(caps[1:]), reversed(shifts[1:])):
                codes.append(rem & (cap - 1))
                rem = rem >> sh
            codes.append(rem)
            codes.reverse()
        lanes = np.empty((len(key_specs), num_groups), np.int32)
        lanes[:, len(codes[0]) :] = _LANE_DEAD
        for i, (ks, code) in enumerate(zip(key_specs, codes)):
            if ks.kind == "dict":
                col = enc.columns[ks.column]
                remap = ks.gdict.absorb(col.dictionary, self._dict_arrow(enc, col))
                np.take(remap, code, out=lanes[i, : len(code)])
            else:
                if keep.bin_origin[i] is None:
                    keep.bin_origin[i] = origins[i]
                off = origins[i] - keep.bin_origin[i]
                if abs(off) + caps[i] >= int(_LANE_NULL):
                    return None
                lanes[i, : len(code)] = np.where(code == caps[i] - 1, _LANE_NULL, code + off) if ks.null_slot else code + off
        return lanes

    def _device_merge(
        self,
        keep: _KeptPartials,
        partials: list[pa.Table],
        key_specs: list[KeySpec],
        specs: list[AggSpec],
        lay: AccLayout,
    ) -> None:
        """Run `jit_executor_merge` over the kept blocks and add to
        `partials` ONE table in the per-block format: the per-block rows of
        the groups that can be in the top k, keys decoded for them only
        (none where no group survived: there was none). The host merge and
        `finish()` make the answer from it as from any partials. When more
        groups survived than were gathered that table would not hold them
        all, and the kept blocks go through the host ("survivors")."""
        import jax.numpy as jnp

        rs = self.route_stats
        si, desc, k = keep.si, keep.desc, keep.k
        func = specs[si].func
        if func == "count_star":
            kind, t_row, pac_row = "count", 0, 0
        elif func == "count":
            kind, t_row, pac_row = "count", lay.pac_row(si), lay.pac_row(si)
        else:
            kind, pac_row = func, lay.pac_row(si)
            t_row = {"sum": lay.sum_row, "min": lay.min_row, "max": lay.max_row}[func](si)
        nkeys = len(key_specs)
        k_out = min(_pow2(max(4 * k, 64)), SURVIVORS_MAX)
        # shapes in powers of two, so that few ever compile: a query over
        # another number of blocks meets the program of its bucket
        n_pad = _pow2(max(keep.entries, k, k_out))
        run_max = _pow2(len(keep.blocks), 1)
        n_rows = keep.blocks[0][0].shape[0]
        prev = rs.enter("dispatch")
        program = _merge_program(n_pad, run_max, n_rows, nkeys, kind, t_row, pac_row, desc, k, k_out, rs)
        pad = n_pad - keep.entries
        got_dev, meta_dev = program(
            jnp.concatenate(
                [b[0] for b in keep.blocks] + [jnp.zeros((n_rows, pad), jnp.float32)] * (pad > 0), axis=1
            ),
            jnp.concatenate(
                [b[1] for b in keep.blocks] + [jnp.full((nkeys, pad), _LANE_DEAD)] * (pad > 0), axis=1
            ),
        )
        rs.dispatched(prev)
        meta = _timed_readback(meta_dev, rs, dtype=None)
        rs["merge_entries"] = keep.entries
        rs["merge_survivors"] = survivors = int(meta[nkeys, 0])
        if survivors > k_out:
            self._spill_kept(keep, partials, specs, lay, "survivors")
            return
        got = _timed_readback(got_dev, rs)
        prev = rs.enter("partial")
        keyinfo: list[tuple] = []
        key_codes: list[np.ndarray] = []
        for i, ks in enumerate(key_specs):
            lane = meta[i].astype(np.int64)
            if ks.kind == "dict":
                # the survivors' own small dictionary; the null key and the
                # lanes of slots that hold no survivor take its null slot
                named = lane < len(ks.gdict.values)
                uniq = np.unique(lane[named])
                keyinfo.append(("dict", [ks.gdict.values[c] for c in uniq] + [None], 0))
                lane = np.where(named, np.searchsorted(uniq, lane), len(uniq))
            else:
                keyinfo.append(("timebin", keep.bin_origin[i], ks.bin_ms, int(_LANE_NULL) if ks.null_slot else None, 0))
            key_codes.append(np.repeat(lane, run_max))
        pt = self._partial_from_arrays(got, lay, keyinfo, specs, key_codes=key_codes)
        rs.enter(prev)
        if pt is not None:
            partials.append(pt)
        keep.blocks = []  # the device arrays go with the query's last use of them
        rs["merge_device"] = 1
        DEVICE_MERGES.labels("device").inc()

    @staticmethod
    def _hll_lut(enc: EncodedBatch, col: EncodedColumn) -> np.ndarray:
        """[2, N] (idx, rank) HLL LUT over the block's dictionary, cached
        on the batch (lifetime == dictionary lifetime) so hot-set-resident
        blocks hash their values exactly once."""
        cache = getattr(enc, "lut_cache", None)
        if cache is None:
            cache = {}
            enc.lut_cache = cache
        key = ("__hll", col.name, len(col.dictionary))
        hit = cache.get(key)
        if hit is None:
            from parseable_tpu.ops.hll_sketch import luts_for_dictionary

            idx, rank = luts_for_dictionary(col.dictionary)
            hit = np.stack([idx, rank]).astype(np.int32)
            cache[key] = hit
        return hit

    @staticmethod
    def _host_codes(
        enc: EncodedBatch, dev: dict, column: str, stats: dict | None = None
    ) -> np.ndarray:
        """A column's encoded codes on host: the encode-time array when it
        still exists, else a readback (hot-set entries strip host copies,
        so for a warm block this is every call). Its bytes and time are
        `stats`'s."""
        col = enc.columns.get(column)
        if col is None:
            raise UnsupportedOnDevice(f"group key column {column} missing")
        if col.values is not None and len(col.values):
            return col.values
        return _timed_readback(dev[column], stats, dtype=None)

    def _get_local_program(
        self,
        enc: EncodedBatch,
        caps: tuple[int, ...],
        origins: tuple[int, ...],
        key_sig: tuple,
        layout: PlanLayout,
        lut_shapes: tuple,
        dev_keys: tuple,
        num_groups: int,
    ) -> Callable:
        """One jitted dispatch for a block-local partial: mask + own-code
        group ids + fused aggregate; partials psum over the mesh data axis."""
        mesh = self.mesh
        kinds = tuple(sorted((n, c.kind) for n, c in enc.columns.items()))
        bounds_ms = self._bounds_ms()
        key = (
            "local",
            _expr_fingerprint(self.plan.select.where),
            (bounds_ms[0] is not None, bounds_ms[1] is not None),
            key_sig,
            caps,
            # origins deliberately NOT in the key: the block's bin offset
            # ships as a runtime scalar, so one program serves every block
            num_groups,
            tuple(layout.stacked_cols),
            tuple(layout.sum_cols),
            tuple(layout.min_cols),
            tuple(layout.max_cols),
            tuple(layout.sq_cols),
            tuple(layout.cnt_cols),
            enc.block_rows,
            kinds,
            lut_shapes,
            dev_keys,
            None if mesh is None else id(mesh),
        )
        prog = _PROGRAM_CACHE.get(key)
        if prog is not None:
            self.route_stats["programs_reused"] += 1
            return prog

        import jax
        import jax.numpy as jnp

        sel_where = self.plan.select.where
        compiler = PredicateCompiler()
        n_timebin = sum(1 for k in key_sig if k[0] == "timebin")
        n_bounds = sum(1 for b in bounds_ms if b is not None)
        # a key off the block's origin says so in its signature (`_local_block`)
        n_key_args = 2 * n_timebin + sum(1 for k in key_sig if len(k) > 3 and k[3])
        n_time_args = n_key_args + n_bounds

        from parseable_tpu import DEFAULT_TIMESTAMP_KEY

        def fold(dev: dict, luts: tuple, row_mask):
            local_rows = row_mask.shape[0]
            # per-block time scalars ride the tail of the luts tuple
            # (_time_args layout); trace consumes the head
            extra = list(luts[len(luts) - n_time_args :]) if n_time_args else []
            with jax.named_scope("where"):
                mask = compiler.trace(
                    sel_where, enc, dev, list(luts[: len(luts) - n_time_args])
                )
                mask = jnp.logical_and(mask, row_mask)
                if n_bounds and DEFAULT_TIMESTAMP_KEY in enc.columns:
                    ts = dev[DEFAULT_TIMESTAMP_KEY]
                    bi = n_key_args
                    if bounds_ms[0] is not None:
                        mask = jnp.logical_and(mask, ts >= extra[bi][0])
                        bi += 1
                    if bounds_ms[1] is not None:
                        mask = jnp.logical_and(mask, ts < extra[bi][0])
                    mask = jnp.logical_and(mask, dev[f"{DEFAULT_TIMESTAMP_KEY}__valid"])
            with jax.named_scope("keys"):
                if key_sig and key_sig[0][0] == "pair":
                    # host-compacted composite codes (multi-key high cardinality)
                    ids = jnp.minimum(dev["__pairkey"], num_groups - 1)
                else:
                    ids = None
                    stride = 1
                    ti = 0
                    for (kind, column, bin_ms, *flags), cap in zip(key_sig, caps):
                        if kind == "dict":
                            codes = jnp.minimum(dev[column], cap - 1)
                        else:
                            off, null_slot = flags or (False, False)
                            shift, k_off = extra[ti][0], extra[ti + 1][0]
                            steps = extra[ti + 2][0] if off else jnp.int32(bin_ms)
                            ti += 3 if off else 2
                            codes = jnp.clip(
                                (dev[column] + shift) // steps + k_off,
                                0,
                                cap - 1 - null_slot,
                            )
                            if null_slot:
                                codes = jnp.where(dev[f"{column}__valid"], codes, cap - 1)
                        part = codes * jnp.int32(stride)
                        ids = part if ids is None else ids + part
                        stride *= cap
                    ids = (ids if ids is not None else jnp.zeros(local_rows, jnp.int32)).astype(jnp.int32)
                ids = ids.astype(jnp.int32)

            if layout.exprs:
                with jax.named_scope("fold"), jax.named_scope("expr"):
                    dev = AggExprCompiler.trace(dev, layout.exprs)
            with jax.named_scope("fold"):
                sum_v, min_v, max_v, valid_v, n_sumk, n_mink, n_maxk = _kernel_stacks(
                    dev, layout, local_rows
                )
                count, pac, sums, mins, maxs = kernels.fused_groupby_block(
                    ids,
                    mask,
                    sum_v,
                    min_v,
                    max_v,
                    valid_v,
                    num_groups,
                    n_sumk,
                    n_mink,
                    n_maxk,
                )
            m2_loc, m2_n, m2_s = _block_m2(
                dev, layout, ids, mask, pac, sums, num_groups
            )
            if mesh is not None:
                m2_loc, _, _ = _psum_m2(m2_loc, m2_n, m2_s, layout.sq_cols)
                count = jax.lax.psum(count, "data")
                pac = jax.lax.psum(pac, "data")
                sums = jax.lax.psum(sums, "data")
                mins = jax.lax.pmin(mins, "data")
                maxs = jax.lax.pmax(maxs, "data")
            m2 = (
                jnp.stack(m2_loc)
                if layout.sq_cols
                else jnp.zeros((0, num_groups), jnp.float32)
            )
            # ONE stacked output -> ONE device->host readback per block
            return jnp.concatenate(
                [count[None, :], pac, sums, m2, mins, maxs], axis=0
            )

        if mesh is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            dev_spec = {k: P("data") for k in dev_keys}
            in_specs = (dev_spec, tuple(P() for _ in lut_shapes), P("data"))
            body = shard_map(fold, mesh=mesh, in_specs=in_specs, out_specs=P())
        else:
            body = fold

        body.__name__ = body.__qualname__ = "executor_local"  # XLA module jit_executor_local
        # no donate_argnums here either — see the executor.dense note in
        # _get_program
        prog = jax.jit(body)  # jit-cache: executor.local
        if mesh is not None:
            global MESH_PROGRAMS_BUILT
            MESH_PROGRAMS_BUILT += 1
        _note_program_build("executor.local", key, self.route_stats)
        _PROGRAM_CACHE[key] = prog
        return prog

    @staticmethod
    def _decode_key_col(info: tuple, code: np.ndarray) -> pa.Array:
        """One key's codes -> typed arrow values (dictionary-typed for dict
        keys — readback partials carry codes, values decode only when the
        final rows do; time bins decode arithmetically)."""
        if info[0] == "dict":
            values = info[1]  # last entry is the null slot (None)
            if not values:
                return pa.nulls(len(code))
            arr = pa.array(values)
            take = np.minimum(code, len(values) - 1).astype(np.int32)
            return pa.DictionaryArray.from_arrays(pa.array(take), arr)
        origin_bin, bin_ms, null_code = info[1], info[2], info[3]
        abs_ms = (origin_bin + code) * bin_ms
        return pa.array(
            abs_ms.astype("datetime64[ms]"), pa.timestamp("ms"),
            mask=None if null_code is None else code == null_code,
        )

    def _partial_from_arrays(
        self,
        arr: np.ndarray,
        lay: AccLayout,
        keyinfo: list[tuple],
        specs: list[AggSpec],
        composite_vals: np.ndarray | None = None,
        key_codes: list[np.ndarray] | None = None,
    ) -> pa.Table | None:
        """Nonzero groups of one dense partial -> partial-format table
        (__g{i} keys, __cnt, per-spec __pac/__sum/__min/__max), fully
        vectorized: divmod key decode + dictionary takes.

        Default layout: group id = sum(code_i * stride_i), first key minor.
        With `composite_vals` (pair-compacted mode): group g's keys decode
        from composite_vals[g] = ((c0*cap1 + c1)*cap2 + c2)..., first key
        MAJOR — the np.unique compaction order. With `key_codes` (the
        device merge's gather) column j's keys are key_codes[i][j]."""
        count = arr[0]
        idxs = np.nonzero(count > 0)[0]
        if len(idxs) == 0:
            return None
        cols: dict[str, pa.Array] = {}
        if key_codes is not None:
            for i, (info, code) in enumerate(zip(keyinfo, key_codes)):
                cols[f"__g{i}"] = self._decode_key_col(info, code[idxs])
        elif composite_vals is None:
            rem = idxs.copy()
            for i, info in enumerate(keyinfo):
                cap = info[-1]
                code = rem % cap
                rem = rem // cap
                cols[f"__g{i}"] = self._decode_key_col(info, code)
        else:
            rem = composite_vals[idxs].copy()
            decoded: list[np.ndarray] = []
            for info in reversed(keyinfo[1:]):
                cap = info[-1]
                decoded.append(rem % cap)
                rem = rem // cap
            decoded.append(rem)
            for i, (info, code) in enumerate(zip(keyinfo, reversed(decoded))):
                cols[f"__g{i}"] = self._decode_key_col(info, code)
        cols["__cnt"] = pa.array(count[idxs])
        for si, spec in enumerate(specs):
            if spec.func == "count_star":
                continue
            pacv = arr[lay.pac_row(si)][idxs]
            cols[f"__pac{si}"] = pa.array(pacv)
            seen = pacv > 0
            if spec.func in ("sum", "avg"):
                cols[f"__sum{si}"] = pa.array(arr[lay.sum_row(si)][idxs], mask=~seen)
            elif spec.func in ("stddev", "var"):
                s = arr[lay.sqx_row(si)][idxs]
                n = np.maximum(pacv, 1.0)
                cols[f"__sum{si}"] = pa.array(s, mask=~seen)
                # raw sumsq reconstructed in f64 (see _flush_state note)
                cols[f"__sumsq{si}"] = pa.array(
                    arr[lay.sqm2_row(si)][idxs] + s * s / n, mask=~seen
                )
            elif spec.func == "min":
                cols[f"__min{si}"] = pa.array(arr[lay.min_row(si)][idxs], mask=~seen)
            elif spec.func == "max":
                cols[f"__max{si}"] = pa.array(arr[lay.max_row(si)][idxs], mask=~seen)
        return pa.table(cols)

    def _dense_to_partial(
        self,
        acc,
        num_groups: int,
        key_specs: list[KeySpec],
        specs: list[AggSpec],
        lay: AccLayout,
    ) -> pa.Table | None:
        """Dense global accumulator -> partial table (used when switching to
        block-local mode mid-query: the dense epoch's results merge through
        the same vectorized group_by as the block partials)."""
        arr = _read_counts_exact(_timed_readback(acc, self.route_stats), lay)
        prev = self.route_stats.enter("partial")
        keyinfo: list[tuple] = []
        for ks in key_specs:
            if ks.kind == "dict":
                keyinfo.append(("dict", ks.epoch_values() + [None], ks.capacity))
            else:
                keyinfo.append(("timebin", ks.origin_rel or 0, ks.bin_ms, ks.capacity - 1 if ks.null_slot else None, ks.capacity))
        pt = self._partial_from_arrays(arr, lay, keyinfo, specs)
        self.route_stats.enter(prev)
        return pt

    def _read_hist(self, h, num_groups: int) -> np.ndarray:
        """Percentile-histogram readback: flat [G * DEVICE_NB] device f32
        -> (G, DEVICE_NB) host array.

        Large single-device histograms first read back an NB-sized
        column-occupancy vector and gather only the ACTIVE bins — log data
        clusters in a few dozen octaves, so this typically cuts the
        readback bytes 10-50x; whether the extra round trip pays for that
        is not measured on a directly attached chip. Mesh runs read back
        directly (the buffer is local to the host that owns it)."""
        import jax.numpy as jnp

        total = num_groups * DEVICE_NB
        if self.mesh is not None or total <= (1 << 20):
            return _timed_readback(h, self.route_stats).reshape(num_groups, DEVICE_NB)
        mat = h.reshape(num_groups, DEVICE_NB)
        # NB-sized (~8 KB) occupancy probe gating a readback 10-50x larger:
        # when sparse the probe pays for itself
        colsum = _timed_readback(jnp.sum(mat, axis=0), self.route_stats, dtype=None)
        active = np.nonzero(colsum > 0)[0]
        if len(active) * 2 >= DEVICE_NB:
            return _timed_readback(h, self.route_stats).reshape(num_groups, DEVICE_NB)
        out = np.zeros((num_groups, DEVICE_NB))
        if len(active):
            gathered = _timed_readback(mat[:, jnp.asarray(active)], self.route_stats)
            out[:, active] = gathered.reshape(num_groups, len(active))
        return out

    @staticmethod
    def _agg_groups_to_partial(
        agg: HashAggregator,
        specs: list[AggSpec],
        nkeys: int,
    ) -> pa.Table | None:
        """CPU-fallback partials (HashAggregator groups) -> partial table so
        mixed device/CPU runs merge exactly. Sized by the fallback blocks'
        group count only."""
        if not agg.groups:
            return None
        cs_idx = next((i for i, s in enumerate(specs) if s.func == "count_star"), None)
        cols: dict[str, list] = {f"__g{i}": [] for i in range(nkeys)}
        cols["__cnt"] = []
        for si, spec in enumerate(specs):
            if spec.func == "count_star":
                continue
            cols[f"__pac{si}"] = []
            if spec.func in ("sum", "avg"):
                cols[f"__sum{si}"] = []
            elif spec.func in ("stddev", "var"):
                cols[f"__sum{si}"] = []
                cols[f"__sumsq{si}"] = []
            elif spec.func == "min":
                cols[f"__min{si}"] = []
            elif spec.func == "max":
                cols[f"__max{si}"] = []
        for key, st in agg.groups.items():
            for i in range(nkeys):
                cols[f"__g{i}"].append(key[i])
            cols["__cnt"].append(
                float(st.count[cs_idx]) if cs_idx is not None else 1.0
            )
            for si, spec in enumerate(specs):
                if spec.func == "count_star":
                    continue
                cols[f"__pac{si}"].append(float(st.count[si]))
                if spec.func in ("sum", "avg"):
                    cols[f"__sum{si}"].append(st.sums[si] if st.count[si] else None)
                elif spec.func in ("stddev", "var"):
                    cols[f"__sum{si}"].append(st.sums[si] if st.count[si] else None)
                    cols[f"__sumsq{si}"].append(st.sumsqs[si] if st.count[si] else None)
                elif spec.func == "min":
                    cols[f"__min{si}"].append(st.mins[si])
                elif spec.func == "max":
                    cols[f"__max{si}"].append(st.maxs[si])
        return pa.table(cols)

    def _merge_partials(
        self, partials: list[pa.Table], specs: list[AggSpec], nkeys: int
    ) -> pa.Table:
        """Host merge phase of the two-phase aggregation (shared with the
        CPU engine: query/partials.py merge_partials)."""
        from parseable_tpu.query import partials as PT

        return PT.merge_partials(partials, specs, nkeys)

    # ------------------------------------------------------------- programs

    def _get_program(
        self,
        enc: EncodedBatch,
        layout: PlanLayout,
        num_groups: int,
        operand_sig: tuple,
        n_blocks: int = 1,
        dev_keys: tuple = (),
    ) -> Callable:
        """One jitted dispatch: WHERE mask + dict remap + group ids + fused
        aggregate + fold into the device accumulator.

        `operand_sig` is one block's `_operand_sig` of its predicate LUTs
        (with the time scalars), its key remaps and its distinct remaps: the
        program takes all `n_blocks` blocks' operands as `_pack_operands`
        left them and slices them apart at the offsets that follow from it.

        With a mesh active, the whole fold runs under `shard_map`: each
        device computes the fused partial aggregate for its row shard and
        the partials combine with psum/pmin/pmax over the `data` axis — the
        reduction the reference does in querier-side merge loops
        (cluster/mod.rs:1785-1964) happens on ICI inside one XLA program.

        Cached process-wide; the key covers everything baked into the trace.
        """
        mesh = self.mesh
        # 2D layout: the accumulator itself shards over the `groups` axis
        shard_groups = _group_shards_of(mesh, num_groups)
        # distinct presence bitmaps shard over `groups` too: the flat
        # groups-major layout (group * Vcap + code) makes each shard's
        # window contiguous, so P("groups") on the flat dim is exact
        kinds = tuple(sorted((n, c.kind) for n, c in enc.columns.items()))
        bounds_ms = self._bounds_ms()
        key = (
            _expr_fingerprint(self.plan.select.where),
            (bounds_ms[0] is not None, bounds_ms[1] is not None),
            tuple(S.expr_name(ks.expr) for ks in layout.key_specs),
            tuple(layout.stacked_cols),
            tuple(layout.sum_cols),
            tuple(layout.min_cols),
            tuple(layout.max_cols),
            enc.block_rows,
            kinds,
            layout.caps,
            # origins deliberately NOT in the key: bin offsets ship as
            # runtime scalars, so origin epoch changes reuse the program
            operand_sig,
            num_groups,
            n_blocks,
            None if mesh is None else id(mesh),
            dev_keys,
            tuple(layout.distinct_cols),
            layout.distinct_caps,
            layout.distinct_sketch,
            shard_groups,
            tuple(layout.sq_cols),
            tuple(layout.pct_cols),
            tuple(layout.cnt_cols),
            layout.off_origin,
        )
        prog = _PROGRAM_CACHE.get(key)
        if prog is not None:
            self.route_stats["programs_reused"] += 1
            return prog

        import jax
        import jax.numpy as jnp

        sel_where = self.plan.select.where
        compiler = PredicateCompiler()
        kernel_groups = num_groups // shard_groups  # per-device group window
        key_specs = [
            KeySpec(ks.kind, ks.column, ks.expr, ks.bin_ms, ks.gdict, cap, orig, ks.null_slot)
            for ks, cap, orig in zip(layout.key_specs, layout.caps, layout.origins)
        ]
        n_timebin = sum(1 for ks in key_specs if ks.kind == "timebin")
        n_bounds = sum(1 for b in bounds_ms if b is not None)
        off_origin = layout.off_origin or (False,) * len(key_specs)
        n_key_args = 2 * n_timebin + sum(off_origin)
        n_time_args = n_key_args + n_bounds

        from parseable_tpu import DEFAULT_TIMESTAMP_KEY

        def fold_one(acc, dacc: tuple, pacc: tuple, dev: dict, luts: tuple, remaps: tuple, dremaps: tuple, row_mask):
            # row count as seen by this trace: the full block single-chip,
            # or this device's shard under shard_map
            local_rows = row_mask.shape[0]
            # per-block time scalars ride the tail of the luts tuple
            # (_time_args layout); trace consumes the head
            extra = list(luts[len(luts) - n_time_args :]) if n_time_args else []
            with jax.named_scope("where"):
                mask = compiler.trace(
                    sel_where, enc, dev, list(luts[: len(luts) - n_time_args])
                )
                mask = jnp.logical_and(mask, row_mask)
                if n_bounds and DEFAULT_TIMESTAMP_KEY in enc.columns:
                    ts = dev[DEFAULT_TIMESTAMP_KEY]
                    bi = n_key_args
                    if bounds_ms[0] is not None:
                        mask = jnp.logical_and(mask, ts >= extra[bi][0])
                        bi += 1
                    if bounds_ms[1] is not None:
                        mask = jnp.logical_and(mask, ts < extra[bi][0])
                    mask = jnp.logical_and(mask, dev[f"{DEFAULT_TIMESTAMP_KEY}__valid"])
            with jax.named_scope("keys"):
                if not key_specs:
                    ids = jnp.zeros(local_rows, dtype=jnp.int32)
                else:
                    ids = None
                    stride = 1
                    ri = 0
                    ti = 0
                    for ks, off in zip(key_specs, off_origin):
                        cap = ks.capacity
                        if ks.kind == "dict":
                            codes = jnp.minimum(remaps[ri][_as_index(dev[ks.column])], cap - 1)
                            ri += 1
                        else:
                            shift, k_off = extra[ti][0], extra[ti + 1][0]
                            # the bin in the column's own steps is the block's
                            # to say (`_time_args`); on the origin it is the text's
                            steps = extra[ti + 2][0] if off else jnp.int32(ks.bin_ms)
                            ti += 3 if off else 2
                            codes = jnp.clip(
                                (dev[ks.column] + shift) // steps + k_off,
                                0,
                                cap - 1 - ks.null_slot,
                            )
                            if ks.null_slot:
                                codes = jnp.where(dev[f"{ks.column}__valid"], codes, cap - 1)
                        part = codes * jnp.int32(stride)
                        ids = part if ids is None else ids + part
                        stride *= cap
                    ids = ids.astype(jnp.int32)

                # group-sharded (2D) layout: this device owns one contiguous
                # window of the group space; rows outside it mask off instead
                # of routing (parallel/mesh.py distributed_groupby_2d design)
                if shard_groups > 1:
                    gshard = jax.lax.axis_index("groups")
                    local = ids - gshard * jnp.int32(kernel_groups)
                    in_window = jnp.logical_and(local >= 0, local < kernel_groups)
                    mask = jnp.logical_and(mask, in_window)
                    ids = jnp.clip(local, 0, kernel_groups - 1)

            if layout.exprs:
                with jax.named_scope("fold"), jax.named_scope("expr"):
                    dev = AggExprCompiler.trace(dev, layout.exprs)
            with jax.named_scope("fold"):
                sum_v, min_v, max_v, valid_v, n_sumk, n_mink, n_maxk = _kernel_stacks(
                    dev, layout, local_rows
                )
                count, pac, sums, mins, maxs = kernels.fused_groupby_block(
                    ids,
                    mask,
                    sum_v,
                    min_v,
                    max_v,
                    valid_v,
                    kernel_groups,
                    n_sumk,
                    n_mink,
                    n_maxk,
                )
            # stddev/var: centered per-group second moments for this block
            # (local to the device's row shard under a mesh)
            m2_loc, m2_n, m2_s = _block_m2(
                dev, layout, ids, mask, pac, sums, kernel_groups
            )
            adds = jnp.concatenate([count[None, :], pac, sums], axis=0)
            # distinct presence: OR (max) each (group, value-code) bit;
            # approx_distinct maxes HLL RANKS into the register slot the
            # value's hash selects (same flat shape, same pmax merge)
            dacc_new = []
            sketch_flags = layout.distinct_sketch or (False,) * len(layout.distinct_cols)
            for di, (dcol, dcap) in enumerate(zip(layout.distinct_cols, layout.distinct_caps)):
                dm = jnp.logical_and(mask, dev[f"{dcol}__valid"])
                if sketch_flags[di]:
                    lut = dremaps[di]
                    raw = _as_index(dev[dcol])
                    codes = jnp.minimum(lut[0][raw], dcap - 1)
                    val = jnp.where(dm, lut[1][raw].astype(jnp.float32), 0.0)
                else:
                    codes = jnp.minimum(dremaps[di][_as_index(dev[dcol])], dcap - 1)
                    val = dm.astype(jnp.float32)
                flat = ids * jnp.int32(dcap) + codes
                upd = jax.ops.segment_max(
                    val, flat, num_segments=kernel_groups * dcap
                )
                if mesh is not None:
                    upd = jax.lax.pmax(upd, "data")
                dacc_new.append(jnp.maximum(dacc[di], upd))
            # percentile histograms: per-row log2 bin -> one additive
            # segment_sum into the flat [G * DEVICE_NB] sketch layout
            # (query/sketch.py); partials psum over the data axis and ADD
            # into the running histogram — same mergeability as the sums
            pacc_new = []
            for pi, pcol in enumerate(layout.pct_cols):
                v = dev[pcol].astype(jnp.float32)
                pm = jnp.logical_and(
                    jnp.logical_and(mask, dev[f"{pcol}__valid"]), ~jnp.isnan(v)
                )
                mag = jnp.clip(
                    jnp.log2(jnp.abs(v)),
                    jnp.float32(LOG_LO),
                    jnp.float32(LOG_HI - 1e-6),
                )
                bin_ = jnp.clip(
                    ((mag - jnp.float32(LOG_LO)) * jnp.float32(PCT_SCALE)).astype(jnp.int32),
                    0,
                    PCT_BINS - 1,
                )
                slot = jnp.where(
                    v == 0.0,
                    jnp.int32(2 * PCT_BINS),
                    jnp.where(v > 0, jnp.int32(PCT_BINS) + bin_, bin_),
                )
                flat = ids * jnp.int32(DEVICE_NB) + slot
                upd = jax.ops.segment_sum(
                    pm.astype(jnp.float32), flat, num_segments=kernel_groups * DEVICE_NB
                )
                if mesh is not None:
                    upd = jax.lax.psum(upd, "data")
                pacc_new.append(pacc[pi] + upd)
            if mesh is not None:
                # the distributed reduce tree: partials ride ICI (centered
                # moments via Chan's two-psum recenter, _psum_m2)
                m2_loc, m2_n, m2_s = _psum_m2(m2_loc, m2_n, m2_s, layout.sq_cols)
                adds = jax.lax.psum(adds, "data")
                mins = jax.lax.pmin(mins, "data")
                maxs = jax.lax.pmax(maxs, "data")
            a0 = adds.shape[0]  # 1 + n_allk + n_sum + n_sq (additive rows)
            n_sq = len(layout.sq_cols)
            n_sum_only = len(layout.sum_cols)
            n_allk_ = valid_v.shape[0]
            n_cnt = 1 + n_allk_  # count rows; their carry rows close the accumulator
            n_packed = acc.shape[0] - n_cnt
            # counts: TwoSum. `cnt` is the rounded f32 total, `lost` exactly what
            # that rounding left out of this add (0 below 2^24): carried, so a
            # group's count is exact however many rows it holds
            cnt_old, cnt_add = acc[:n_cnt], adds[:n_cnt]
            cnt = cnt_old + cnt_add
            seen = cnt - cnt_old
            lost = (cnt_old - (cnt - seen)) + (cnt_add - seen)
            parts = [cnt, acc[n_cnt:a0] + adds[n_cnt:]]
            if n_sq:
                m2_new = [
                    _chan_merge_m2(
                        acc[1 + n_sum_only + qi],  # pac (pre-block)
                        acc[1 + n_allk_ + n_sum_only + qi],  # sum (pre-block)
                        acc[a0 + qi],  # M2 (pre-block)
                        m2_n[qi], m2_s[qi], m2_loc[qi],
                    )
                    for qi in range(n_sq)
                ]
                parts.append(jnp.stack(m2_new))
            parts.append(jnp.minimum(acc[a0 + n_sq : a0 + n_sq + n_mink], mins))
            parts.append(jnp.maximum(acc[a0 + n_sq + n_mink : n_packed], maxs))
            parts.append(acc[n_packed:] + lost)
            new_acc = jnp.concatenate(parts, axis=0)
            return new_acc, tuple(dacc_new), tuple(pacc_new)

        def prog_fn(
            acc,
            dacc: tuple,
            pacc: tuple,
            devs: tuple,
            packed: tuple,
            row_masks: tuple,
        ):
            # unrolled folds: N blocks per dispatch amortize round-trip
            # latency; XLA sees one big program and schedules it as a unit
            operands = _unpack_operands(packed, operand_sig, n_blocks)
            for i in range(n_blocks):
                acc, dacc, pacc = fold_one(acc, dacc, pacc, devs[i], *operands[i], row_masks[i])
            return acc, dacc, pacc

        if mesh is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            dev_spec = {k: P("data") for k in dev_keys}
            # accumulator: replicated on 1D meshes; its G axis shards over
            # `groups` on the 2D layout (each device owns G/shard buckets)
            acc_spec = P(None, "groups") if shard_groups > 1 else P()
            dacc_spec = P("groups") if shard_groups > 1 else P()
            in_specs = (
                acc_spec,
                tuple(dacc_spec for _ in layout.distinct_caps),  # presence bitmaps
                tuple(dacc_spec for _ in layout.pct_cols),  # pct histograms
                tuple(dev_spec for _ in range(n_blocks)),
                tuple(P() for _ in _operand_dtypes(operand_sig)),  # the packed small operands
                tuple(P("data") for _ in range(n_blocks)),
            )
            out_specs = (
                acc_spec,
                tuple(dacc_spec for _ in layout.distinct_caps),
                tuple(dacc_spec for _ in layout.pct_cols),
            )
            prog_body = shard_map(prog_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
        else:
            prog_body = prog_fn

        # the name the XLA module (jit_executor_dense) and the profiler's
        # scope paths carry: its _note_program_build family
        prog_body.__name__ = prog_body.__qualname__ = "executor_dense"
        # NOTE: no donate_argnums — the choice was made for a backend this
        # code no longer runs on; donation's cost against the G-sized
        # accumulator copy is not measured on a directly attached chip
        prog = jax.jit(prog_body)  # jit-cache: executor.dense
        if mesh is not None:
            global MESH_PROGRAMS_BUILT, GROUP_SHARDED_PROGRAMS_BUILT
            MESH_PROGRAMS_BUILT += 1
            if shard_groups > 1:
                GROUP_SHARDED_PROGRAMS_BUILT += 1
        _note_program_build("executor.dense", key, self.route_stats)
        _PROGRAM_CACHE[key] = prog
        return prog

    # ------------------------------------------------------------- internals

    def _bounds_ms(self) -> tuple[int | None, int | None]:
        """API time bounds as absolute epoch ms, FLOORED for sub-ms bounds
        — the CPU engine's _bounds_filter coerces through
        pa.scalar(..., type=timestamp('ms')) the same way, and engine
        parity is the contract."""
        tb = self.plan.time_bounds
        out = []
        for b in (tb.low, tb.high):
            if b is None:
                out.append(None)
                continue
            bb = b if b.tzinfo else b.replace(tzinfo=UTC)
            out.append(_dt_to_us(bb) // 1000)
        return tuple(out)

    @staticmethod
    def _time_args(
        enc: EncodedBatch,
        key_specs: list[KeySpec],
        origins: tuple | list,
        bounds_ms: tuple[int | None, int | None],
    ) -> list[np.ndarray]:
        """Per-block time scalars appended after the predicate LUTs, in a
        fixed layout both the host builder and the traced fold share:
        [per-timebin-key (shift, K, steps?)...,  bounds lo?,  bounds hi?].

        shift = origin % bin (so (rel + shift) // bin is the global bin
        index minus origin//bin) and K = origin//bin - scan_lo_bin (the
        block's bin offset inside the scan's group window, bounded by the
        group capacity). A key over a column off the block's origin
        (`_off_origin_keys`) counts all three in the column's own steps and
        ships the divisor too: steps = bin_ms // unit_ms, shift = (origin_ms
        // unit_ms) % steps, K = origin_ms // bin_ms - scan_lo_bin, so one
        program serves every origin and every unit. Bounds clamp like
        predicate literals."""
        out: list[np.ndarray] = []
        from parseable_tpu import DEFAULT_TIMESTAMP_KEY

        if any(b is not None for b in bounds_ms):
            _require_on_origin(enc.columns.get(DEFAULT_TIMESTAMP_KEY))
        for ks, origin_bin in zip(key_specs, origins):
            if ks.kind != "timebin":
                continue
            col = enc.columns[ks.column]  # there: `_required_layout` / `_local_block` looked
            origin_ms, unit_ms = _time_base(enc, col)
            steps = _bin_steps(ks, col)  # bin_ms itself on the block's origin
            shift = origin_ms // unit_ms % steps
            k_off = origin_ms // ks.bin_ms - int(origin_bin)
            if not (-(2**31) < k_off < 2**31):
                raise UnsupportedOnDevice("block outside the scan's bin window")
            out.append(np.asarray([shift], dtype=np.int32))
            out.append(np.asarray([k_off], dtype=np.int32))
            if col.origin_ms is not None:
                out.append(np.asarray([steps], dtype=np.int32))
        for b in bounds_ms:
            if b is not None:
                rel = b - enc.time_origin_ms
                rel = max(-(2**31) + 2, min(2**31 - 2, rel))
                out.append(np.asarray([rel], dtype=np.int32))
        return out

    def _where_window_ms(self, column: str) -> tuple[int | None, int | None]:
        """The least and greatest epoch ms of `column` that the WHERE's
        top-level conjuncts let through (`plan.constraints`), None where
        they set no such bound: lets a time-bin key over an event-time
        column size its group window once, as `scan_time_hint` does for
        p_timestamp (one capacity epoch, one readback a query)."""
        lo = hi = None
        for c in self.plan.constraints:
            if c.column != column or c.op not in ("=", "<", "<=", ">", ">=") or not isinstance(c.value, str):
                continue
            try:
                v = _dt_to_us(parse_rfc3339(c.value)) // 1000
            except ValueError:
                continue
            if c.op in (">", ">=", "="):
                lo = v if lo is None else max(lo, v)
            if c.op in ("<", "<=", "="):
                v = v - 1 if c.op == "<" else v
                hi = v if hi is None else min(hi, v)
        return lo, hi

    def _required_layout(self, ks: KeySpec, enc: EncodedBatch) -> tuple[int, int]:
        """(origin, capacity) this key needs for the incoming batch. A change
        in either forces a dense-state flush before processing the batch."""
        if ks.kind == "dict":
            card = max(1, len(ks.gdict) + 1)  # +1 null slot
            cap = max(ks.capacity, 2)
            while cap < card:
                cap *= 2
            return 0, cap
        col = enc.columns.get(ks.column)
        if col is None:
            raise UnsupportedOnDevice(f"time column {ks.column} missing")
        _bin_steps(ks, col)  # declared here, before any state is touched
        if col.vmin is None or col.vmax is None:
            return ks.origin_rel or 0, max(ks.capacity, 2)
        lo_bin, hi_bin = _bin_range(ks, enc, col)
        if col.origin_ms is not None:
            # an event-time column is not what the manifests' p_timestamp
            # range speaks of: its window is the text's own bounds on it
            if ks.origin_rel is None:
                w_lo, w_hi = self._where_window_ms(ks.column)
                if w_lo is not None and w_hi is not None and 0 <= (w_hi - w_lo) // ks.bin_ms <= (1 << 22):
                    lo_bin = min(lo_bin, w_lo // ks.bin_ms)
                    hi_bin = max(hi_bin, w_hi // ks.bin_ms)
        elif ks.origin_rel is None and self.plan.scan_time_hint is not None:
            # pre-size from the scan's manifest time range: one capacity
            # epoch, one flush, one readback for the whole query
            h_lo, h_hi = self.plan.scan_time_hint
            hint_lo_bin = int(h_lo.timestamp() * 1000) // ks.bin_ms
            hint_hi_bin = int(h_hi.timestamp() * 1000) // ks.bin_ms
            if 0 < hint_hi_bin - hint_lo_bin <= (1 << 22):
                lo_bin = min(lo_bin, hint_lo_bin)
                hi_bin = max(hi_bin, hint_hi_bin)
        origin_bin = lo_bin if ks.origin_rel is None else min(ks.origin_rel, lo_bin)
        span = hi_bin - origin_bin + 1 + ks.null_slot
        cap = max(ks.capacity, 2)
        while cap < span:
            cap *= 2
        if cap > (1 << 22):
            raise UnsupportedOnDevice(
                f"time-bin span {span} exceeds device group capacity; widen the bin"
            )
        return origin_bin, cap

    def _flush_state(
        self,
        arr: np.ndarray,
        key_specs: list[KeySpec],
        agg: HashAggregator,
        specs: list[AggSpec],
        lay: AccLayout,
        dists: list[tuple] | None = None,  # (spec_idx, KeySpec, [G, Vcap] presence)
        pcts: list[tuple[int, np.ndarray]] | None = None,  # (spec_idx, [G, NB])
    ) -> None:
        """Dense accumulators -> sparse host aggregator, decoding group ids.

        `arr` is the packed accumulator readback (AccLayout rows, f64).
        Percentile histograms become QuantileSketch objects so device
        blocks and CPU-fallback blocks merge exactly; stddev/var rows fold
        into GroupState sum/sumsq."""
        from parseable_tpu.query.sketch import QuantileSketch

        idxs = np.nonzero(arr[0] > 0)[0]
        lives = [ks.epoch_values() if ks.kind == "dict" else None for ks in key_specs]
        for flat in idxs:
            key_parts = []
            rem = int(flat)
            for ks, live in zip(key_specs, lives):
                code = rem % ks.capacity
                rem //= ks.capacity
                if ks.kind == "dict":
                    key_parts.append(live[code] if code < len(live) else None)
                elif ks.null_slot and code == ks.capacity - 1:
                    key_parts.append(None)
                else:
                    abs_ms = ((ks.origin_rel or 0) + code) * ks.bin_ms
                    key_parts.append(
                        datetime.fromtimestamp(abs_ms / 1000.0, UTC).replace(tzinfo=None)
                    )
            counts = []
            sums_l = []
            sumsqs_l = []
            mins_l = []
            maxs_l = []
            for si, spec in enumerate(specs):
                if spec.func == "count_star":
                    counts.append(int(arr[0][flat]))
                elif spec.func in ("count_distinct", "approx_distinct", "percentile"):
                    # finalized from the merged value sets / registers /
                    # sketches
                    counts.append(0)
                else:
                    counts.append(int(arr[lay.pac_row(si)][flat]))
                if spec.func in ("sum", "avg"):
                    sums_l.append(float(arr[lay.sum_row(si)][flat]))
                    sumsqs_l.append(0.0)
                elif spec.func in ("stddev", "var"):
                    # reconstruct raw sumsq = M2 + sum^2/n in f64 so device
                    # partials merge with CPU GroupState raw moments; the
                    # sum^2/n terms cancel exactly at finalize, preserving
                    # the M2-level accuracy
                    s = float(arr[lay.sqx_row(si)][flat])
                    n = float(arr[lay.pac_row(si)][flat])
                    sums_l.append(s)
                    sumsqs_l.append(
                        float(arr[lay.sqm2_row(si)][flat]) + (s * s / n if n else 0.0)
                    )
                else:
                    sums_l.append(0.0)
                    sumsqs_l.append(0.0)
                if spec.func == "min":
                    # unseen = per-agg count 0 (the sentinel is f32 3.4e38,
                    # not inf, so gate on the count instead of the value)
                    seen = arr[lay.pac_row(si)][flat] > 0
                    mins_l.append(float(arr[lay.min_row(si)][flat]) if seen else None)
                else:
                    mins_l.append(None)
                if spec.func == "max":
                    seen = arr[lay.pac_row(si)][flat] > 0
                    maxs_l.append(float(arr[lay.max_row(si)][flat]) if seen else None)
                else:
                    maxs_l.append(None)
            distincts = None
            hlls = None
            if dists:
                distincts = {}
                for si, dk, presence in dists:
                    if specs[si].func == "approx_distinct":
                        if hlls is None:
                            hlls = {}
                        hlls[si] = presence[flat].astype(np.uint8)
                    else:
                        codes = np.nonzero(presence[flat][: len(dk.gdict)] > 0)[0]
                        distincts[si] = {dk.gdict.values[c] for c in codes}
            sketches = None
            if pcts:
                sketches = {}
                for si, hists in pcts:
                    row = hists[flat]
                    if row.sum() > 0:
                        sketches[si] = QuantileSketch.from_device_hist(
                            row,
                            float(arr[lay.pct_min_row(si)][flat]),
                            float(arr[lay.pct_max_row(si)][flat]),
                        )
                if not sketches:
                    sketches = None
            agg.merge_raw(
                tuple(key_parts), counts, sums_l, mins_l, maxs_l, distincts,
                sumsqs=sumsqs_l, sketches=sketches, hlls=hlls,
            )


# --------------------------------------------------------------- device util


def _bitcast_from_u8(seg, dtype: np.dtype, count: int):
    """Reinterpret a device u8 slice as `dtype` (no host round trip)."""
    import jax.numpy as jnp
    from jax import lax

    dt = np.dtype(dtype)
    if dt == np.uint8:
        return seg
    if dt == np.bool_:
        return seg != 0
    if dt.itemsize == 1:  # int8
        return lax.bitcast_convert_type(seg, jnp.dtype(dt))
    return lax.bitcast_convert_type(
        seg.reshape(count, dt.itemsize), jnp.dtype(dt)
    )


def _transfer(enc: EncodedBatch, mesh=None) -> tuple[dict, int]:
    """Ship encoded columns to device (row-sharded over the mesh `data`
    axis when one is active).

    Null-free columns share ONE device `ones` mask instead of shipping a
    validity array each — transfer bytes are the scan budget.

    Single-device path: ALL of a block's buffers are packed into one
    contiguous u8 payload and shipped with ONE device_put, then carved
    back into typed columns on-device (slice + bitcast, async, no round
    trips). One put per block instead of one per column was chosen for a
    link with tens of ms of per-put latency; what it buys is not measured
    on a directly attached chip.

    Every block shards or the call fails: block rows are a power of two
    >= 1024 (ops/device.pow2_block) and the `data` axis is a power of two
    (resolve_mesh), so a block that does not divide is a bug upstream —
    it is never parked on one device.
    """
    import jax.numpy as jnp

    if mesh is not None and enc.block_rows % mesh.shape.get("data", mesh.size):
        raise ValueError(
            f"block of {enc.block_rows} rows does not divide over the mesh "
            f"data axis {dict(mesh.shape)}"
        )
    dev: dict[str, Any] = {}
    nbytes = 0
    ones = _device_ones(enc.block_rows, mesh)
    if mesh is not None:
        # mesh path keeps per-column puts: each column is row-sharded and
        # device counts are small on a pod slice
        import jax

        row_s, _ = _mesh_shardings(mesh)

        def put_row(a):  # link-priced: per-column nbytes summed into the
            return jax.device_put(a, row_s)  # scan tick below the loop

        for name, col in enc.columns.items():
            dev[name] = put_row(col.values)
            nbytes += col.values.nbytes
            if col.all_valid:
                dev[f"{name}__valid"] = ones
            else:
                dev[f"{name}__valid"] = put_row(col.valid)
                nbytes += col.valid.nbytes
        dev["__ones"] = ones
        if enc.num_rows != enc.block_rows:
            dev["__rowmask"] = put_row(enc.row_mask)
            nbytes += enc.row_mask.nbytes
        DEVICE_BYTES_TO_DEVICE.labels("scan").inc(nbytes)
        return dev, nbytes

    parts: list[tuple[str, np.dtype, int, int]] = []  # key, dtype, count, offset
    bufs: list[np.ndarray] = []
    off = 0

    def pack(key: str, arr: np.ndarray) -> None:
        nonlocal off
        a = np.ascontiguousarray(arr)
        parts.append((key, a.dtype, len(a), off))
        bufs.append(a.view(np.uint8).reshape(-1))
        off += a.nbytes

    for name, col in enc.columns.items():
        pack(name, col.values)
        if not col.all_valid:
            pack(f"{name}__valid", col.valid)
    if enc.num_rows != enc.block_rows:
        # padding mask must live with the block (host copy gets stripped
        # when the block enters the hot set)
        pack("__rowmask", enc.row_mask)
    payload = np.concatenate(bufs) if bufs else np.empty(0, np.uint8)
    dev_payload = jnp.asarray(payload)  # asynchronous: nothing here waits for it
    nbytes = payload.nbytes
    for key, dtype, count, o in parts:
        dev[key] = _bitcast_from_u8(
            dev_payload[o : o + count * np.dtype(dtype).itemsize], dtype, count
        )
    for name, col in enc.columns.items():
        if col.all_valid:
            dev[f"{name}__valid"] = ones
    dev["__ones"] = ones
    DEVICE_BYTES_TO_DEVICE.labels("scan").inc(nbytes)
    return dev, nbytes


def _strip_host_values(enc: EncodedBatch) -> None:
    """Free the host-side ndarray copies before caching (dictionaries,
    vmin/vmax and flags stay — they're what queries need)."""
    empty = np.empty(0, np.int32)
    for col in enc.columns.values():
        col.values = empty
        col.valid = empty
    enc.row_mask = np.empty(0, bool)


def _concat_tables(tables: list[pa.Table]) -> pa.Table:
    if len(tables) == 1:
        return tables[0]
    return pa.concat_tables(tables, promote_options="permissive")


def _strip_where(sel: S.Select) -> S.Select:
    import copy

    out = copy.copy(sel)
    out.where = None
    return out
