"""JAX device kernels for query operators.

These are the TPU replacements for DataFusion's physical operators
(reference: src/query/mod.rs execution). Design rules:

- every kernel is jit-compiled with static (block_rows, num_groups) so XLA
  compiles one program per shape bucket and fuses predicate evaluation into
  the aggregation;
- no dynamic shapes: filters produce masks, never compacted arrays;
  aggregations weight by mask instead of selecting rows;
- group-by is *dense*: group keys are pre-combined into a single int32 id in
  [0, num_groups) (dictionary codes and time bins are already dense), and
  partials land in [num_groups]-sized accumulators: sums and counts by
  one-hot products on the MXU (plain, or factored in two past the plain
  one-hot's element budget) and by scatter-adds for very large G
  (`fold_route`), min / max by segment ops;
- partial aggregates are associative, so device blocks accumulate with `+`
  / min / max, and the distributed tree is a psum over the mesh data axis
  (see parallel/mesh.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
# numpy, not jnp: a module-level jnp scalar would initialise a backend at
# IMPORT time (and take the chip from whichever process should own it);
# jnp ops accept numpy scalars transparently
import numpy as _np

F32_MAX = _np.float32(3.4e38)


# ------------------------------------------------------------------ predicates


@jax.jit
def lut_mask(codes: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """String predicate as dictionary-LUT gather: lut[codes].

    The LUT is the predicate evaluated host-side over the dictionary values
    (plus a trailing False for the null slot)."""
    return lut[codes]


# ------------------------------------------------------------------- aggregate


@partial(jax.jit, static_argnames=("num_groups", "num_values"))
def masked_distinct_bitmap(
    group_ids: jnp.ndarray,
    value_codes: jnp.ndarray,
    mask: jnp.ndarray,
    num_groups: int,
    num_values: int,
) -> jnp.ndarray:
    """Exact per-group distinct of a dict-encoded column: presence matrix
    [num_groups, num_values] (works while G*V stays device-sized;
    approx_distinct instead maxes HLL ranks into a fixed [G, HLL_M]
    register file — ops/hll_sketch.py — so high-cardinality distinct
    stays on device)."""
    flat = group_ids * num_values + jnp.minimum(value_codes, num_values - 1)
    present = jax.ops.segment_max(
        mask.astype(jnp.float32), flat, num_segments=num_groups * num_values
    )
    return present.reshape(num_groups, num_values)


@partial(jax.jit, static_argnames=("k",))
def topk(values: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k over per-group aggregates -> (values, group indices)."""
    return jax.lax.top_k(values, k)


# -------------------------------------------------------------- fused group-by


# The plain one-hot dot's ceiling in groups. With the element budget below
# it is the budget that binds at a served block's size (1,024 groups at
# 2^20 rows, 4,096 at a mesh shard's 2^18); past either the fold takes the
# factored product (`fold_route`), not the scatter
MATMUL_MAX_GROUPS = 8192

# The one-hot operand may MATERIALIZE (N, G) when XLA declines to fuse it
# into the dot; bound its footprint (elements): a 1M-row block at G=8192
# is a 16 GB bf16 tensor otherwise (observed as a CPU-backend OOM and as
# memory-bound slowness on chip). Past it the one-hot is factored
MATMUL_MAX_ONEHOT_ELEMS = 1 << 30

# The factored product's low factor: the one-hot over `ids % 128` is one
# lane-wide tile, and the high factor `ids // 128` selects among
# ceil(G / 128) copies of each reduced row. 256 read 1.2 times slower at
# G = 8,192; a split balanced on G (512 at 2^18, 1,024 at 2^20) 1.15-1.26
# times faster there, never enough to carry a second constant (v5e, PR 33)
FACTORED_G_LO = 128

# Rows a step of the factored product. On a v5e, ms a 2^20-row block at
# G = 8,192, three count rows and one summed (PR 33): 1,024 rows 3.31,
# 2,048 2.39, 4,096 1.95, 8,192 1.69, 16,384 and more 3.2-3.3, which is
# also what the product reads with no tiling at all (3.17)
FACTORED_ROW_TILE = 1 << 13

# Past this many groups the scatter wins. On a v5e, ms a block of 2^20 rows
# (of a mesh shard's 2^18), factored against `segment_sum` (PR 33): G =
# 8,192: 1.63 against 21.84 (0.45 / 5.48); 2^16: 6.43 / 30.03 (1.64 /
# 7.24); 2^18: 22.29 / 29.25 (5.61 / 7.41); 2^19: 49.30 / 56.28 (12.37 /
# 14.11); 2^20: 95.84 / 56.30 (24.01 / 14.12). The MXU's work grows with G,
# the scatter's hardly does
FACTORED_MAX_GROUPS = 1 << 19


# VMEM ceiling for the pallas path: the (ROW_TILE=2048, G) f32 one-hot
# tile lives on-chip (2048*512*4B = 4MB; at G=512 it compiles and runs on
# a v5e under Mosaic's default scoped-VMEM limit — chip_smoke.py)
PALLAS_MAX_GROUPS = 512

# f32 x f32 dots: on a TPU the default precision rounds each f32 operand to
# bf16 before the MXU pass. The one-hot operand survives that (0/1), the
# summed VALUES do not (~3 significant digits per addend), and a group of
# a few rows cannot average the error away — chip_smoke.py's sparse-group
# query is the check. Seen on a v5e (PR 21): DEFAULT passes the dense
# BASELINE configs at the engine's 1e-4 tolerance but misses a 10-row
# group's sum(bytes) by 4e-4; HIGH passes every served query (~1e-5) but
# Mosaic refuses it in the Pallas twin ("Unsupported dot precision:
# HIGH"); HIGHEST passes both at ~1e-7, so one constant serves both
# kernels. What it costs is not measured on today's code.
SUM_DOT_PRECISION = jax.lax.Precision.HIGHEST


def _pallas_mode() -> str:
    """Opt-in pallas additive reduction (ops/pallas_groupby.py), off by
    default: which of it and the XLA dot is faster is not measured on
    today's code. P_TPU_USE_PALLAS=1 compiles the kernel with Mosaic (TPU
    only); P_TPU_USE_PALLAS=interpret runs Pallas' interpreter, which is
    what tests on the CPU backend pass explicitly.

    NOTE: read at TRACE time — fused_groupby_block's jit cache bakes the
    routing in, so toggling mid-process needs
    `fused_groupby_block.clear_cache()` (a process-level deployment
    choice, not a per-query switch)."""
    from parseable_tpu.config import env_str

    mode = env_str("P_TPU_USE_PALLAS", "")
    return mode if mode in ("1", "interpret") else ""


def fold_route(n_rows: int, num_groups: int, backend: str | None = None) -> str:
    """Which form the additive reduction (count, per-aggregate counts, sums)
    of one folded block takes: "onehot" (one dot against the (N, G)
    one-hot), "factored" (`_factored_additive`) or "scatter"
    (`jax.ops.segment_sum`). A pure function of the block's rows (a
    shard's under a mesh), the group count and the backend, all static
    when the program is traced: `fused_groupby_block` branches on it and
    the executor counts it (`device_routes.fold_*_blocks`).

    The one-hot dots are the MXU's; every other backend (the virtual CPU
    mesh, the dryrun) lacks a systolic array and pays the full (N, G)
    materialization, so scatter wins there beyond tiny shapes."""
    if backend is None:
        backend = jax.default_backend()
    max_onehot = (
        MATMUL_MAX_ONEHOT_ELEMS
        if backend == "tpu"
        else min(MATMUL_MAX_ONEHOT_ELEMS, 1 << 22)
    )
    if num_groups <= MATMUL_MAX_GROUPS and n_rows * num_groups <= max_onehot:
        return "onehot"
    if backend == "tpu" and num_groups <= FACTORED_MAX_GROUPS:
        return "factored"
    return "scatter"


def _factored_additive(
    group_ids: jnp.ndarray,  # int32 [N] in [0, num_groups)
    count_rows: jnp.ndarray,  # bool [Rc, N]
    sum_rows: jnp.ndarray,  # float32 [Rs, N], finite, zero where the row does not count
    num_groups: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The one-hot reduction with the one-hot factored in two:
    `hi = id // FACTORED_G_LO`, `lo = id % FACTORED_G_LO`, and for a
    reduced row r

        out[r, hi, lo] = sum_n where(hi_n == hi, rows[r, n], 0) * onehot_lo[n, lo]

    one dot of [R * G_hi, N] x [N, G_lo], read as [R, G]. The MXU does the
    plain one-hot's 2 R N G; what has to be generated falls from N * G
    elements to N * (R * G_hi + G_lo). The precision split is the plain
    one-hot's: 0/1 rows bf16 x bf16 -> f32 (exact), value rows f32 x 0/1
    at SUM_DOT_PRECISION, so an addend is the f32 value or zero. G is
    padded up to whole G_lo and the answer cut back. The rows go through
    in tiles of FACTORED_ROW_TILE, partial sums carried in f32. An inf or
    NaN among `sum_rows` would be NaN in every group of its high row (times
    the one-hot's zeros): the caller keeps them out.

    -> (count sums [Rc, num_groups], value sums [Rs, num_groups]), f32."""
    g_lo = FACTORED_G_LO
    g_hi = -(-num_groups // g_lo)
    iota_hi = jnp.arange(g_hi, dtype=jnp.int32)[None, :, None]
    iota_lo = jnp.arange(g_lo, dtype=jnp.int32)[None, :]
    contract = (((2,), (0,)), ((), ()))

    def fold(acc, start, size: int):
        """acc + the products of rows [start, start + size)."""
        ids = jax.lax.dynamic_slice_in_dim(group_ids, start, size)
        in_hi = (ids // g_lo)[None, None, :] == iota_hi
        lo = (ids % g_lo)[:, None]
        # each dot is the sole consumer of its operands, as in the plain route
        counts = jax.lax.dot_general(
            jnp.logical_and(
                in_hi, jax.lax.dynamic_slice_in_dim(count_rows, start, size, axis=1)[:, None, :]
            ).astype(jnp.bfloat16),
            (lo == iota_lo).astype(jnp.bfloat16),
            contract,
            preferred_element_type=jnp.float32,
        )
        if not sum_rows.shape[0]:
            return acc[0] + counts, acc[1]
        sums = jax.lax.dot_general(
            jnp.where(in_hi, jax.lax.dynamic_slice_in_dim(sum_rows, start, size, axis=1)[:, None, :], 0.0),
            (lo == iota_lo).astype(jnp.float32),
            contract,
            preferred_element_type=jnp.float32,
            precision=SUM_DOT_PRECISION,
        )
        return acc[0] + counts, acc[1] + sums

    n_rows = group_ids.shape[0]
    whole, rest = divmod(n_rows, FACTORED_ROW_TILE)
    acc = tuple(jnp.zeros((rows.shape[0], g_hi, g_lo), jnp.float32) for rows in (count_rows, sum_rows))
    # under shard_map the products vary over the mesh axes their operands
    # vary over, and a loop's carry has to from its first value on
    varying = frozenset().union(*(jax.typeof(x).vma for x in (group_ids, count_rows, sum_rows)))
    if varying:
        acc = jax.lax.pcast(acc, tuple(sorted(varying)), to="varying")
    if whole:
        acc = jax.lax.fori_loop(
            0, whole, lambda i, a: fold(a, i * FACTORED_ROW_TILE, FACTORED_ROW_TILE), acc
        )
    if rest:
        acc = fold(acc, whole * FACTORED_ROW_TILE, rest)
    return tuple(a.reshape(a.shape[0], g_hi * g_lo)[:, :num_groups] for a in acc)


@partial(jax.jit, static_argnames=("num_groups", "n_sum", "n_min", "n_max"))
def fused_groupby_block(
    group_ids: jnp.ndarray,  # int32 [N] in [0, num_groups)
    mask: jnp.ndarray,  # bool [N]
    sum_values: jnp.ndarray,  # float32 [n_sum, N]
    min_values: jnp.ndarray,  # float32 [n_min, N]
    max_values: jnp.ndarray,  # float32 [n_max, N]
    valid: jnp.ndarray,  # bool [n_all, N] per-agg-input validity
    num_groups: int,
    n_sum: int,
    n_min: int,
    n_max: int,
):
    """One block's complete partial aggregate in a single XLA program.

    Returns (count[G], per_agg_count[n_all,G], sums[n_sum,G], mins[n_min,G],
    maxs[n_max,G]).

    The additive reductions run as TWO one-hot matmuls on the MXU: the 0/1
    rows (count + per-agg counts) in bf16 x bf16 -> f32 (halves one-hot HBM
    traffic; 0/1 are exact in bf16) and the value sums in f32 x f32 -> f32.
    The one-hot generation is written so XLA can fuse it into each dot.
    Past the (N, G) one-hot's element budget the same two dots run against
    the one-hot factored in two (`_factored_additive`), and past
    FACTORED_MAX_GROUPS as scatter-based segment_sum; `fold_route` says
    which, and the readings that set its edges stand above the constants.
    The min/max reductions (not expressible as matmul) use scatter-based
    segment ops on every route.

    Precision: counts accumulate in f32 and are exact below 2^24 per block;
    sums are f32 x f32 at SUM_DOT_PRECISION with f32 accumulation and carry
    standard f32 error, matching segment_sum. A valid inf or NaN is its own
    group's sum alone on the factored and scatter routes; on the plain
    one-hot it meets the one-hot's zeros and every group of the block sums
    to NaN (as it always has; ROADMAP S10).
    """
    n_all = valid.shape[0]
    vmask = jnp.logical_and(valid, mask[None, :])
    additive = None  # (count, per_agg_count, sums) when a branch computed them

    pallas_mode = _pallas_mode()
    if pallas_mode and num_groups <= PALLAS_MAX_GROUPS:
        from parseable_tpu.ops.pallas_groupby import (
            ROW_TILE,
            additive_groupby_pallas,
        )

        if group_ids.shape[0] % ROW_TILE == 0:
            with jax.named_scope("pallas"):
                rows = jnp.concatenate(
                    [
                        mask[None, :].astype(jnp.float32),
                        vmask.astype(jnp.float32),
                        jnp.where(vmask[:n_sum], sum_values, 0.0),
                    ],
                    axis=0,
                )
                adds = additive_groupby_pallas(
                    group_ids, rows, num_groups, interpret=pallas_mode == "interpret"
                )
                additive = (adds[0], adds[1 : 1 + n_all], adds[1 + n_all :])

    route = fold_route(group_ids.shape[0], num_groups)
    if additive is not None:
        count, per_agg_count, sums = additive
    elif route == "onehot":
        with jax.named_scope("onehot_dot"):
            # Split-precision one-hot reduction: the 0/1 rows (count + per-agg
            # counts) ride a bf16 x bf16 -> f32 MXU dot — 0 and 1 are exactly
            # representable in bf16 and accumulation is f32, so counts stay
            # EXACT while the one-hot's HBM traffic halves (the gain is not
            # measured on today's code). The value sums use their own
            # independently-generated f32 one-hot: deriving it from the bf16
            # tensor (astype) gave the one-hot two consumers and forced XLA to
            # materialize it — each dot must be the sole consumer of its
            # operand for fusion.
            iota = jnp.arange(num_groups, dtype=jnp.int32)[None, :]
            onehot_bf16 = (group_ids[:, None] == iota).astype(jnp.bfloat16)
            count_rows = jnp.concatenate(
                [mask[None, :].astype(jnp.bfloat16), vmask.astype(jnp.bfloat16)], axis=0
            )
            count_adds = jax.lax.dot_general(
                count_rows, onehot_bf16, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            count = count_adds[0]
            per_agg_count = count_adds[1 : 1 + n_all]
            if n_sum:
                onehot_f32 = (group_ids[:, None] == iota).astype(jnp.float32)
                sum_rows = jnp.where(vmask[:n_sum], sum_values, 0.0)
                sums = jax.lax.dot_general(
                    sum_rows, onehot_f32, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=SUM_DOT_PRECISION,
                )
            else:
                sums = jnp.zeros((0, num_groups), jnp.float32)
    elif route == "factored":
        with jax.named_scope("factored_dot"):
            sum_rows = jnp.where(vmask[:n_sum], sum_values, 0.0)
            finite = jnp.isfinite(sum_rows)
            count_adds, sums = _factored_additive(
                group_ids,
                jnp.concatenate([mask[None, :], vmask], axis=0),
                jnp.where(finite, sum_rows, 0.0),
                num_groups,
            )
            count = count_adds[0]
            per_agg_count = count_adds[1:]
            if n_sum:
                # a valid inf or NaN times the zeros of its one-hot column
                # would be NaN in all 128 groups of its high row, where the
                # scatter this route took over from kept it to its own: the
                # product sums the finite addends, and a block that holds
                # such a value (a rare one) sums once more by scatter
                sums = jax.lax.cond(
                    jnp.all(finite),
                    lambda: sums,
                    lambda: jax.vmap(
                        lambda row: jax.ops.segment_sum(row, group_ids, num_segments=num_groups)
                    )(sum_rows),
                )
    else:
        with jax.named_scope("segment_sum"):
            count = jax.ops.segment_sum(
                mask.astype(jnp.float32), group_ids, num_segments=num_groups
            )
            per_agg_count = jax.vmap(
                lambda vm: jax.ops.segment_sum(
                    vm.astype(jnp.float32), group_ids, num_segments=num_groups
                )
            )(vmask)
            sums = (
                jax.vmap(
                    lambda vals, vm: jax.ops.segment_sum(
                        jnp.where(vm, vals, 0.0), group_ids, num_segments=num_groups
                    )
                )(sum_values, vmask[:n_sum])
                if n_sum
                else jnp.zeros((0, num_groups), jnp.float32)
            )

    def seg_min(vals, vm):
        return jax.ops.segment_min(jnp.where(vm, vals, F32_MAX), group_ids, num_segments=num_groups)

    def seg_max(vals, vm):
        return jax.ops.segment_max(jnp.where(vm, vals, -F32_MAX), group_ids, num_segments=num_groups)

    # a scope of its own, so that a device trace says what the one
    # reduction no one-hot product can carry costs (the executor counts the
    # blocks: `device_routes.fold_minmax_scatter_blocks`)
    with jax.named_scope("segment_minmax"):
        mins = (
            jax.vmap(seg_min)(min_values, vmask[n_sum : n_sum + n_min])
            if n_min
            else jnp.zeros((0, num_groups), jnp.float32)
        )
        maxs = (
            jax.vmap(seg_max)(max_values, vmask[n_sum + n_min : n_sum + n_min + n_max])
            if n_max
            else jnp.zeros((0, num_groups), jnp.float32)
        )
    return count, per_agg_count, sums, mins, maxs


