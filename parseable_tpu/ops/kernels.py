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
  partials land in [num_groups]-sized accumulators via segment_sum — which
  XLA lowers to efficient one-hot matmuls on the MXU for small G and
  scatter-adds for large G;
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


# Above this group count the one-hot matmul's N*G work loses to scatter
MATMUL_MAX_GROUPS = 8192

# The one-hot operand may MATERIALIZE (N, G) when XLA declines to fuse it
# into the dot; bound its footprint (elements) or take the scatter path —
# a 1M-row block at G=8192 is a 16 GB bf16 tensor otherwise (observed as a
# CPU-backend OOM and as memory-bound slowness on chip)
MATMUL_MAX_ONEHOT_ELEMS = 1 << 30


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
    This is the design's hot loop; its speed against scatter-based
    segment_sum is not measured on today's code. Groups beyond
    MATMUL_MAX_GROUPS and the min/max reductions (not expressible as
    matmul) use scatter-based segment ops.

    Precision: counts accumulate in f32 and are exact below 2^24 per block;
    sums are f32 x f32 at SUM_DOT_PRECISION with f32 accumulation and carry
    standard f32 error, matching segment_sum.
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

    n_rows = group_ids.shape[0]
    # the one-hot dot is the MXU's fast path; every other backend (the
    # virtual CPU mesh, the dryrun) lacks a systolic array and pays the
    # full (N, G) materialization — scatter wins there beyond tiny shapes
    max_onehot = (
        MATMUL_MAX_ONEHOT_ELEMS
        if jax.default_backend() == "tpu"
        else min(MATMUL_MAX_ONEHOT_ELEMS, 1 << 22)
    )
    if additive is not None:
        count, per_agg_count, sums = additive
    elif (
        num_groups <= MATMUL_MAX_GROUPS
        and n_rows * num_groups <= max_onehot
    ):
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


