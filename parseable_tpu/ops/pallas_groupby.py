"""Pallas TPU kernel for the additive group-by reduction.

An opt-in (P_TPU_USE_PALLAS=1) alternative to the XLA one-hot matmul in
`ops/kernels.py`: tiles of rows stream HBM -> VMEM, each tile builds its
one-hot on the fly in VMEM and accumulates `rows_tile @ onehot_tile` into a
VMEM accumulator on the MXU — the one-hot never round-trips to HBM, which
is the XLA version's main residual traffic at large G.

Correctness is pinned against the XLA kernel in tests through Pallas'
interpreter (`interpret=True`, passed explicitly); chip_smoke.py compiles
it with Mosaic on the chip and compares it with the XLA path there (the
1-D ids block, the `ids[:, None]` relayout and an R that is no multiple
of 8 all compile on a v5e with jax 0.9.0). Kept opt-in: which of the two
is faster is not measured on today's code (ROADMAP S5).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from parseable_tpu.ops.kernels import SUM_DOT_PRECISION

ROW_TILE = 2048  # rows per grid step (sublane-friendly multiple of 8)


def _additive_kernel(ids_ref, rows_ref, out_ref, *, num_groups: int):
    """One grid step: accumulate rows_tile @ onehot(ids_tile) into out.

    ids_ref:  int32 [ROW_TILE]      (VMEM)
    rows_ref: f32   [R, ROW_TILE]   (VMEM)
    out_ref:  f32   [R, num_groups] (VMEM accumulator; same block every
                                     step — first step initializes it)
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, (ROW_TILE, num_groups), 1)
    ids = ids_ref[...]  # load the tile, then index the VALUE (not the ref)
    onehot = (ids[:, None] == iota).astype(jnp.float32)
    partial_sum = jax.lax.dot_general(
        rows_ref[...], onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=SUM_DOT_PRECISION,  # f32 products; see kernels.py
    )
    first = pl.program_id(0) == 0
    out_ref[...] = jnp.where(first, partial_sum, out_ref[...] + partial_sum)


@partial(jax.jit, static_argnames=("num_groups", "interpret"))
def additive_groupby_pallas(
    group_ids: jnp.ndarray,  # int32 [N] (invalid rows -> any group, rows zeroed)
    rows: jnp.ndarray,  # f32 [R, N] (count/pac/sum rows, already masked)
    num_groups: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """rows @ onehot(group_ids) -> [R, num_groups], tiled over N."""
    r, n = rows.shape
    assert n % ROW_TILE == 0, (n, ROW_TILE)
    grid = (n // ROW_TILE,)
    return pl.pallas_call(
        partial(_additive_kernel, num_groups=num_groups),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_TILE,), lambda i: (i,)),
            pl.BlockSpec((r, ROW_TILE), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r, num_groups), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, num_groups), jnp.float32),
        interpret=interpret,
    )(group_ids, rows)
