"""Distributed query execution over a TPU mesh.

The reference scales queries by fanning out over querier/ingestor nodes and
merging JSON results host-side (reference: handlers/http/cluster/mod.rs
round-robin + stream_schema_provider.rs snapshot merge). The TPU-native
replacement keeps object storage as the rendezvous but turns the *aggregate
merge* into XLA collectives over the chip mesh:

- rows (the time/sequence axis of a log store) shard across the `data` mesh
  axis — each device computes a dense partial aggregate for its row shard
  with the same fused kernel the single-chip path uses;
- partials combine with `psum` / `pmin` / `pmax` over ICI — the reduction
  tree the reference does in host loops happens in hardware;
- for very large group spaces the `groups` axis shards the accumulator
  (each device owns G/n_groups buckets) — psum over `data`, no collective
  over `groups`, then an all_gather only at finalize.

Used by: executor_tpu (when a mesh is configured), __graft_entry__'s
dryrun_multichip, and the distributed benchmark config.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from parseable_tpu.ops import kernels


def make_mesh(n_devices: int | None = None, axis: str = "data") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def make_mesh_2d(n_data: int, n_groups: int) -> Mesh:
    devs = np.array(jax.devices()[: n_data * n_groups]).reshape(n_data, n_groups)
    return Mesh(devs, ("data", "groups"))


def shard_rows(mesh: Mesh, *arrays: jnp.ndarray):
    """Place [N, ...] arrays row-sharded over the data axis."""
    out = []
    for a in arrays:
        spec = P("data") if a.ndim == 1 else P(None, "data")
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


def distributed_groupby(
    mesh: Mesh,
    num_groups: int,
    n_sum: int,
    n_min: int,
    n_max: int,
):
    """Build the sharded partial-aggregate step for a fixed plan shape.

    Inputs are row-sharded over `data`; the output partials are fully
    replicated (psum/pmin/pmax over ICI). jit-compiled once per
    (block, groups) shape bucket.
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P("data"),  # group_ids
            P("data"),  # mask
            P(None, "data"),  # sum_values
            P(None, "data"),  # min_values
            P(None, "data"),  # max_values
            P(None, "data"),  # valid
        ),
        out_specs=(P(), P(), P(), P(), P()),
    )
    def step(group_ids, mask, sum_values, min_values, max_values, valid):
        count, pac, sums, mins, maxs = kernels.fused_groupby_block(
            group_ids, mask, sum_values, min_values, max_values, valid,
            num_groups, n_sum, n_min, n_max,
        )
        count = jax.lax.psum(count, "data")
        pac = jax.lax.psum(pac, "data")
        sums = jax.lax.psum(sums, "data")
        mins = jax.lax.pmin(mins, "data")
        maxs = jax.lax.pmax(maxs, "data")
        return count, pac, sums, mins, maxs

    return jax.jit(step)


def distributed_groupby_2d(
    mesh: Mesh,
    groups_per_shard: int,
    n_sum: int,
    n_min: int,
    n_max: int,
):
    """2D variant: rows shard over `data`, the group space shards over
    `groups` (each device owns `groups_per_shard` buckets). Rows outside a
    device's bucket range are masked instead of routed — with G large this
    trades an all-to-all for recompute-free masking, and the only collective
    is the psum over `data`.
    """
    n_group_shards = mesh.shape["groups"]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P(None, "data"), P(None, "data"), P(None, "data"), P(None, "data")),
        out_specs=(P("groups"), P(None, "groups"), P(None, "groups"), P(None, "groups"), P(None, "groups")),
    )
    def step(group_ids, mask, sum_values, min_values, max_values, valid):
        shard = jax.lax.axis_index("groups")
        lo = shard * groups_per_shard
        local_ids = group_ids - lo
        in_shard = (local_ids >= 0) & (local_ids < groups_per_shard)
        local_ids = jnp.clip(local_ids, 0, groups_per_shard - 1)
        m = mask & in_shard
        count, pac, sums, mins, maxs = kernels.fused_groupby_block(
            local_ids, m, sum_values, min_values, max_values, valid,
            groups_per_shard, n_sum, n_min, n_max,
        )
        return (
            jax.lax.psum(count, "data"),
            jax.lax.psum(pac, "data"),
            jax.lax.psum(sums, "data"),
            jax.lax.pmin(mins, "data"),
            jax.lax.pmax(maxs, "data"),
        )

    return jax.jit(step)


def full_query_step(mesh: Mesh, num_groups: int):
    """One complete sharded "training step" of the query engine: predicate
    mask -> dense group ids -> fused partial aggregate -> psum tree.

    This is what `__graft_entry__.dryrun_multichip` compiles over an
    n-device mesh: it exercises the real sharding layout end to end
    (row-sharded inputs, replicated partials).
    """

    def step(rel_time, status_codes, host_codes, lut, bin_units, num_host, values, valid):
        mask = kernels.lut_mask(host_codes, lut)
        bins = rel_time // bin_units
        ids = (bins * num_host + jnp.minimum(host_codes, num_host - 1)).astype(jnp.int32)
        ids = jnp.clip(ids, 0, num_groups - 1)
        count, pac, sums, mins, maxs = kernels.fused_groupby_block(
            ids,
            mask,
            values[None, :],
            jnp.zeros((0,) + values.shape, jnp.float32),
            jnp.zeros((0,) + values.shape, jnp.float32),
            valid[None, :],
            num_groups,
            1,
            0,
            0,
        )
        return count, sums

    sharded = shard_map(
        lambda *a: tuple(
            jax.lax.psum(o, "data") for o in step(*a)
        ),
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(None), None, None, P("data"), P("data")),
        out_specs=(P(), P()),
    )
    return jax.jit(sharded, static_argnums=(4, 5))
