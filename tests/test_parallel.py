"""Distributed mesh tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parseable_tpu.ops import kernels
from parseable_tpu.parallel.mesh import (
    distributed_groupby,
    distributed_groupby_2d,
    make_mesh,
    make_mesh_2d,
    shard_rows,
)


def _inputs(n=1024, g=16, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, g, n).astype(np.int32)
    mask = rng.random(n) < 0.8
    vals = rng.random((1, n)).astype(np.float32)
    valid = np.ones((1, n), dtype=bool)
    return ids, mask, vals, valid


def test_devices_available():
    assert len(jax.devices()) == 8


def test_distributed_groupby_matches_single():
    n, g = 4096, 32
    ids, mask, vals, valid = _inputs(n, g)
    single = kernels.fused_groupby_block(
        jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(vals),
        jnp.zeros((0, n), jnp.float32), jnp.zeros((0, n), jnp.float32),
        jnp.asarray(valid), g, 1, 0, 0,
    )
    mesh = make_mesh(8)
    step = distributed_groupby(mesh, g, 1, 0, 0)
    sids, smask, svals, svalid = shard_rows(
        mesh, jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(vals), jnp.asarray(valid)
    )
    dist = step(sids, smask, svals, jnp.zeros((0, n), jnp.float32), jnp.zeros((0, n), jnp.float32), svalid)
    np.testing.assert_allclose(np.asarray(single[0]), np.asarray(dist[0]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(single[2]), np.asarray(dist[2]), rtol=1e-5)


def test_distributed_groupby_min_max():
    n, g = 2048, 8
    ids, mask, vals, valid = _inputs(n, g, seed=1)
    mesh = make_mesh(8)
    step = distributed_groupby(mesh, g, 0, 1, 1)
    empty = jnp.zeros((0, n), jnp.float32)
    sids, smask, svals, svalid = shard_rows(
        mesh, jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(vals),
        jnp.asarray(np.concatenate([valid, valid])),
    )
    count, pac, sums, mins, maxs = step(sids, smask, empty, svals, svals, svalid)
    # reference on host
    ref_min = np.full(g, np.inf)
    ref_max = np.full(g, -np.inf)
    for i in range(n):
        if mask[i]:
            ref_min[ids[i]] = min(ref_min[ids[i]], vals[0, i])
            ref_max[ids[i]] = max(ref_max[ids[i]], vals[0, i])
    got_min = np.asarray(mins[0])
    got_max = np.asarray(maxs[0])
    present = np.asarray(count) > 0
    np.testing.assert_allclose(ref_min[present], got_min[present], rtol=1e-5)
    np.testing.assert_allclose(ref_max[present], got_max[present], rtol=1e-5)


def test_distributed_groupby_2d_shards_group_space():
    n, g = 4096, 64
    shards = 4
    per = g // shards
    ids, mask, vals, valid = _inputs(n, g, seed=2)
    mesh = make_mesh_2d(2, shards)
    step = distributed_groupby_2d(mesh, per, 1, 0, 0)
    from jax.sharding import NamedSharding, PartitionSpec as P

    put = lambda a, spec: jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))
    out = step(
        put(ids, P("data")),
        put(mask, P("data")),
        put(vals, P(None, "data")),
        put(np.zeros((0, n), np.float32), P(None, "data")),
        put(np.zeros((0, n), np.float32), P(None, "data")),
        put(valid, P(None, "data")),
    )
    count = np.asarray(out[0])
    assert count.shape == (g,)
    ref = np.zeros(g)
    for i in range(n):
        if mask[i]:
            ref[ids[i]] += 1
    np.testing.assert_allclose(count, ref)


def test_pallas_groupby_opt_in_parity(monkeypatch):
    """P_TPU_USE_PALLAS routes the additive reduction through the pallas
    kernel — through the Pallas interpreter, which the test asks for
    explicitly — with results matching the XLA path."""
    import numpy as np
    import jax.numpy as jnp

    import parseable_tpu.ops.kernels as K

    rng = np.random.default_rng(0)
    n, g = 4096, 128
    ids = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.9)
    sums = jnp.asarray(rng.random((1, n)).astype(np.float32))
    mins = jnp.asarray(rng.random((1, n)).astype(np.float32))
    empty = jnp.zeros((0, n), jnp.float32)
    valid = jnp.ones((2, n), bool)

    base = K.fused_groupby_block(ids, mask, sums, mins, empty, valid, g, 1, 1, 0)
    monkeypatch.setenv("P_TPU_USE_PALLAS", "interpret")
    K.fused_groupby_block.clear_cache()
    try:
        pal = K.fused_groupby_block(ids, mask, sums, mins, empty, valid, g, 1, 1, 0)
    finally:
        monkeypatch.delenv("P_TPU_USE_PALLAS")
        K.fused_groupby_block.clear_cache()
    for a, b in zip(base, pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
