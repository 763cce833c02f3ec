"""Regressions for the device-path findings dlint surfaced and this PR
fixed — each test names the rule it pins down.

The static gate proves the *shape* of the discipline (annotated jit sites
riding a declared cache, syncs routed through declared boundaries, priced
transfers); these tests prove the *behavior*: warm queries build zero new
XLA programs, every readback and LUT ship lands in the byte accounting a
response reports, and the program-cache traffic is consumed from
stats.stages.programs.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from parseable_tpu.query import executor_tpu as ET
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql
from parseable_tpu.utils import metrics


def table(n=6_000, seed=0, groups=8):
    rng = np.random.default_rng(seed)
    return pa.table(
        {
            "g": pa.array([f"g{int(x)}" for x in rng.integers(0, groups, n)]),
            "v": pa.array(rng.random(n) * 100),
        }
    )


def run_tpu(sql: str, tables: list[pa.Table]):
    lp = build_plan(parse_sql(sql))
    ex = ET.TpuQueryExecutor(lp)
    out = ex.execute(iter(tables)).to_pylist()
    return out, ex


# ------------------------------------------------- jit-cache-discipline


def test_warm_agg_query_builds_zero_new_programs():
    """jit-cache-discipline: the dense-agg jit site rides _PROGRAM_CACHE —
    a warm query with identical shape classes must compile NOTHING new
    (this is the per-call-jit failure mode the rule and the P_DLINT
    tripwire both exist to block)."""
    t = table()
    sql = "SELECT g, count(v) c, avg(v) a FROM t GROUP BY g ORDER BY g"
    cold, _ = run_tpu(sql, [t])  # builds whatever keys are missing
    before = ET.PROGRAM_BUILDS[0]
    warm, ex = run_tpu(sql, [t])
    assert warm == cold
    assert ET.PROGRAM_BUILDS[0] == before, "warm query rebuilt a program"
    assert ex.route_stats["programs_built"] == 0
    assert ex.route_stats["programs_reused"] > 0
    assert ex.route_stats["recompiles"] == 0


def test_warm_topk_query_builds_zero_new_programs():
    """jit-cache-discipline, executor.topk program family."""
    t = table()
    sql = "SELECT g, count(v) c FROM t GROUP BY g ORDER BY c DESC LIMIT 3"
    cold, _ = run_tpu(sql, [t])
    before = ET.PROGRAM_BUILDS[0]
    warm, ex = run_tpu(sql, [t])
    assert warm == cold
    assert ET.PROGRAM_BUILDS[0] == before
    assert ex.route_stats["programs_built"] == 0
    assert ex.route_stats["recompiles"] == 0


def test_note_program_build_detects_rebuilt_keys():
    """The accounting under the tripwire's metric: rebuilding an
    already-built (program, key) ticks tpu_recompiles_total{program} and
    the route recompile counter; a fresh key does not."""
    program = "regress.note"

    def sample():
        return (
            metrics.REGISTRY.get_sample_value(
                "parseable_tpu_recompiles_total", {"program": program}
            )
            or 0.0
        )

    stats = {}
    base = sample()
    ET._note_program_build(program, ("k", 1), stats)
    assert sample() == base and stats.get("recompiles", 0) == 0
    ET._note_program_build(program, ("k", 2), stats)
    assert sample() == base  # second DISTINCT key: still no recompile
    ET._note_program_build(program, ("k", 1), stats)
    assert sample() == base + 1
    assert stats["recompiles"] == 1
    assert stats["programs_built"] == 3


# ------------------------------------------------------------- host-sync


def test_select_readback_is_priced_d2h():
    """host-sync: the filter-mask readback flows through _timed_readback
    (the declared sync boundary), so its bytes land in d2h accounting
    instead of an invisible np.asarray stall."""
    t = table()
    out, ex = run_tpu("SELECT g, v FROM t WHERE v > 50", [t])
    assert out, "filter should select roughly half the rows"
    assert ex.route_stats["d2h_bytes"] > 0


def test_timed_readback_prices_wire_bytes_at_device_width():
    """host-sync: wire bytes are priced at the DEVICE dtype width (capped
    at 4 — the layer is f32/int32/bool end to end) even when the host
    target is f64, and `dtype=None` keeps the device dtype."""
    jnp = pytest.importorskip("jax.numpy")
    x = jnp.ones((16,), dtype=jnp.float32)
    stats = {"d2h_bytes": 0}
    arr = ET._timed_readback(x, stats)
    assert arr.dtype == np.float64  # host representation promoted
    assert stats["d2h_bytes"] == 16 * 4  # ...but priced as f32 on the wire

    native = ET._timed_readback(jnp.arange(8, dtype=jnp.int32), None, dtype=None)
    assert native.dtype == np.int32


# ---------------------------------------------------- transfer-discipline


def test_group_lut_and_accumulator_ships_are_priced_h2d():
    """transfer-discipline: the group-LUT and accumulator device_put sites
    tick h2d route bytes and the tpu_bytes_to_device{op} counter —
    un-priced ships would be missing from the bytes a response reports."""

    def op_total(op):
        return (
            metrics.REGISTRY.get_sample_value(
                "parseable_tpu_bytes_to_device_total", {"op": op}
            )
            or 0.0
        )

    before = op_total("lut") + op_total("acc")
    t = table(seed=7)
    _, ex = run_tpu("SELECT g, sum(v) s FROM t GROUP BY g ORDER BY g", [t])
    assert ex.route_stats["h2d_bytes"] > 0
    assert op_total("lut") + op_total("acc") > before


# ------------------------------------------------------- stages.programs


def test_stages_programs_consumed_from_session(parseable):
    """The wlint stages-contract consumer for the new stages.programs
    entry: a TPU-engine query reports built/reused/recompiles (recompiles
    pinned at 0 — the tripwire budget), and the CPU engine reports None."""
    from datetime import datetime, timedelta

    from parseable_tpu import DEFAULT_TIMESTAMP_KEY
    from parseable_tpu.event import Event
    from parseable_tpu.query.session import QuerySession

    p = parseable
    stream = p.create_stream_if_not_exists("dlint_logs")
    rng = np.random.default_rng(3)
    base = datetime(2024, 6, 1)
    n = 4_000
    tbl = pa.table(
        {
            DEFAULT_TIMESTAMP_KEY: pa.array(
                [base + timedelta(milliseconds=int(i)) for i in range(n)],
                pa.timestamp("ms"),
            ),
            "host": pa.array([f"h{int(x)}" for x in rng.integers(0, 8, n)]),
            "bytes": pa.array(rng.random(n) * 100),
        }
    )
    for b in tbl.to_batches():
        Event(
            stream_name="dlint_logs", rb=b, origin_size=1, is_first_event=True,
            parsed_timestamp=base,
        ).process(stream, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()

    sql = "SELECT host, count(*) c FROM dlint_logs GROUP BY host ORDER BY host"
    res = QuerySession(p, engine="tpu").query(sql)
    prog = res.stats["stages"]["programs"]
    assert prog is not None
    assert set(prog) == {"built", "reused", "recompiles"}
    assert prog["built"] + prog["reused"] > 0
    assert prog["recompiles"] == 0

    cpu = QuerySession(p, engine="cpu").query(sql)
    assert cpu.stats["stages"]["programs"] is None
