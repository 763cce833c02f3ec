"""`execute_ms` split where the work happens (ISSUE 27): the phase clock of
`query/executor_tpu` behind `stats.stages.execute`, the spans under
`query.execute` and `http.request`, host spans as profiler annotations, the
names and scopes of the three device programs, and the readbacks that no
counter used to see.

Everything here runs the TPU engine on the CPU backend: it shows what the
program counts and names, never a time worth reporting."""

from __future__ import annotations

import asyncio
import base64
import contextlib
import re
import subprocess
import sys
from datetime import datetime, timedelta

import numpy as np
import pyarrow as pa
import pytest

from parseable_tpu.query import executor_tpu as ET
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql
from parseable_tpu.utils import metrics, telemetry

PHASE_KEYS = {f"{p}_ms" for p in ET.PHASES}
EXECUTE_KEYS = PHASE_KEYS | {"head_ms", "tail_ms", "blocks", "readbacks", "expr_nodes", "merge_device", "merge_entries", "merge_survivors"}


@pytest.fixture(autouse=True)
def _no_result_cache(monkeypatch):
    # no answer from the result cache
    monkeypatch.setenv("P_QUERY_RESULT_CACHE_BYTES", "0")
    telemetry.clear_recent_spans()
    yield
    telemetry.clear_recent_spans()


def load_stream(p, name: str, minutes: int = 2, rows: int = 3_000) -> None:
    """`minutes` parquet files (one device block each): 8 hosts, and 1,500 users that every minute draws from."""
    from parseable_tpu import DEFAULT_TIMESTAMP_KEY
    from parseable_tpu.event import Event

    stream = p.create_stream_if_not_exists(name)
    rng = np.random.default_rng(27)
    for m in range(minutes):
        base = datetime(2024, 6, 1, 0, m)
        tbl = pa.table(
            {
                DEFAULT_TIMESTAMP_KEY: pa.array(
                    [base + timedelta(milliseconds=int(i)) for i in range(rows)], pa.timestamp("ms")
                ),
                "host": pa.array([f"h{int(x)}" for x in rng.integers(0, 8, rows)]),
                "user": pa.array([f"u{int(x):05d}" for x in rng.integers(0, 1_500, rows)]),
                "bytes": pa.array(rng.integers(0, 100, rows).astype(np.float64)),
            }
        )
        for b in tbl.to_batches():
            Event(stream_name=name, rb=b, origin_size=1, is_first_event=m == 0, parsed_timestamp=base).process(
                stream, commit_schema=p.commit_schema
            )
    p.local_sync(shutdown=True)
    p.sync_all_streams()


def lower(monkeypatch, thresholds: dict) -> None:
    """Lower the executor's thresholds so that a test's small table takes the path of a large one."""
    for attr, value in thresholds.items():
        monkeypatch.setattr(ET.TpuQueryExecutor if attr == "TOPK_MIN_GROUPS" else ET, attr, value)


# (SQL, what to lower on the executor, the span that reads the result back, readbacks per query as f(blocks))
PATHS = {
    "dense": ("SELECT host, count(*) c, sum(bytes) s FROM {s} GROUP BY host", {}, "execute.readback", lambda blocks: 1),
    "local": ("SELECT user, count(*) c, sum(bytes) s FROM {s} GROUP BY user", {"DENSE_G_MAX": 1 << 9}, "execute.merge",
              lambda blocks: blocks),
    "topk": ("SELECT user, sum(bytes) s FROM {s} GROUP BY user ORDER BY s DESC LIMIT 5", {"TOPK_MIN_GROUPS": 64},
             "execute.topk", lambda blocks: 2),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_stages_execute_on_each_path(parseable, monkeypatch, path):
    """(a) every key is there and at least 0, `blocks` and `readbacks` are what ran, the
    phases' sum stays inside `execute_ms`, and the path's own span lies under `query.execute`."""
    from parseable_tpu.query.session import QuerySession

    sql, thresholds, span_name, want_readbacks = PATHS[path]
    lower(monkeypatch, thresholds)
    load_stream(parseable, "phases")
    with telemetry.trace_context() as trace_id:
        res = QuerySession(parseable, engine="tpu").query(sql.format(s="phases"))
    assert res.table.num_rows > 0
    stages = res.stats["stages"]
    ex = stages["execute"]
    assert set(ex) == EXECUTE_KEYS
    assert all(ex[k] >= 0 for k in EXECUTE_KEYS), ex
    routes = res.stats["device_routes"]
    assert routes["cpu_fallback"] == 0 and routes["cpu_adaptive"] == 0
    assert ex["blocks"] == routes["device_warm"] + routes["device_cold"] == 2
    assert ex["readbacks"] == want_readbacks(ex["blocks"])
    assert ex["device_wait_ms"] + ex["readback_ms"] > 0 and ex["dispatch_ms"] > 0
    # eight values rounded to the microsecond, and execute_ms once
    assert sum(ex[k] for k in PHASE_KEYS) <= stages["execute_ms"] + 0.005
    assert ex["head_ms"] <= stages["execute_ms"] + stages["scan_ms"] + 0.002
    assert (ex["merge_ms"] > 0) == (path == "local")
    # the counters stay what they were: no clock among device_routes
    assert not [k for k in routes if k.endswith("_ns") or k in ("blocks", "readbacks")]

    spans = {s["name"]: s for s in telemetry.recent_spans(trace_id)}
    under_execute = {"execute.blocks", span_name, "execute.finalize"}
    assert under_execute <= set(spans)
    assert {spans[n]["parent_span_id"] for n in under_execute} == {spans["query.execute"]["span_id"]}
    assert spans["execute.blocks"]["rows"] == ex["blocks"]
    assert len(telemetry.recent_spans(trace_id)) <= 12


def test_cpu_engine_reports_no_execute_split(parseable):
    from parseable_tpu.query.session import QuerySession

    load_stream(parseable, "phases_cpu", minutes=1)
    res = QuerySession(parseable, engine="cpu").query("SELECT host, count(*) c FROM phases_cpu GROUP BY host")
    assert res.stats["stages"]["execute"] is None and res.stats["stages"]["execute_ms"] >= 0


def test_phase_seconds_reach_the_metrics_once_a_query():
    """`parseable_tpu_execute_phase_seconds_total{phase}` grows by exactly the query's finished clock."""

    def total(phase: str) -> float:
        return metrics.REGISTRY.get_sample_value("parseable_tpu_execute_phase_seconds_total", {"phase": phase}) or 0.0

    before = {p: total(p) for p in ET.PHASES}
    rng = np.random.default_rng(5)
    t = pa.table({"g": pa.array([f"g{int(x)}" for x in rng.integers(0, 8, 5_000)]), "v": pa.array(rng.random(5_000))})
    ex = ET.TpuQueryExecutor(build_plan(parse_sql("SELECT g, sum(v) s FROM t GROUP BY g")))
    assert ex.execute(iter([t])).num_rows == 8
    for phase in ET.PHASES:
        assert total(phase) - before[phase] == pytest.approx(ex.route_stats.ns[phase] / 1e9, abs=1e-9)
    assert ex.route_stats.ns["dispatch"] > 0 and ex.route_stats.ns["merge"] == 0


# ------------------------------------------------------------ (b) the spans of one served request

AUTH = {"Authorization": "Basic " + base64.b64encode(b"admin:admin").decode()}


def test_one_served_query_records_the_named_spans_under_their_parents(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from parseable_tpu.config import Options, StorageOptions
    from parseable_tpu.core import Parseable
    from parseable_tpu.server.app import ServerState, build_app

    opts = Options()
    opts.local_staging_path = tmp_path / "staging"
    opts.query_engine = "tpu"
    state = ServerState(Parseable(opts, StorageOptions(backend="local-store", root=tmp_path / "data")))

    async def drive():
        client = TestClient(TestServer(build_app(state)))
        await client.start_server()
        try:
            rows = [{"host": f"h{i % 4}", "bytes": float(i)} for i in range(64)]
            r = await client.post("/api/v1/ingest", json=rows, headers={**AUTH, "X-P-Stream": "served"})
            assert r.status == 200
            for _ in range(2):  # the first also flushes the rows just ingested; the second is a request as a window has them
                r = await client.post(
                    "/api/v1/query", headers=AUTH,
                    json={"query": "SELECT host, count(*) c, sum(bytes) s FROM served GROUP BY host", "fields": True},
                )
            body = await r.json()
            assert r.status == 200 and len(body["records"]) == 4
            assert body["stats"]["stages"]["execute"]["blocks"] == 1
            trace_id = r.headers["X-P-Trace-Id"]
            r = await client.get(f"/api/v1/debug/spans?trace_id={trace_id}", headers=AUTH)
            return (await r.json())["spans"]
        finally:
            await client.close()
            state.stop()

    spans = asyncio.new_event_loop().run_until_complete(drive())
    by_name = {s["name"]: s for s in spans}
    parents = {
        "query": "http.request", "response.encode": "http.request",
        "query.parse": "query", "query.plan": "query", "query.execute": "query",
        "execute.blocks": "query.execute", "execute.readback": "query.execute", "execute.finalize": "query.execute",
    }
    assert set(parents) | {"http.request"} <= set(by_name)
    for child, parent in parents.items():
        assert by_name[child]["parent_span_id"] == by_name[parent]["span_id"], child
    assert by_name["execute.readback"]["bytes"] > 0
    roots, orphans = telemetry.build_span_tree(spans)
    assert orphans == 0 and [r["name"] for r in roots] == ["http.request"]
    assert len(spans) <= 12  # the ring holds 4,096 rows for a window of 131 requests


# ------------------------------------------------------------ (c) host spans on the profiler's clock


def test_a_span_enters_a_trace_annotation_with_its_name_and_ids(monkeypatch):
    import jax.profiler

    seen: list = []

    class Annotation:
        def __init__(self, name, **kwargs):
            self.what = (name, kwargs)

        def __enter__(self):
            seen.append(("enter", *self.what))

        def __exit__(self, *exc):
            seen.append(("exit", *self.what))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with telemetry.trace_context() as trace_id:
        with telemetry.TRACER.span("query"):
            with telemetry.TRACER.span("query.execute"):
                pass
    rows = {s["name"]: s for s in telemetry.recent_spans(trace_id)}
    ids = {n: {"trace_id": trace_id, "span_id": rows[n]["span_id"]} for n in rows}
    assert seen == [
        ("enter", "query", ids["query"]), ("enter", "query.execute", ids["query.execute"]),
        ("exit", "query.execute", ids["query.execute"]), ("exit", "query", ids["query"]),
    ]
    # nothing consumes, nothing is annotated
    seen.clear()
    with telemetry.TRACER.span("idle"):
        pass
    assert seen == []


def test_a_span_imports_no_jax():
    code = (
        "import sys\n"
        "from parseable_tpu.utils import telemetry\n"
        "with telemetry.trace_context() as t:\n"
        "    with telemetry.TRACER.span('query'):\n"
        "        pass\n"
        "assert [s['name'] for s in telemetry.recent_spans(t)] == ['query']\n"
        "assert not [m for m in sys.modules if m == 'jax' or m.startswith('jax.')], 'a span imported jax'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


# ------------------------------------------------------------ (d) names on the device side


def scope_in(text: str, scope: str) -> bool:
    return re.search(rf'["/]{scope}[/"]', text) is not None


def key_table(n: int, keys: int, seed: int) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({"k": pa.array([f"k{int(x):05d}" for x in rng.integers(0, keys, n)]), "v": pa.array(rng.random(n) * 100)})


# program -> (SQL, rows, keys, module attributes to lower, environment, scopes its lowered text must hold)
PROGRAMS = {
    "executor_dense": ("SELECT k, count(*) c, sum(v) s, stddev(v) d FROM t WHERE v > 1 GROUP BY k", 4_096, 8, {}, {},
                       ["where", "keys", "fold", "onehot_dot", "m2"]),
    "executor_dense-pallas": ("SELECT k, count(*) c, sum(v) s FROM t WHERE v > 1 GROUP BY k", 4_096, 8, {},
                              {"P_TPU_USE_PALLAS": "interpret"}, ["where", "keys", "fold", "pallas"]),
    "executor_local": ("SELECT k, count(*) c, sum(v) s FROM t WHERE v > 1 GROUP BY k", 8_192, 3_000, {"DENSE_G_MAX": 1 << 9}, {},
                       ["where", "keys", "fold", "segment_sum"]),
    "executor_topk": ("SELECT k, sum(v) s FROM t GROUP BY k ORDER BY s DESC LIMIT 5", 4_096, 300, {"TOPK_MIN_GROUPS": 64}, {},
                      ["topk"]),
}


@pytest.mark.parametrize("case", sorted(PROGRAMS))
def test_the_programs_carry_their_names_and_scopes_and_the_scopes_change_no_operation(monkeypatch, case):
    import jax

    from parseable_tpu.ops import kernels, pallas_groupby  # noqa: F401  (its jit sites bind before `jax.jit` is patched)

    program = case.split("-")[0]
    sql, rows, keys, thresholds, env, scopes = PROGRAMS[case]
    lower(monkeypatch, thresholds)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    # this test's programs are built anew and leave nothing behind in the process-wide caches
    monkeypatch.setattr(ET, "_PROGRAM_CACHE", {})
    monkeypatch.setattr(ET, "_PROGRAM_KEYS_BUILT", set())
    kernels.fused_groupby_block.clear_cache()
    real_jit, built = jax.jit, {}

    def recording_jit(fn, *a, **kw):
        jitted = real_jit(fn, *a, **kw)

        def call(*args):
            built.setdefault(fn.__name__, (fn, args))
            return jitted(*args)

        return call

    monkeypatch.setattr(jax, "jit", recording_jit)
    try:
        ex = ET.TpuQueryExecutor(build_plan(parse_sql(sql)))
        ex.mesh = None  # one device, as the benchmark's cells run (the tests' eight virtual devices would make a mesh)
        out = ex.execute(iter([key_table(rows, keys, seed=len(case))]))
        assert out.num_rows > 0 and program in built
        fn, args = built[program]
        scoped = real_jit(fn).lower(*args)
        text = scoped.as_text(debug_info=True)
        assert f"jit_{program}" in text
        assert [s for s in scopes if not scope_in(text, s)] == []

        # the same body with every scope a null context: other debug info, the same operations
        def clone(*xs):
            return fn(*xs)

        clone.__name__ = clone.__qualname__ = fn.__name__
        monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        kernels.fused_groupby_block.clear_cache()
        bare = real_jit(clone).lower(*args)
        assert [s for s in scopes if scope_in(bare.as_text(debug_info=True), s)] == []
        assert bare.as_text() == scoped.as_text()
    finally:
        kernels.fused_groupby_block.clear_cache()  # what was traced under this test's patches goes with them


# ------------------------------------------------------------ a counted readback


def stripped_block(rows: int = 1_024):
    """A hot-set entry as a warm query meets it: codes on the device, the host copy stripped."""
    import jax.numpy as jnp

    from parseable_tpu.ops.device import encode_table

    enc = encode_table(key_table(rows, 50, seed=3), {"k"}, dict_columns={"k"})
    dev, _ = ET._transfer(enc)
    width = min(np.dtype(dev["k"].dtype).itemsize, 4)
    ET._strip_host_values(enc)
    assert len(enc.columns["k"].values) == 0 and isinstance(dev["k"], jnp.ndarray)
    return enc, dev, enc.block_rows * width


def test_a_stripped_key_column_read_back_is_counted():
    enc, dev, wire = stripped_block()
    stats = ET.RouteStats()
    codes = ET.TpuQueryExecutor._host_codes(enc, dev, "k", stats)
    assert codes.shape == (enc.block_rows,) and codes.dtype == np.asarray(dev["k"]).dtype
    assert stats["d2h_bytes"] == wire and stats.readbacks == 1
    assert stats.ns["readback"] > 0 and stats.last_readback_ns > 0


def test_a_sparse_histogram_reads_a_probe_and_its_active_bins():
    """The histogram read: one occupancy probe, then the gather of the active bins, both counted and clocked."""
    import jax.numpy as jnp

    ex = ET.TpuQueryExecutor(build_plan(parse_sql("SELECT count(*) c FROM t")))
    ex.mesh = None  # a mesh reads the whole histogram at once
    groups = (1 << 20) // ET.DEVICE_NB + 1
    hist = jnp.zeros(groups * ET.DEVICE_NB, jnp.float32).at[7].set(3.0)
    out = ex._read_hist(hist, groups)
    assert out.shape == (groups, ET.DEVICE_NB) and out[0, 7] == 3.0 and out.sum() == 3.0
    assert ex.route_stats.readbacks == 2
    assert ex.route_stats["d2h_bytes"] == ET.DEVICE_NB * 4 + groups * 4
    assert ex.route_stats.ns["readback"] > 0 and ex.route_stats.last_readback_ns > 0
