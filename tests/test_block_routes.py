"""A block has two routes: it is folded on the device (`device_cold` the first time, `device_warm` from the hot
set after), or it was declared `UnsupportedOnDevice` and is a counted `cpu_fallback`. Nothing prices a link, nothing
warms in the background, nothing is written beside the data (ISSUE 32 took the third route out). `cpu_adaptive` stays
in `device_routes` as a constant 0: the benchmark's readers sum the four keys."""

from __future__ import annotations

import ast
import asyncio
import base64
import json
import subprocess
import sys
import threading
from datetime import datetime, timedelta
from pathlib import Path

import numpy as np
import pyarrow as pa
import pytest

from parseable_tpu.config import Options, StorageOptions
from parseable_tpu.ops.hotset import DeviceHotSet, HotEntry, get_hotset
from parseable_tpu.query import executor_tpu as ET
from parseable_tpu.query.executor import QueryExecutor
from parseable_tpu.query.planner import plan as build_plan
from parseable_tpu.query.sql import parse_sql

ROOT = Path(__file__).resolve().parent.parent
ROUTES = ("device_warm", "device_cold", "cpu_adaptive", "cpu_fallback")
BLOCK_ROWS = 1 << 16  # the least the old gate would route


# ------------------------------------------------------------ (a) a link that was measured slow sends no block anywhere


def blocks(tag: str, n_blocks: int = 2) -> list[pa.Table]:
    """Blocks of 65,536 rows, kept apart by a source id as scanned parquet files are: 64 hosts, 3,000 users."""
    rng = np.random.default_rng(32)
    return [
        pa.table(
            {
                "host": pa.array([f"h{int(x):02d}" for x in rng.integers(0, 64, BLOCK_ROWS)]),
                "user": pa.array([f"u{int(x):04d}" for x in rng.integers(0, 3_000, BLOCK_ROWS)]),
                "v": pa.array(rng.integers(0, 100, BLOCK_ROWS).astype(np.float64)),
            }
        ).replace_schema_metadata({ET.SOURCE_ID_META: f"routes-{tag}-{b}".encode()})
        for b in range(n_blocks)
    ]


def plant_a_slow_link(staging: Path) -> None:
    """What the link-adaptive routing kept per staging directory, stamped for the running device: 1 KB/s, 1 s a put. At the
    parent commit this file alone sent every cold block of 65,536 rows to the CPU engine."""
    import jax

    d = jax.local_devices()[0]
    staging.mkdir(parents=True, exist_ok=True)
    (staging / "link_profile.json").write_text(
        json.dumps(
            {
                "h2d_bw": 1e3, "h2d_lat": 1.0, "d2h_bw": 1e3, "d2h_lat": 1.0,
                "cpu_rows_per_sec": 2.0e7, "cpu_filter_rows_per_sec": 4.0e7,
                "device": f"{d.platform}/{d.device_kind}",
            }
        )
    )


# (SQL, the executor's thresholds to lower, the columns whose floats the device sums in f32)
SHAPES = {
    "dense_aggregate": ("SELECT host, count(*) c, sum(v) s FROM t GROUP BY host", {}, ("s",)),
    "block_local_aggregate": ("SELECT user, count(*) c, sum(v) s FROM t GROUP BY user", {"DENSE_G_MAX": 1 << 10}, ("s",)),
    "filtered_select": ("SELECT host, user, v FROM t WHERE v > 90.0", {}, ()),
}


def same_answer(got: list[dict], want: list[dict], f32_cols: tuple) -> None:
    key = lambda r: tuple(str(v) for k, v in sorted(r.items()) if k not in f32_cols)  # noqa: E731
    got, want = sorted(got, key=key), sorted(want, key=key)
    assert len(got) == len(want) and len(want) > 0
    for g, w in zip(got, want, strict=True):
        assert key(g) == key(w)
        for c in f32_cols:
            assert g[c] == pytest.approx(w[c], rel=1e-5)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_cold_block_goes_to_the_device_whatever_a_stored_link_profile_says(tmp_path, monkeypatch, shape):
    sql, thresholds, f32_cols = SHAPES[shape]
    for attr, value in thresholds.items():
        monkeypatch.setattr(ET, attr, value)
    monkeypatch.setenv("P_TPU_ADAPTIVE", "1")  # a dead name: read by nothing
    monkeypatch.setenv("P_TPU_BLOCK_ROWS", str(BLOCK_ROWS))
    opts = Options()
    opts.local_staging_path = tmp_path / "staging"
    plant_a_slow_link(opts.local_staging_path)
    tables = blocks(f"{shape}-{tmp_path.name}")
    want = QueryExecutor(build_plan(parse_sql(sql))).execute(iter(tables)).to_pylist()
    for route in ("device_cold", "device_warm"):
        ex = ET.TpuQueryExecutor(build_plan(parse_sql(sql)), opts)
        ex.mesh = None  # one device, as the benchmark's one-chip cells run
        got = ex.execute(iter(tables)).to_pylist()
        rs = ex.route_stats
        assert {k: rs[k] for k in ROUTES} == {**dict.fromkeys(ROUTES, 0), route: len(tables)}, dict(rs)
        assert rs.blocks == (0 if shape == "filtered_select" else len(tables))  # a SELECT's loop counts none
        same_answer(got, want, f32_cols)
    assert not [t for t in threading.enumerate() if t.name == "device-warmer"]


# ------------------------------------------------------------ (b) the four keys of a response add up to its blocks


def load_stream(p, name: str, minutes: int = 2, rows: int = 3_000) -> None:
    """`minutes` parquet files, one device block each."""
    from parseable_tpu import DEFAULT_TIMESTAMP_KEY
    from parseable_tpu.event import Event

    stream = p.create_stream_if_not_exists(name)
    rng = np.random.default_rng(33)
    for m in range(minutes):
        base = datetime(2024, 6, 1, 0, m)
        tbl = pa.table(
            {
                DEFAULT_TIMESTAMP_KEY: pa.array([base + timedelta(milliseconds=int(i)) for i in range(rows)], pa.timestamp("ms")),
                "host": pa.array([f"h{int(x)}" for x in rng.integers(0, 8, rows)]),
                "bytes": pa.array(rng.integers(0, 100, rows).astype(np.float64)),
                # whole seconds in the first file, a microsecond residue in the second: ops/device.py declines that one
                "seen": pa.array(np.arange(rows, dtype=np.int64) * 1_000_000 + (7 if m else 0), pa.timestamp("us")),
            }
        )
        for b in tbl.to_batches():
            Event(stream_name=name, rb=b, origin_size=1, is_first_event=m == 0, parsed_timestamp=base).process(
                stream, commit_schema=p.commit_schema
            )
        p.local_sync(shutdown=True)
    p.sync_all_streams()


RESPONSES = {
    "every_block_on_the_device": ("SELECT host, count(*) c, sum(bytes) s FROM {s} GROUP BY host", 0),
    "one_block_declared_unsupported": (
        "SELECT host, count(*) c, sum(bytes) s FROM {s} WHERE seen >= '1970-01-01T00:00:00Z' GROUP BY host", 1,
    ),
}


@pytest.mark.parametrize("case", sorted(RESPONSES))
def test_a_responses_four_route_keys_add_up_to_its_blocks(parseable, monkeypatch, case):
    from parseable_tpu.query.session import QuerySession

    monkeypatch.setenv("P_QUERY_RESULT_CACHE_BYTES", "0")
    sql, on_cpu = RESPONSES[case]
    load_stream(parseable, "routes")
    res = QuerySession(parseable, engine="tpu").query(sql.format(s="routes"))
    want = QuerySession(parseable, engine="cpu").query(sql.format(s="routes"))
    routes = res.stats["device_routes"]
    assert set(ROUTES) <= set(routes)
    assert routes["cpu_adaptive"] == 0 and routes["cpu_fallback"] == on_cpu
    assert sum(routes[k] for k in ROUTES) == res.stats["stages"]["execute"]["blocks"] == 2
    same_answer(res.to_json_rows(), want.to_json_rows(), ("s",))


# ------------------------------------------------------------ (c) a served process: no warmer, no file beside the data

AUTH = {"Authorization": "Basic " + base64.b64encode(b"admin:admin").decode()}


@pytest.mark.parametrize("engine", ["tpu", "cpu"])
def test_a_served_node_starts_no_warmer_and_writes_no_link_profile(tmp_path, engine):
    from aiohttp.test_utils import TestClient, TestServer

    from parseable_tpu.core import Parseable
    from parseable_tpu.server.app import ServerState, build_app

    opts = Options()
    opts.local_staging_path = tmp_path / "staging"
    opts.query_engine = engine
    state = ServerState(Parseable(opts, StorageOptions(backend="local-store", root=tmp_path / "data")))

    async def drive():
        client = TestClient(TestServer(build_app(state)))
        await client.start_server()
        try:
            rows = [{"host": f"h{i % 4}", "bytes": float(i)} for i in range(64)]
            r = await client.post("/api/v1/ingest", json=rows, headers={**AUTH, "X-P-Stream": "served"})
            assert r.status == 200
            for sql in ("SELECT host, count(*) c, sum(bytes) s FROM served GROUP BY host", "SELECT host FROM served WHERE bytes > 60"):
                r = await client.post("/api/v1/query", headers=AUTH, json={"query": sql, "fields": True})
                body = await r.json()
                assert r.status == 200 and body["records"]
                if engine == "tpu":
                    assert body["stats"]["device_routes"]["cpu_adaptive"] == 0
        finally:
            await client.close()
            state.stop()

    asyncio.new_event_loop().run_until_complete(drive())
    assert not [t for t in threading.enumerate() if t.name == "device-warmer"]
    assert not list(tmp_path.rglob("link_profile.json"))


# ------------------------------------------------------------ (d) a put is asynchronous for every block


def test_transfer_never_waits_for_a_put(monkeypatch):
    import jax.numpy as jnp

    from parseable_tpu.ops.device import encode_table

    waits = []
    array_type = type(jnp.zeros(1))
    real = array_type.block_until_ready
    monkeypatch.setattr(array_type, "block_until_ready", lambda self: (waits.append(self.nbytes), real(self))[1])
    enc = encode_table(pa.table({"v": pa.array(np.arange(1 << 18, dtype=np.float64))}), {"v"})
    for _ in range(9):  # the old probe waited for the first put and for every eighth
        dev, nbytes = ET._transfer(enc)
        assert nbytes >= 1 << 20 and dev["v"].shape == (1 << 18,)
    assert waits == []
    dev["v"].block_until_ready()
    assert waits == [1 << 20]  # the spy does see a wait


# ------------------------------------------------------------ (e) the hot set: one policy, two constants


def entry(nbytes: int) -> HotEntry:
    return HotEntry(dev={}, meta=None, nbytes=nbytes)


def test_the_default_ship_cost_makes_a_small_block_dearer_per_byte_than_a_large_one():
    """A put costs what it costs whatever it carries: of two blocks as hot as each other, the 64 MiB one goes first."""
    small, large = 64 << 10, 64 << 20
    hs = DeviceHotSet(budget_bytes=large + 2 * small)
    hs.put(("large",), entry(large))
    hs.put(("small",), entry(small))
    hs.put(("more",), entry(2 * small))  # needs room
    assert hs.contains(("small",)) and hs.contains(("more",)) and not hs.contains(("large",))
    assert hs.evictions == 1


def test_the_hot_set_takes_no_policy():
    with pytest.raises(TypeError):
        DeviceHotSet(budget_bytes=100, policy="lru")
    assert not hasattr(DeviceHotSet(budget_bytes=100), "policy")


def test_get_hotset_is_rooted_on_the_budget_alone(monkeypatch):
    base = get_hotset()
    monkeypatch.setenv("P_TPU_HOT_POLICY", "lru")  # a dead name: read by nothing
    assert get_hotset() is base
    assert "policy" not in base.stats_snapshot()


# ------------------------------------------------------------ (f) bench.py: the two names the benchmark cites


def test_bench_py_defines_build_dataset_and_configs_and_imports_no_jax():
    tree = ast.parse((ROOT / "bench.py").read_text())
    defined = [
        n.name if isinstance(n, (ast.FunctionDef, ast.ClassDef)) else n.targets[0].id
        for n in tree.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Assign))
    ]
    assert defined == ["build_dataset", "CONFIGS"]
    probe = "import sys, bench; assert sorted(bench.CONFIGS) == ['groupby', 'regex_filter', 'topk_multicol']; print('jax' in sys.modules)"
    out = subprocess.run([sys.executable, "-c", probe], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "False", out.stdout + out.stderr
