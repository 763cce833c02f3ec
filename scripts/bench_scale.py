"""100GB-class scale bench over the persistent .benchwork dataset.

VERDICT r4 #2: config 4 is specified at 100 GB but had only run at 8-32M
row smoke scale, which never stresses the tiering (hot-set eviction under
budget pressure, enccache hit rates, sustained host decode). This runs
the north-star query over the FULL persistent dataset (700M rows ~= 150GB
logical NDJSON, built by scripts/build_benchwork.py) and reports, per
engine:

- cpu:       full streaming scan through the CPU engine;
- tpu first: compile + live-cold (parquet decode -> encode -> ship, with
             enccache write-behind populating);
- tpu cache-cold: hot set cleared, blocks reload via the enccache
             (zero-copy memmap) — the restart-recovery path;
- tpu warm:  whatever the 8 GiB HBM budget keeps resident (at ~11 GB
             encoded, eviction pressure is the point: the hot set churns
             and the run measures steady-state re-ship cost);

plus the tiering counters that prove the machinery engaged (hot-set
evictions, enccache hits/misses, per-route block counts).

The chip is the default: without an accelerator the run exits non-zero
and emits nothing. `--virtual-mesh` is the explicit rehearsal on a virtual
8-device CPU mesh — same executor, same tiering, CPU "HBM" — and every
line it emits carries `"platform": "cpu"`, so none of them can be read as
a device number.
Reference: src/hottier.rs:281-432; BASELINE.json config 4.

Usage: python scripts/bench_scale.py [--virtual-mesh] [--max-minutes N]
Emits one JSON line per measurement; the last line is the summary.
bench.py calls main() IN-PROCESS (a chip belongs to one process, so a
child of the chip-holding bench could never initialize it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

WORK = REPO / ".benchwork"

SQL = (
    "SELECT path, host, count(*) AS c, sum(bytes) AS s FROM bench "
    "GROUP BY path, host ORDER BY s DESC LIMIT 10"
)


def rows_close(a: list, b: list) -> bool:
    """Exact on keys/counts; 1e-4 relative on floats (device sums are f32
    per block — same tolerance the test suite and bench.py use)."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if len(ra) != len(rb):
            return False
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and isinstance(vb, float):
                if abs(va - vb) > 1e-4 * max(1.0, abs(va)):
                    return False
            elif va != vb:
                return False
    return True


def run_battery(p, sess_cpu, sess, sql: str, rows_total: int, emit, label: str) -> dict:
    """The measurement protocol: cpu -> tpu first (compile + live cold) ->
    enccache settle -> hot-set clear -> cache cold -> warm, with tiering
    counters deltas. Returns the summary dict (also emitted per stage)."""
    from parseable_tpu.ops.enccache import get_enccache
    from parseable_tpu.ops.hotset import get_hotset

    ec = get_enccache(p.options)
    hs = get_hotset()

    def run(s) -> tuple[float, list, dict]:
        t0 = time.perf_counter()
        res = s.query(sql)
        dt = time.perf_counter() - t0
        rows = sorted(
            (tuple(r.values()) for r in res.to_json_rows()),
            key=lambda t: tuple(str(v) for v in t),
        )
        return dt, rows, res.stats

    cpu_t, cpu_rows, _ = run(sess_cpu)
    emit("cpu", config=label, secs=round(cpu_t, 2), rows_per_sec=round(rows_total / cpu_t))

    first_t, tpu_rows, stats1 = run(sess)
    emit(
        "tpu_first",
        config=label,
        secs=round(first_t, 2),
        rows_per_sec=round(rows_total / first_t),
        note="compile + live cold (decode/encode/ship + enccache write-behind)",
        routes=stats1.get("device_routes"),
    )
    if ec is not None:
        ec.wait_idle()

    hs.clear()
    ev0, h0, m0 = hs.evictions, (ec.hits if ec else 0), (ec.misses if ec else 0)
    cold_t, rows2, stats2 = run(sess)
    emit(
        "tpu_cache_cold",
        config=label,
        secs=round(cold_t, 2),
        rows_per_sec=round(rows_total / cold_t),
        enccache_hits=(ec.hits - h0) if ec else None,
        enccache_misses=(ec.misses - m0) if ec else None,
        hotset_evictions=hs.evictions - ev0,
        routes=stats2.get("device_routes"),
    )

    ev0 = hs.evictions
    warm_t, rows3, stats3 = run(sess)
    emit(
        "tpu_warm",
        config=label,
        secs=round(warm_t, 2),
        rows_per_sec=round(rows_total / warm_t),
        hotset_resident_gb=round(hs.resident_bytes / 2**30, 2),
        hotset_evictions=hs.evictions - ev0,
        routes=stats3.get("device_routes"),
    )

    match = (
        rows_close(cpu_rows, tpu_rows)
        and rows_close(cpu_rows, rows2)
        and rows_close(cpu_rows, rows3)
    )
    if not match:
        emit("mismatch", config=label, cpu=cpu_rows[:2], tpu=tpu_rows[:2])
    return {
        "rows": rows_total,
        "cpu_secs": round(cpu_t, 2),
        "first_run_secs": round(first_t, 2),
        "cache_cold_secs": round(cold_t, 2),
        "cache_cold_vs_cpu": round(cpu_t / cold_t, 3),
        "warm_secs": round(warm_t, 2),
        "warm_vs_cpu": round(cpu_t / warm_t, 3),
        "rows_per_sec_warm": round(rows_total / warm_t, 1),
        "hotset_evictions": hs.evictions,
        "hotset_resident_gb": round(hs.resident_bytes / 2**30, 2),
        "enccache_hits": ec.hits if ec else None,
        "enccache_misses": ec.misses if ec else None,
        "results_match": bool(match),
    }


def run_pressure_battery(p, sql: str, rows_total: int, emit) -> dict:
    """Memory-pressure phase (ROADMAP "make the tiering story true"): the
    SAME scale query with P_TPU_HOT_BYTES capped well below the encoded
    working set (BENCH_SCALE_HOT_BYTES, default 2 GiB vs the ~7-11 GB
    encoded working set), warm p50/p95 over >=BENCH_SCALE_PRESSURE_REPS
    (10) reps per eviction policy (P_TPU_HOT_POLICY cost vs lru A/B).
    The recorded scale runs showed hotset_evictions: 0 — the budget was
    never exceeded, so the "100 GB on a 16 GiB device" label was untested.
    This phase makes the eviction path the thing under measurement.
    BENCH_SCALE_PRESSURE=0 skips."""
    if os.environ.get("BENCH_SCALE_PRESSURE", "1") == "0":
        return {}
    import bench as _bench
    from parseable_tpu.ops.hotset import get_hotset
    from parseable_tpu.query.session import QuerySession

    budget = int(os.environ.get("BENCH_SCALE_HOT_BYTES", str(2 << 30)))
    reps = int(os.environ.get("BENCH_SCALE_PRESSURE_REPS", "10"))
    saved = {k: os.environ.get(k) for k in ("P_TPU_HOT_BYTES", "P_TPU_HOT_POLICY")}
    out: dict = {"pressure_budget_bytes": budget}
    try:
        os.environ["P_TPU_HOT_BYTES"] = str(budget)
        for policy in ("lru", "cost"):
            os.environ["P_TPU_HOT_POLICY"] = policy
            hs = get_hotset()  # re-roots onto the capped budget + policy
            hs.clear()
            sess = QuerySession(p, engine="tpu")
            sess.query(sql)  # populate up to the capped budget
            ev0, times = hs.evictions, []
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                sess.query(sql)
                times.append(time.perf_counter() - t0)
            p50 = _bench.percentile(times, 0.50)
            p95 = _bench.percentile(times, 0.95)
            emit(
                f"tpu_pressure_{policy}",
                config="scale_topk_pressure",
                budget_bytes=budget,
                warm_p50_s=round(p50, 2),
                warm_p95_s=round(p95, 2),
                rows_per_sec=round(rows_total / max(p50, 1e-9)),
                hotset_evictions=hs.evictions - ev0,
                hotset_resident_gb=round(hs.resident_bytes / 2**30, 2),
            )
            out[f"pressure_{policy}_p50_s"] = round(p50, 2)
            out[f"pressure_{policy}_p95_s"] = round(p95, 2)
            out[f"pressure_{policy}_evictions"] = hs.evictions - ev0
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        get_hotset().clear()
    if "pressure_cost_p95_s" in out and "pressure_lru_p95_s" in out:
        out["pressure_cost_vs_lru_p95"] = round(
            out["pressure_lru_p95_s"] / max(out["pressure_cost_p95_s"], 1e-9), 3
        )
    return out


def main(virtual_mesh: bool = False, max_minutes: int = 0) -> None:
    meta_path = WORK / "meta.json"
    if not meta_path.exists():
        print(json.dumps({"error": "no .benchwork dataset"}))
        sys.exit(1)
    meta = json.loads(meta_path.read_text())

    if virtual_mesh:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    from parseable_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if virtual_mesh:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform == "cpu" and not virtual_mesh:
        sys.exit(
            "bench_scale.py: JAX found no accelerator; nothing emitted "
            "(--virtual-mesh is the explicit CPU rehearsal)"
        )

    from parseable_tpu.config import Options, StorageOptions
    from parseable_tpu.core import Parseable
    from parseable_tpu.query.session import QuerySession

    opts = Options()
    opts.local_staging_path = WORK / "staging"
    p = Parseable(opts, StorageOptions(backend="local-store", root=WORK / "data"))

    sql = SQL
    rows = meta["rows"]
    if max_minutes:
        # dataset minutes start 2024-05-01T00:00, 1M rows per minute
        sql = SQL.replace(
            "FROM bench ",
            "FROM bench WHERE p_timestamp < '2024-05-01T"
            f"{max_minutes // 60:02d}:{max_minutes % 60:02d}:00' ",
        )
        rows = min(rows, max_minutes * 1_000_000)

    def emit(kind: str, **kw) -> None:
        print(json.dumps({"kind": kind, "platform": platform, **kw}), flush=True)

    sess_cpu = QuerySession(p, engine="cpu")
    sess = QuerySession(p, engine="tpu")
    result = run_battery(p, sess_cpu, sess, sql, rows, emit, "scale_topk")
    pressure = run_pressure_battery(p, sql, rows, emit)
    if pressure:
        result.update(pressure)
    summary = {
        "metric": "scale_topk_multicol_rows_per_sec",
        "value": result["rows_per_sec_warm"],
        "unit": "rows/s",
        "vs_baseline": result["warm_vs_cpu"],
        "logical_gb": meta.get("logical_gb"),
        "disk_gb": round(meta.get("disk_bytes", 0) / 1e9, 1),
        "devices": jax.device_count(),
        "platform": platform,
        "note": "config 4 at 100GB-logical scale through the tiering "
        "(hot set under eviction pressure + enccache)",
        **result,
    }
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--virtual-mesh",
        action="store_true",
        help="rehearse on a virtual 8-device CPU mesh (lines stamped platform=cpu)",
    )
    ap.add_argument(
        "--max-minutes",
        type=int,
        default=0,
        help="bound the scan to the first N minute-partitions (0 = full)",
    )
    args = ap.parse_args()
    main(virtual_mesh=args.virtual_mesh, max_minutes=args.max_minutes)
