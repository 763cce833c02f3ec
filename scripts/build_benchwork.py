"""Build the persistent large-scale bench dataset at /root/repo/.benchwork.

VERDICT r4 #2: config 4 is specified at 100 GB and had only ever run at
8-32M-row smoke scale. This builds the dataset ONCE through the real
pipeline (staging -> parquet -> catalog) and persists it so bench.py
and scripts/bench_scale.py can execute the scale config without paying
the build again.

Default 700M rows of the flog-like default profile ~= 100 GB of logical
JSON (measured per-row serialization x rows, recorded in meta.json);
~26 GB parquet on disk. Resumable is not worth the complexity at ~45 min
build: if meta.json is missing the tree is wiped and rebuilt.

Usage: python scripts/build_benchwork.py [--rows N] [--hc-rows N]
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402

# building the dataset needs no accelerator and must not take the chip
# from whichever process is about to measure on it
jax.config.update("jax_platforms", "cpu")

WORK = REPO / ".benchwork"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=700_000_000)
    ap.add_argument(
        "--hc-rows",
        type=int,
        default=32_000_000,
        help="rows for the high-cardinality profile stream (bench_hc)",
    )
    args = ap.parse_args()

    meta_path = WORK / "meta.json"
    if meta_path.exists():
        meta = json.loads(meta_path.read_text())
        if meta.get("rows") == args.rows and meta.get("hc_rows") == args.hc_rows:
            print(f"already built: {meta}")
            return
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)

    import bench
    from parseable_tpu.config import Options, StorageOptions
    from parseable_tpu.core import Parseable

    opts = Options()
    opts.local_staging_path = WORK / "staging"
    # cpu engine during the build: skips the upload-time enccache seeding
    # (core.py upload_files_from_staging) so the scale bench's first TPU
    # run measures a true live-cold pass that populates the cache itself
    opts.query_engine = "cpu"
    p = Parseable(opts, StorageOptions(backend="local-store", root=WORK / "data"))

    # logical-size yardstick: the NDJSON bytes these rows would occupy on
    # the wire (what "100 GB of logs" means operationally)
    sample_row = {
        "p_timestamp": "2024-05-01T00:00:00.000",
        "host": "10.0.3.7",
        "method": "GET",
        "path": "/api/v1/resource42",
        "message": "error: upstream timeout after 350ms",
        "status": 200.0,
        "bytes": 24731.0,
        "latency_ms": 211.7,
    }
    row_bytes = len(json.dumps(sample_row)) + 1
    logical = row_bytes * args.rows

    t0 = time.perf_counter()
    bench.build_dataset(p, "bench", args.rows, sync_every=8)
    build_s = time.perf_counter() - t0
    print(f"bench: {args.rows} rows in {build_s:.0f}s ({args.rows/build_s:,.0f} rows/s)")

    t0 = time.perf_counter()
    if args.hc_rows:
        bench.build_dataset(p, "bench_hc", args.hc_rows, profile="highcard", sync_every=8)
        print(f"bench_hc: {args.hc_rows} rows in {time.perf_counter()-t0:.0f}s")

    du = sum(f.stat().st_size for f in WORK.rglob("*") if f.is_file())
    meta = {
        "rows": args.rows,
        "hc_rows": args.hc_rows,
        "logical_json_bytes": logical,
        "logical_gb": round(logical / 1e9, 1),
        "disk_bytes": du,
        "build_secs": round(build_s, 1),
        "profile": "default",
        "built_at": time.time(),
    }
    meta_path.write_text(json.dumps(meta, indent=2))
    print(json.dumps(meta))


if __name__ == "__main__":
    main()
