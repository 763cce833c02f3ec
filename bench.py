"""`build_dataset`, the access-log generator that `BENCHMARK.json` and three `benchmark/configs/` files cite as
their `source`, and `CONFIGS`, the three BASELINE SQL texts that `tests/benchmark_suite/test_reference.py` parses
and `chip_smoke.py` imports: nothing else. The benchmark is `benchmark/run.py`; importing this pulls in no JAX.
"""

from __future__ import annotations

from datetime import UTC, datetime, timedelta

import numpy as np
import pyarrow as pa


def build_dataset(
    p,
    stream_name: str,
    total_rows: int,
    profile: str = "default",
    sync_every: int | None = None,
) -> None:
    """Synthesize an access-log stream through the real pipeline.

    Profiles (VERDICT r2 "de-rig the benchmark"):
    - "default": flog-like, low-cardinality columns (32 hosts, 64 paths,
      ~27 message templates) — blocks dictionary-encode tightly, the
      dictionary-LUT design's best case;
    - "highcard": ~10k hosts, ~100k paths, and messages templated with
      random request ids so per-block message uniques ≈ 50k — the case
      where host-side dictionary encode and the group-space explosion are
      the real costs.
    - "highentropy": low-compressibility numerics (full-range uniform
      bytes/latency, random per-row message ids) so parquet compression
      buys ~nothing and disk size approaches logical size — the profile
      the tiering story must survive (memory-pressure runs cap
      P_TPU_HOT_BYTES below the working set; see bench_memory_pressure).
      Group keys stay moderate-cardinality so the device group space is
      dense while the payload bytes stay incompressible.
    """
    from parseable_tpu import DEFAULT_TIMESTAMP_KEY
    from parseable_tpu.event import Event

    rng = np.random.default_rng(42)
    stream = p.create_stream_if_not_exists(stream_name)
    base = datetime(2024, 5, 1, 0, 0, tzinfo=UTC)
    batch_rows = 1_000_000  # one "minute" of a high-throughput stream
    statuses = np.array([200, 200, 200, 200, 301, 404, 500, 503])
    methods = np.array(["GET", "GET", "GET", "POST", "PUT", "DELETE"])
    if profile == "highcard":
        hosts = np.array(
            [f"10.{i}.{j}.{k}" for i in range(10) for j in range(32) for k in range(32)]
        )  # 10,240 hosts
        paths = np.array(
            [f"/api/v1/tenant{t}/resource{r}" for t in range(400) for r in range(256)]
        )  # 102,400 paths
        messages = None  # synthesized per batch with unique request ids
    elif profile == "highentropy":
        # moderate-cardinality group keys (dense device group space), but
        # per-row-unique messages: every batch's message column is ~pure
        # entropy, so parquet compression buys nothing and disk size
        # approaches logical size (the tiering-under-pressure profile)
        hosts = np.array([f"10.0.{i}.{j}" for i in range(8) for j in range(16)])
        paths = np.array([f"/api/v1/resource{i}" for i in range(128)])
        messages = None  # synthesized per batch with unique request ids
    else:
        hosts = np.array([f"10.0.{i}.{j}" for i in range(4) for j in range(8)])
        paths = np.array([f"/api/v1/resource{i}" for i in range(64)])
        # OTel-ish message bodies: low-cardinality template set so blocks
        # dictionary-encode (config 3 exercises the LUT regex path)
        messages = np.array(
            [f"request completed in {d}ms" for d in range(0, 400, 25)]
            + [f"error: upstream timeout after {d}ms" for d in range(0, 400, 50)]
            + [f"slow query warning threshold {d}" for d in range(0, 200, 25)]
            + ["connection reset by peer", "error: permission denied", "cache miss"]
        )
    written = 0
    minute = 0
    while written < total_rows:
        n = min(batch_rows, total_rows - written)
        ts_offsets = np.sort(rng.integers(0, 60_000, n))
        ts = [base + timedelta(minutes=minute, milliseconds=int(o)) for o in ts_offsets]
        if messages is None:
            # ~50k unique messages per 1M-row batch: templates carry a
            # request id drawn from a batch-fresh window
            req_ids = rng.integers(minute * 50_000, minute * 50_000 + 50_000, n)
            tmpl = rng.integers(0, 4, n)
            msg_arr = np.empty(n, dtype=object)
            for t_i, fmt in enumerate(
                (
                    "request %d completed in 34ms",
                    "error: upstream timeout for request %d",
                    "slow query warning for request %d",
                    "request %d cache miss",
                )
            ):
                sel_rows = tmpl == t_i
                msg_arr[sel_rows] = [fmt % r for r in req_ids[sel_rows]]
            batch_messages = pa.array(msg_arr.tolist())
        else:
            batch_messages = pa.array(messages[rng.integers(0, len(messages), n)])
        tbl = pa.table(
            {
                DEFAULT_TIMESTAMP_KEY: pa.array(
                    [t.replace(tzinfo=None) for t in ts], pa.timestamp("ms")
                ),
                "host": pa.array(hosts[rng.integers(0, len(hosts), n)]),
                "method": pa.array(methods[rng.integers(0, len(methods), n)]),
                "path": pa.array(paths[rng.integers(0, len(paths), n)]),
                "message": batch_messages,
                "status": pa.array(statuses[rng.integers(0, len(statuses), n)].astype(np.float64)),
                # highentropy: full-mantissa uniform floats defeat both
                # parquet byte-stream compression and dictionary encoding
                "bytes": pa.array(
                    (rng.random(n) * 50_000).astype(np.float64)
                    if profile == "highentropy"
                    else rng.integers(100, 50_000, n).astype(np.float64)
                ),
                "latency_ms": pa.array((rng.random(n) * 500).astype(np.float64)),
            }
        ).combine_chunks()
        for batch in tbl.to_batches():
            ev = Event(
                stream_name=stream_name,
                rb=batch,
                origin_size=batch.num_rows * 150,
                is_first_event=written == 0,
                parsed_timestamp=base + timedelta(minutes=minute),
            )
            ev.process(stream, commit_schema=p.commit_schema)
        written += n
        minute += 1
        if sync_every and minute % sync_every == 0:
            # large builds: convert + upload as we go so staging arrows
            # (uncompressed, ~3x the parquet bytes) never accumulate —
            # the backdated minute buckets all count as past, so a plain
            # local_sync finishes and compacts everything written so far
            p.local_sync(shutdown=True)
            p.sync_all_streams()
    p.local_sync(shutdown=True)
    p.sync_all_streams()


CONFIGS = {
    # BASELINE config 2: time-bucketed GROUP BY aggregation
    "groupby": (
        "SELECT date_bin(interval '1 minute', p_timestamp) AS t, status, count(*) AS c, "
        "sum(bytes) AS b, avg(latency_ms) AS l FROM {stream} GROUP BY t, status"
    ),
    # BASELINE config 3: substring/LIKE filter (dictionary-LUT predicates)
    "regex_filter": (
        "SELECT status, count(*) AS c, avg(latency_ms) AS l FROM {stream} "
        "WHERE message LIKE '%error%' GROUP BY status"
    ),
    # BASELINE config 4: top-K + multi-column GROUP BY (north star)
    "topk_multicol": (
        "SELECT path, host, count(*) AS c, sum(bytes) AS s FROM {stream} "
        "GROUP BY path, host ORDER BY s DESC LIMIT 10"
    ),
}
