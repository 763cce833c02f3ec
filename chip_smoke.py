"""chip_smoke.py — does the served TPU query path still start on the chip?

Drives the system's main path once, through the entry points a user would
call: a 32M-row access-log stream goes through the product's own staging ->
parquet -> object store -> catalog path, `python -m parseable_tpu.server`
boots over that store with the default engine (`tpu`), and the parent asks
it over HTTP: an ingest whose ack is read back from staging, the three
BASELINE SQL texts of bench.py (configs 2, 3, 4) cold, from the result cache
and device-warm, a sparse-group aggregate that a reduced-precision multiply
cannot survive, and a filtered SELECT. Every answer is compared with a plain
numpy reference computed from the generated batches, off the chip, and every
response's own stats must show the device did the work. Then the Pallas
kernel is compiled with Mosaic and compared with the XLA path.

Process layout (a chip belongs to one process):
  parent   stdlib + HTTP only; never initialises a JAX backend
  probe    `jax.devices()` and exit — fails fast when there is no TPU
  loader   JAX_PLATFORMS=cpu; generates, ingests, syncs, writes the reference
  server   `python -m parseable_tpu.server` — the one process on the chip
  kernels  after the server has exited: Mosaic compile + XLA comparison

Exit code 0 and a last stdout line
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
(exactly these keys; the device as JAX reports it) only if every phase
passed. A phase that fails once the device is known gives a non-zero code,
the same line with "ok": false, and the reason in the {"detail": ...} line
before it and on stderr. Where JAX finds no TPU, or the script stands alone
without the repository, it prints no result at all: a non-zero code and the
reason on stderr. Nothing a failing phase raises is caught and carried past.
Times it prints are set-up information, never a measurement.

`--cpu-rehearsal` is the explicit, tiny run on the CPU backend used to debug
this script before chip time is spent: it stamps `"platform": "cpu"` and
`"rehearsal": true` on its output and is never what the driver runs.
"""

from __future__ import annotations

import argparse
import base64
import http.client
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
STREAM = "smoke"
LIVE_STREAM = "smoke_live"
BATCH_ROWS = 1_000_000  # one minute bucket = one parquet file = one 2^20 block
DEFAULT_ROWS = 32_000_000
BASE_MS = 1_714_521_600_000  # 2024-05-01T00:00:00Z, bench.py's base
REL_TOL = 1e-4  # float sums/averages vs the f64 reference (executor_tpu docstring)

# bench.py build_dataset, default profile: 32 hosts, 64 paths, 27 message templates,
# 8 statuses (5 distinct), 6 methods (4 distinct)
HOSTS = [f"10.0.{i}.{j}" for i in range(4) for j in range(8)]
PATHS = [f"/api/v1/resource{i}" for i in range(64)]
METHODS = ["GET", "GET", "GET", "POST", "PUT", "DELETE"]
STATUSES = [200, 200, 200, 200, 301, 404, 500, 503]
MESSAGES = (
    [f"request completed in {d}ms" for d in range(0, 400, 25)]
    + [f"error: upstream timeout after {d}ms" for d in range(0, 400, 50)]
    + [f"slow query warning threshold {d}" for d in range(0, 200, 25)]
    + ["connection reset by peer", "error: permission denied", "cache miss"]
)

# The rare conjunction behind requests 3 and 4: 1/27 * 1/6 * 1/64 of the rows
RARE = "message = 'cache miss' AND method = 'DELETE' AND path = '/api/v1/resource7'"
SPARSE_SQL = (
    "SELECT host, status, count(*) AS c, sum(bytes) AS b, avg(latency_ms) AS l "
    f"FROM {{stream}} WHERE {RARE} GROUP BY host, status"
)
SELECT_SQL = (
    "SELECT p_timestamp, host, bytes, latency_ms FROM {stream} "
    f"WHERE {RARE} AND status = 503 LIMIT 100000"
)


class SmokeFailure(Exception):
    pass


def check(cond: bool, reason: str) -> None:
    if not cond:
        raise SmokeFailure(reason)


def note(msg: str) -> None:
    print(f"# {msg}", flush=True)


# --------------------------------------------------------------------- children


def role_probe() -> None:
    import jax

    devs = jax.devices()
    print(
        json.dumps(
            {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
        )
    )


def _gen_batch(seed: int, minute: int, n: int):
    """One minute bucket of the access-log stream as integer codes (the
    reference aggregates these) — a function of (seed, minute) alone."""
    import numpy as np

    rng = np.random.default_rng([seed, minute])
    return {
        "ts_ms": BASE_MS + minute * 60_000 + np.sort(rng.integers(0, 60_000, n)),
        "host": rng.integers(0, len(HOSTS), n),
        "method": rng.integers(0, len(METHODS), n),
        "path": rng.integers(0, len(PATHS), n),
        "message": rng.integers(0, len(MESSAGES), n),
        "status": rng.integers(0, len(STATUSES), n),
        "bytes": rng.integers(100, 50_000, n).astype(np.float64),
        "latency_ms": rng.random(n) * 500,
    }


class Reference:
    """Plain numpy over the generated codes: the same operations on the same
    data, independent of every layer under test. f64 throughout."""

    def __init__(self) -> None:
        import numpy as np

        self.np = np
        self.status_vals = np.array(STATUSES, dtype=np.float64)
        self.error_msg = np.array(["error" in m for m in MESSAGES])
        self.q2: dict = {}  # (minute, status) -> [c, sum_bytes, sum_lat]
        self.q3 = np.zeros((len(STATUSES), 2))  # status slot -> c, sum_lat
        self.q4 = np.zeros((len(PATHS) * len(HOSTS), 2))  # path*H+host -> c, sum_bytes
        self.q5 = np.zeros((len(HOSTS) * len(STATUSES), 3))  # host*S+status -> c, sb, sl
        self.q6: list = []

    def absorb(self, minute: int, b: dict) -> None:
        np = self.np
        S, H = len(STATUSES), len(HOSTS)
        st = b["status"]
        c = np.bincount(st, minlength=S)
        sb = np.bincount(st, weights=b["bytes"], minlength=S)
        sl = np.bincount(st, weights=b["latency_ms"], minlength=S)
        for slot in range(S):
            acc = self.q2.setdefault((minute, STATUSES[slot]), [0, 0.0, 0.0])
            acc[0] += int(c[slot])
            acc[1] += float(sb[slot])
            acc[2] += float(sl[slot])
        err = self.error_msg[b["message"]]
        self.q3[:, 0] += np.bincount(st[err], minlength=S)
        self.q3[:, 1] += np.bincount(st[err], weights=b["latency_ms"][err], minlength=S)
        ph = b["path"] * H + b["host"]
        self.q4[:, 0] += np.bincount(ph, minlength=len(self.q4))
        self.q4[:, 1] += np.bincount(ph, weights=b["bytes"], minlength=len(self.q4))
        rare = (
            (b["message"] == MESSAGES.index("cache miss"))
            & (b["method"] == METHODS.index("DELETE"))
            & (b["path"] == PATHS.index("/api/v1/resource7"))
        )
        hs = b["host"][rare] * S + st[rare]
        self.q5[:, 0] += np.bincount(hs, minlength=len(self.q5))
        self.q5[:, 1] += np.bincount(hs, weights=b["bytes"][rare], minlength=len(self.q5))
        self.q5[:, 2] += np.bincount(hs, weights=b["latency_ms"][rare], minlength=len(self.q5))
        for i in np.nonzero(rare & (self.status_vals[st] == 503.0))[0]:
            self.q6.append(
                [int(b["ts_ms"][i]), HOSTS[b["host"][i]], float(b["bytes"][i]), float(b["latency_ms"][i])]
            )

    def dump(self) -> dict:
        S, H = len(STATUSES), len(HOSTS)

        def by_status_value(rows: list) -> list:
            # several slots share a status value (200 x4): merge them
            out: dict = {}
            for key, vals in rows:
                acc = out.setdefault(key, [0.0] * len(vals))
                for i, v in enumerate(vals):
                    acc[i] += v
            return [list(k) + v for k, v in sorted(out.items()) if v[0] > 0]

        q2 = by_status_value(
            [((BASE_MS + m * 60_000, float(s)), v) for (m, s), v in self.q2.items()]
        )
        q3 = by_status_value(
            [((float(STATUSES[i]),), list(self.q3[i])) for i in range(S)]
        )
        q5 = by_status_value(
            [
                ((HOSTS[i // S], float(STATUSES[i % S])), list(self.q5[i]))
                for i in range(len(self.q5))
            ]
        )
        q4 = [
            [PATHS[i // H], HOSTS[i % H], self.q4[i, 0], self.q4[i, 1]]
            for i in range(len(self.q4))
        ]
        return {
            # [t_ms, status, c, sum(bytes), avg(latency_ms)]
            "groupby": [[t, s, int(c), b, l / c] for t, s, c, b, l in q2],
            # [status, c, avg(latency_ms)]
            "regex_filter": [[s, int(c), l / c] for s, c, l in q3],
            # every (path, host) group: [path, host, c, sum(bytes)]
            "topk_multicol": [[p, h, int(c), s] for p, h, c, s in q4],
            # [host, status, c, sum(bytes), avg(latency_ms)]
            "sparse": [[h, s, int(c), b, l / c] for h, s, c, b, l in q5],
            # [ts_ms, host, bytes, latency_ms]
            "select": sorted(self.q6),
        }


def role_loader(seed: int, rows: int, batch_rows: int, out_path: str) -> None:
    """Generate from --seed, push through staging -> parquet -> object store
    -> catalog (the product's path, P_STAGING_DIR / P_FS_DIR from the env),
    and write the reference answers. Runs with JAX_PLATFORMS=cpu and never
    touches the query engine."""
    from datetime import UTC, datetime, timedelta

    import numpy as np
    import pyarrow as pa

    from parseable_tpu import DEFAULT_TIMESTAMP_KEY
    from parseable_tpu.config import Options, StorageOptions
    from parseable_tpu.core import Parseable
    from parseable_tpu.event import Event
    from parseable_tpu.native import native_available

    check(native_available(), "native fastpath library did not load in the loader")
    p = Parseable(Options(), StorageOptions(backend="local-store"))
    stream = p.create_stream_if_not_exists(STREAM)
    dicts = {
        "host": pa.array(HOSTS),
        "method": pa.array(METHODS),
        "path": pa.array(PATHS),
        "message": pa.array(MESSAGES),
    }
    status_vals = np.array(STATUSES, dtype=np.float64)
    base = datetime.fromtimestamp(BASE_MS / 1000, UTC)
    ref = Reference()
    t0 = time.time()
    written = minute = 0
    while written < rows:
        n = min(batch_rows, rows - written)
        b = _gen_batch(seed, minute, n)
        ref.absorb(minute, b)
        cols = {DEFAULT_TIMESTAMP_KEY: pa.array(b["ts_ms"], pa.timestamp("ms"))}
        for name in ("host", "method", "path", "message"):
            cols[name] = dicts[name].take(pa.array(b[name]))
        cols["status"] = pa.array(status_vals[b["status"]])
        cols["bytes"] = pa.array(b["bytes"])
        cols["latency_ms"] = pa.array(b["latency_ms"])
        for batch in pa.table(cols).combine_chunks().to_batches():
            Event(
                stream_name=STREAM,
                rb=batch,
                origin_size=batch.num_rows * 150,
                is_first_event=written == 0,
                parsed_timestamp=base + timedelta(minutes=minute),
            ).process(stream, commit_schema=p.commit_schema)
        written += n
        minute += 1
        if minute % 8 == 0:
            # convert + upload as we go so uncompressed staging arrows never
            # pile up (backdated buckets all count as past minutes)
            p.local_sync(shutdown=True)
            p.sync_all_streams()
    p.local_sync(shutdown=True)
    p.sync_all_streams()
    p.shutdown()
    Path(out_path).write_text(json.dumps(ref.dump()))
    print(json.dumps({"rows": written, "minutes": minute, "secs": round(time.time() - t0, 1)}))


def role_kernels(rehearsal: bool) -> None:
    """§5: the Pallas additive kernel at the shapes fused_groupby_block hands
    it — N = 2^20, G in {128, 512}, R = 1 + n_all + n_sum as configs 2-4
    produce (5 for two summed columns, 3 for one) — compiled with Mosaic
    (the interpreter only under --cpu-rehearsal, at a small N) and compared
    with the XLA path on sparse groups, plus both against f64; then the XLA
    path alone at G = 8,192, the factored one-hot product's shape."""
    import numpy as np

    from parseable_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax
    import jax.numpy as jnp

    from parseable_tpu.ops import kernels as K

    n = 1 << 14 if rehearsal else 1 << 20
    rng = np.random.default_rng(0)
    out = []
    for g in (128, 512):
        for n_sum in (2, 1):
            ids = rng.integers(0, g, n).astype(np.int32)
            # a handful of rows per group: the case a rounded multiply fails
            mask = rng.random(n) < (0.5 if rehearsal else 4.0 * g / n)
            vals = np.stack(
                [rng.integers(100, 50_000, n), rng.random(n) * 500][:n_sum]
            ).astype(np.float32)
            valid = np.ones((n_sum, n), bool)
            empty = jnp.zeros((0, n), jnp.float32)
            args = (jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(vals), empty, empty, jnp.asarray(valid), g, n_sum, 0, 0)
            want = np.stack(
                [np.bincount(ids[mask], weights=v[mask].astype(np.float64), minlength=g) for v in vals]
            )
            res = {}
            for mode in ("", "interpret" if rehearsal else "1"):
                os.environ["P_TPU_USE_PALLAS"] = mode
                K.fused_groupby_block.clear_cache()
                res[mode] = [np.asarray(x, np.float64) for x in jax.block_until_ready(K.fused_groupby_block(*args))]
            os.environ.pop("P_TPU_USE_PALLAS")
            xla, pal = res[""], res["interpret" if rehearsal else "1"]
            scale = np.maximum(1.0, np.abs(want))
            err_xla = float(np.max(np.abs(xla[2] - want) / scale))
            err_pal = float(np.max(np.abs(pal[2] - want) / scale))
            check(np.array_equal(xla[0], pal[0]) and np.array_equal(xla[1], pal[1]), f"pallas counts differ from XLA at G={g}")
            check(xla[0].sum() == mask.sum(), f"XLA counts wrong at G={g}")
            check(max(err_xla, err_pal) <= REL_TOL, f"sparse sums off at G={g}: xla {err_xla:.2e} pallas {err_pal:.2e}")
            out.append({"n": n, "g": g, "r": 1 + 2 * n_sum, "max_rel_err_xla": err_xla, "max_rel_err_pallas": err_pal})
    # the same check past the one-hot's element budget, where a chip takes the
    # factored one-hot product (K.fold_route; the rehearsal's backend the
    # scatter): a ten-row group beside a 100k-row group in one high row
    g, big, small = 8192, 4097, 4098
    big_rows = 8_000 if rehearsal else 100_000
    ids = np.concatenate([np.full(big_rows, big), np.full(10, small), rng.integers(0, g, n - big_rows - 10)]).astype(np.int32)
    rng.shuffle(ids)
    mask = np.ones(n, bool)
    vals = rng.integers(100, 50_000, (1, n)).astype(np.float32)
    empty = jnp.zeros((0, n), jnp.float32)
    K.fused_groupby_block.clear_cache()
    got = [
        np.asarray(x, np.float64)
        for x in jax.block_until_ready(
            K.fused_groupby_block(jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(vals), empty, empty, jnp.asarray(mask[None, :]), g, 1, 0, 0)
        )
    ]
    want = np.bincount(ids, weights=vals[0].astype(np.float64), minlength=g)
    err = float(np.max(np.abs(got[2][0] - want) / np.maximum(1.0, np.abs(want))))
    route = K.fold_route(n, g)
    check(rehearsal or route == "factored", f"a chip's fold at G={g} took the route {route}")
    check(np.array_equal(got[0], np.bincount(ids, minlength=g)), f"{route} counts wrong at G={g}")
    check(err <= REL_TOL, f"sparse sums off at G={g} ({route}): {err:.2e}")
    out.append({"n": n, "g": g, "r": 3, "route": route, "max_rel_err_xla": err, "small_group_rel_err": float(abs(got[2][0][small] - want[small]) / want[small])})
    # a valid inf is its own group's sum and no other's: in a one-hot product
    # it would be NaN in every group of its column (the ten-row group's too)
    at = int(np.flatnonzero(ids == big)[0])
    vals[0, at] = np.inf
    got_inf = np.asarray(
        jax.block_until_ready(
            K.fused_groupby_block(jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(vals), empty, empty, jnp.asarray(mask[None, :]), g, 1, 0, 0)
        )[2][0],
        np.float64,
    )
    rest = np.arange(g) != big
    check(got_inf[big] == np.inf, f"{route}: a valid inf summed to {got_inf[big]} at G={g}")
    err_inf = float(np.max(np.abs(got_inf[rest] - want[rest]) / np.maximum(1.0, np.abs(want[rest]))))  # NaN fails
    check(err_inf <= REL_TOL, f"{route}: a valid inf in one group moved another's sum at G={g}: {err_inf:.2e}")
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "mosaic": not rehearsal, "precision": str(K.SUM_DOT_PRECISION), "shapes": out}))


# ----------------------------------------------------------------------- parent


def run_child(role: str, args: list[str], env: dict, timeout: float) -> dict:
    """Run `chip_smoke.py --role ...` to its end; its last stdout line is its
    JSON result. A child that fails fails the smoke with its stderr tail."""
    proc = subprocess.run(
        [sys.executable, str(HERE / "chip_smoke.py"), "--role", role, *args],
        env=env, cwd=str(HERE), capture_output=True, text=True, timeout=timeout,
    )
    if proc.returncode != 0:
        raise SmokeFailure(
            f"{role} child exited {proc.returncode}: {proc.stderr.strip()[-1500:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    AUTH = "Basic " + base64.b64encode(b"admin:admin").decode()

    def __init__(self, env: dict, workdir: Path):
        self.port = free_port()
        self.log_path = workdir / "server.log"
        self.log = open(self.log_path, "wb")
        self.started = time.time()
        # the normal entry point, mode all, local-store; engine, platform and
        # every P_TPU_* knob are the defaults
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "parseable_tpu.server",
                "--mode", "all", "--address", f"127.0.0.1:{self.port}",
                "local-store",
            ],
            cwd=str(HERE), env=env, stdout=self.log, stderr=subprocess.STDOUT,
        )

    def http(self, method: str, path: str, body=None, headers=None, timeout=600.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data, method=method,
            headers={"Authorization": self.AUTH, "Content-Type": "application/json", **(headers or {})},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                raw = resp.read()
                return resp.status, raw
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def wait_live(self, timeout: float = 300.0) -> float:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(f"server died during boot (exit {self.proc.returncode}): {self.log_tail()}")
            try:
                if self.http("GET", "/api/v1/liveness", timeout=2.0)[0] == 200:
                    return time.time() - self.started
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.25)
        raise SmokeFailure(f"server not live after {timeout}s: {self.log_tail()}")

    def query(self, sql: str, start: str, end: str) -> tuple[list, dict, float]:
        t0 = time.time()
        try:
            status, raw = self.http(
                "POST", "/api/v1/query",
                {"query": sql, "startTime": start, "endTime": end, "fields": True},
            )
        except (OSError, http.client.HTTPException) as e:
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                raise SmokeFailure(f"no answer from the server ({e!r}) | sql: {sql}") from e
            raise SmokeFailure(
                f"server died (exit {self.proc.returncode}) on: {sql} | log: {self.log_tail()}"
            ) from e
        secs = time.time() - t0
        check(status == 200, f"query answered HTTP {status}: {raw[:400]!r} | sql: {sql}")
        doc = json.loads(raw)
        return doc["records"], doc["stats"], secs

    def metric(self, name: str, label: str) -> dict:
        """{label value: sample value} of one family from /api/v1/metrics."""
        from prometheus_client.parser import text_string_to_metric_families

        status, raw = self.http("GET", "/api/v1/metrics")
        check(status == 200, f"/api/v1/metrics answered HTTP {status}")
        return {
            sample.labels[label]: sample.value
            for family in text_string_to_metric_families(raw.decode())
            for sample in family.samples
            if sample.name == name and label in sample.labels
        }

    def scan_bytes_shipped(self) -> float:
        return self.metric("parseable_tpu_bytes_to_device_total", "op").get("scan", 0.0)

    def log_tail(self, n: int = 1500) -> str:
        return self.log_path.read_text(errors="replace")[-n:]

    def stop(self) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(120)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(30)
        self.log.close()
        return self.proc.returncode


def close(a: float, b: float) -> bool:
    return abs(a - b) <= REL_TOL * max(1.0, abs(b))


def parse_ts_ms(v) -> int:
    from datetime import datetime, timezone

    dt = datetime.fromisoformat(str(v).replace("Z", "+00:00"))
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=timezone.utc)
    return round(dt.timestamp() * 1000)


def compare(name: str, records: list, ref: dict) -> None:
    """Keys, counts and the select's values exact; float sums and averages
    within REL_TOL of the f64 reference."""
    want = ref[name]
    if name == "topk_multicol":
        groups = {(p, h): (c, s) for p, h, c, s in want}
        ranked = sorted((s for _, _, _, s in want), reverse=True)
        check(len(records) == 10, f"{name}: {len(records)} rows, want 10")
        for i, r in enumerate(records):
            c, s = groups[(r["path"], r["host"])]
            check(r["c"] == c, f"{name}: count of {r['path']},{r['host']} is {r['c']}, want {c}")
            check(close(r["s"], s), f"{name}: sum of {r['path']},{r['host']} is {r['s']}, want {s}")
            # rank by rank the sums are the reference's (a near-tie may swap
            # two keys; it may not change the values)
            check(close(r["s"], ranked[i]), f"{name}: rank {i} sum {r['s']}, want {ranked[i]}")
        return
    if name == "select":
        got = sorted(
            [parse_ts_ms(r["p_timestamp"]), r["host"], float(r["bytes"]), float(r["latency_ms"])]
            for r in records
        )
        check(len(got) == len(want), f"{name}: {len(got)} rows, want {len(want)}")
        for g, w in zip(got, want):
            check(g[:3] == w[:3] and abs(g[3] - w[3]) <= 1e-9, f"{name}: row {g}, want {w}")
        return
    keys, floats = {
        "groupby": (("t", "status"), ("b", "l")),
        "regex_filter": (("status",), ("l",)),
        "sparse": (("host", "status"), ("b", "l")),
    }[name]
    nk = len(keys)

    def key_of(r: dict) -> tuple:
        return tuple(parse_ts_ms(r[k]) if k == "t" else r[k] for k in keys)

    got = {key_of(r): r for r in records}
    check(len(got) == len(records), f"{name}: duplicate groups in the answer")
    check(set(got) == {tuple(w[:nk]) for w in want}, f"{name}: group keys differ from the reference")
    for w in want:
        r = got[tuple(w[:nk])]
        check(r["c"] == w[nk], f"{name}: count of {w[:nk]} is {r['c']}, want {w[nk]}")
        for col, wv in zip(floats, w[nk + 1 :]):
            check(close(r[col], wv), f"{name}: {col} of {w[:nk]} is {r[col]!r}, want {wv!r}")


def check_routes(name: str, kind: str, stats: dict, blocks: int) -> dict:
    """What every response's own stats must show. kind: cold | cached | warm."""
    check("engine_fallback" not in stats, f"{name} {kind}: engine_fallback in stats")
    check(stats.get("engine") == "tpu", f"{name} {kind}: engine is {stats.get('engine')}")
    check(stats.get("scan_errors", 0) == 0, f"{name} {kind}: scan_errors {stats.get('scan_errors')}")
    stages = stats.get("stages") or {}
    programs = stages.get("programs")
    routes = stats.get("device_routes")
    if kind == "cached":
        # a repeat the result cache answered did no device work
        check(stages.get("result_cache") == "hit" and programs is None,
              f"{name}: literal repeat was not a result-cache hit ({stages.get('result_cache')}, programs {programs})")
        return {}
    check(routes is not None and programs is not None, f"{name} {kind}: no device_routes/programs in stats (no device work)")
    check(routes["cpu_fallback"] == 0, f"{name} {kind}: cpu_fallback {routes['cpu_fallback']}")
    on_device = routes["device_cold"] + routes["device_warm"]
    check(on_device == blocks - routes["cpu_adaptive"],
          f"{name} {kind}: device_cold+device_warm {on_device} != blocks {blocks} - cpu_adaptive {routes['cpu_adaptive']}")
    check(programs["recompiles"] == 0, f"{name} {kind}: recompiles {programs['recompiles']}")
    if kind == "warm":
        check(routes["cpu_adaptive"] == 0, f"{name} warm: cpu_adaptive {routes['cpu_adaptive']}")
        check(routes["device_cold"] == 0, f"{name} warm: device_cold {routes['device_cold']}")
        check(programs["built"] == 0, f"{name} warm: programs.built {programs['built']}")
    return {"device_routes": routes, "programs": programs}


def ask(srv: Server, name: str, sql: str, ref: dict, blocks: int, rows: int, n_devices: int) -> dict:
    """One SQL text: cold, then the literal repeat (the result cache's), then
    three device-warm runs — every answer compared, every response's routes
    checked. A new endTime is a new result-cache key and the same program."""
    start = "2024-05-01T00:00:00Z"
    end = "2024-05-02T00:00:{:02d}Z".format
    q: dict = {}
    records, stats, secs = srv.query(sql, start, end(0))
    q["answered_at_s"] = round(time.time() - srv.started, 1)
    compare(name, records, ref)
    check(stats["rows_scanned"] == rows or name == "select", f"{name}: scanned {stats['rows_scanned']} rows")
    q["cold_s"] = round(secs, 3)
    q["cold"] = check_routes(name, "cold", stats, blocks)
    if name != "select":  # aggregates only: the result cache holds interims
        records, stats, secs = srv.query(sql, start, end(0))
        compare(name, records, ref)
        check_routes(name, "cached", stats, blocks)
        q["result_cache_hit_s"] = round(secs, 3)
    shipped = srv.scan_bytes_shipped()
    check(shipped > 0, f"{name}: no block bytes counted as shipped after a cold run")
    q["warm_s"] = []
    for i in range(1, 4):
        records, stats, secs = srv.query(sql, start, end(i))
        compare(name, records, ref)
        q["warm"] = check_routes(name, "warm", stats, blocks)
        q["warm_s"].append(round(secs, 3))
    # block payload bytes (op="scan") must not move on warm runs, whatever the
    # topology; route h2d_bytes also carries the mesh path's per-query LUT and
    # accumulator ships, so it is held to 0 only where there is no mesh
    reshipped = srv.scan_bytes_shipped() - shipped
    check(reshipped == 0, f"{name}: warm runs shipped {reshipped} block bytes")
    h2d = q["warm"]["device_routes"]["h2d_bytes"]
    check(n_devices > 1 or h2d == 0, f"{name} warm: h2d_bytes {h2d}")
    return q


def smoke(args, workdir: Path, result: dict) -> None:
    rehearsal = args.cpu_rehearsal
    env = {k: v for k, v in os.environ.items() if k != "P_QUERY_ENGINE" and not k.startswith("P_TPU_")}
    env.update(
        {
            "P_NATIVE_REQUIRED": "1",  # a library that fails to build or load is a failure
            "P_STAGING_DIR": str(workdir / "staging"),
            "P_FS_DIR": str(workdir / "data"),
            "P_CHECK_UPDATE": "false",
            "P_SEND_ANONYMOUS_USAGE_DATA": "false",
            "PYTHONUNBUFFERED": "1",
        }
    )
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    cpu_env = {**env, "JAX_PLATFORMS": "cpu"}

    # -- is there a chip at all? (fail fast, before 32M rows are made)
    probe = run_child("probe", [], env, 300)
    check(rehearsal or probe["platform"] == "tpu",
          f"JAX found no TPU: {probe} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    # from here on there is a device to report, pass or fail
    result["device"] = probe
    note(f"probe: {probe}")

    # -- built from the files git would commit: the native library is built
    # here, on the machine that runs it (build.sh uses -march=native)
    t0 = time.time()
    build = subprocess.run(["sh", str(HERE / "parseable_tpu/native/build.sh")], capture_output=True, text=True)
    check(build.returncode == 0, f"native build failed: {build.stderr.strip()[-800:]}")
    note(f"native library built in {time.time() - t0:.1f}s")

    cache_dir = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or HERE / ".jax_cache")
    count_cache = lambda: len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    result["compile_cache"] = {"dir": str(cache_dir), "entries_before": count_cache()}

    # -- load
    ref_path = workdir / "reference.json"
    loaded = run_child(
        "loader",
        ["--seed", str(args.seed), "--rows", str(args.rows), "--batch-rows", str(args.batch_rows), "--out", str(ref_path)],
        cpu_env, 900,
    )
    check(loaded["rows"] == args.rows, f"loader wrote {loaded['rows']} rows, want {args.rows}")
    ref = json.loads(ref_path.read_text())
    blocks = loaded["minutes"]
    result["rows_loaded"] = loaded["rows"]
    note(f"loaded {loaded['rows']} rows in {loaded['minutes']} minute buckets in {loaded['secs']}s "
         f"(sparse groups hold {sorted(w[2] for w in ref['sparse'])[len(ref['sparse']) // 2]} rows at the median; "
         f"select matches {len(ref['select'])})")

    # -- serve
    srv = Server(env, workdir)
    try:
        result["boot_s"] = round(srv.wait_live(), 1)
        status, raw = srv.http("GET", "/api/v1/about")
        check(status == 200, f"/api/v1/about answered HTTP {status}")
        about = json.loads(raw)
        qd = about.get("queryDevice")
        check(about.get("queryEngine") == "tpu" and qd is not None, f"server does not run the tpu engine: {about}")
        note(f"server: {qd} (boot {result['boot_s']}s)")
        check(rehearsal or qd["platform"] == "tpu", f"the server's device is not a TPU: {qd}")
        served = {"platform": qd["platform"], "kind": qd["device_kind"], "count": qd["device_count"]}
        check(served == probe, f"the server runs on {served}, JAX reports {probe}")
        result["mesh"] = qd["mesh"]
        if qd["device_count"] > 1:
            n = 1 << (qd["device_count"].bit_length() - 1)
            check(qd["mesh"] == f"data:{n}", f"{qd['device_count']} devices but mesh {qd['mesh']!r}, want data:{n}")

        # request 1: ingest, ack read back by an exact count over staging
        rnd = random.Random(args.seed)
        batch = [{"level": rnd.choice(["info", "warn", "error"]), "code": rnd.randrange(1000), "msg": f"live {i}"} for i in range(500)]
        status, raw = srv.http("POST", "/api/v1/ingest", batch, {"X-P-Stream": LIVE_STREAM})
        check(status == 200, f"ingest answered HTTP {status}: {raw[:300]!r}")
        want = sum(1 for r in batch if r["level"] == "error" and r["code"] >= 500)
        records, stats, _ = srv.query(f"SELECT count(*) AS c FROM {LIVE_STREAM} WHERE level = 'error' AND code >= 500", "10m", "now")
        check(records == [{"c": want}], f"acked rows read back as {records}, want c={want}")
        check(stats.get("fast_path") is None, "the read-back count was answered from manifests")
        note(f"ingest: {len(batch)} rows acked, {want} matching rows read back from staging")

        # requests 2-4
        from bench import CONFIGS  # the BASELINE SQL texts; numpy/pyarrow only, no JAX

        queries = [(name, sql.format(stream=STREAM)) for name, sql in CONFIGS.items()]
        queries += [("sparse", SPARSE_SQL.format(stream=STREAM)), ("select", SELECT_SQL.format(stream=STREAM))]
        result["queries"] = {}
        for name, sql in queries:
            q = result["queries"][name] = ask(srv, name, sql, ref, blocks, args.rows, qd["device_count"])
            result.setdefault("first_answer_s", q.pop("answered_at_s"))
            note(f"{name}: cold {q['cold_s']}s {q['cold']} | warm {q['warm_s']}s {q['warm']}")

        # device memory after the warm pass, per device, as the server reports it
        in_use = srv.metric("parseable_tpu_device_memory_in_use", "device")
        peak = srv.metric("parseable_tpu_device_memory_peak", "device")
        result["memory"] = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}
        note(f"device memory: in use {in_use} peak {peak}")
        if not rehearsal:  # the CPU backend reports no memory_stats
            check(len(in_use) == qd["device_count"], f"memory gauges for {len(in_use)} of {qd['device_count']} devices")
            check(min(in_use.values()) > 0, f"a device holds no resident bytes: {in_use}")
            check(max(in_use.values()) <= 2 * min(in_use.values()), f"resident bytes differ by more than 2x across devices: {in_use}")
        status, raw = srv.http("GET", "/api/v1/about")
        mesh_built = json.loads(raw)["queryDevice"]["mesh_programs_built"]
        result["mesh_programs_built"] = mesh_built
        check((mesh_built > 0) == (qd["mesh"] is not None), f"mesh {qd['mesh']!r} but {mesh_built} mesh programs built")
    finally:
        code = srv.stop()
    check(code == 0, f"server exited {code} on SIGTERM: {srv.log_tail()}")
    log = srv.log_path.read_text(errors="replace")
    for needle in ("Traceback", "CPU fallback", "falling back to CPU"):
        check(needle not in log, f"{needle!r} in the server's log: ...{log[max(0, log.find(needle) - 300):][:900]}")
    result["compile_cache"]["entries_after_server"] = count_cache()

    # -- kernels, now that the chip is free again
    result["kernels"] = run_child("kernels", ["--cpu-rehearsal"] if rehearsal else [], env, 600)
    note(f"kernels: {result['kernels']}")
    check(rehearsal or result["kernels"]["platform"] == "tpu", "the kernel check did not run on a TPU")
    result["compile_cache"]["entries_after"] = count_cache()
    note(f"compile cache: {result['compile_cache']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny debug run on the CPU backend; output stamped platform=cpu, rehearsal=true")
    ap.add_argument("--keep", action="store_true", help="keep the scratch directory")
    ap.add_argument("--out-dir", default=str(HERE / "chiprun_out"),
                    help="where the full record and the server's log are left (default: ./chiprun_out)")
    ap.add_argument("--role", choices=["probe", "loader", "kernels"], help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--batch-rows", type=int, default=BATCH_ROWS, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.role == "probe":
        role_probe()
    elif args.role == "loader":
        role_loader(args.seed, args.rows, args.batch_rows, args.out)
    elif args.role == "kernels":
        role_kernels(args.cpu_rehearsal)
    if args.role:
        return 0

    result: dict = {"ok": False}
    if args.cpu_rehearsal:
        result.update({"rehearsal": True, "platform": "cpu"})
    workdir = None
    t0 = time.time()
    try:
        check((HERE / "parseable_tpu" / "server" / "__main__.py").is_file(),
              f"{HERE} holds chip_smoke.py but not the repository it drives")
        check(args.cpu_rehearsal or (args.rows >= DEFAULT_ROWS and args.batch_rows == BATCH_ROWS),
              f"--rows {args.rows} / --batch-rows {args.batch_rows}: a chip run loads at least {DEFAULT_ROWS} rows "
              f"in {BATCH_ROWS}-row buckets (smaller sizes are for --cpu-rehearsal)")
        # scratch: never ./staging; the name may be temporary
        workdir = Path(tempfile.mkdtemp(prefix="ptpu-chip-smoke-"))
        smoke(args, workdir, result)
        result["ok"] = True
    except Exception as e:  # the one boundary: report the failure, exit non-zero
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        result["reason"] = str(e) if isinstance(e, SmokeFailure) else f"{type(e).__name__}: {e}"
        print(f"chip_smoke: FAILED: {result['reason']}", file=sys.stderr, flush=True)
    result["total_s"] = round(time.time() - t0, 1)
    if workdir is not None:
        # what is too long for the end of the output goes to the output directory
        out_dir = Path(args.out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / "chip_smoke_detail.json").write_text(json.dumps(result, indent=1))
        if (workdir / "server.log").is_file():
            shutil.copy(workdir / "server.log", out_dir / "chip_smoke_server.log")
        if not args.keep:
            shutil.rmtree(workdir, ignore_errors=True)
    if "device" not in result:
        # no accelerator (or no repository around the script): no result
        return 1
    # everything observed, then the verdict as the LAST line: exactly
    # {"ok", "device"}, the device as JAX reported it to the probe
    print(json.dumps({"detail": result}))
    print(json.dumps({"ok": result["ok"], "device": result["device"]}), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
