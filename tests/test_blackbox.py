"""Black-box multi-process cluster smoke (scripts/blackbox.py in test reach).

ROADMAP: the cross-process unlock ("multi-process black-box cluster
harness"). This smoke boots REAL `python -m parseable_tpu.server`
processes (1 querier + 1 ingestor over one LocalFS store), ingests over
HTTP, waits for the sync tick to land parquet in the shared store, and
queries over HTTP — counts, grouped aggregates, and post-sync visibility
all asserted through the public API only, the way the reference tests
against running containers (docker-compose-distributed-test).

Runs in tier-1 (a few seconds on a warm page cache — the harness boots
processes cheaply by design); generous poll deadlines keep it stable on a
cold or loaded box.
"""

from __future__ import annotations

import importlib.util
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]


def _load_blackbox():
    spec = importlib.util.spec_from_file_location(
        "blackbox", REPO_ROOT / "scripts" / "blackbox.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_blackbox_cluster_ingest_sync_query(tmp_path):
    bb = _load_blackbox()
    with bb.ClusterHarness(tmp_path) as cluster:
        ing = cluster.spawn(
            "ingest",
            "ing0",
            env_extra={
                "P_LOCAL_SYNC_INTERVAL": "1",
                "P_STORAGE_UPLOAD_INTERVAL": "1",
            },
        )
        q = cluster.spawn("query", "q0")
        cluster.wait_live(ing)
        cluster.wait_live(q)

        rows = [{"host": f"h{i % 2}", "v": float(i)} for i in range(40)]
        cluster.ingest(ing, "bb", rows)

        # the querier must see every row over HTTP — first via the remote
        # staging window (fan-in), then from synced parquet; poll because
        # stream discovery + sync are asynchronous across processes
        def count_rows() -> int:
            try:
                recs, _ = cluster.query(
                    q, "SELECT count(*) c FROM bb", "10m", "now"
                )
            except RuntimeError:
                return -1  # stream not discovered yet
            return int(recs[0]["c"]) if recs else 0

        deadline = time.monotonic() + 90
        seen = count_rows()
        while time.monotonic() < deadline and seen != 40:
            time.sleep(0.5)
            seen = count_rows()
        assert seen == 40, f"querier saw {seen}/40 rows; logs: {ing.log_path}"

        # grouped aggregate over the same HTTP surface
        recs, stats = cluster.query(
            q,
            "SELECT host, count(*) c FROM bb GROUP BY host ORDER BY host",
            "10m",
            "now",
        )
        assert recs == [{"host": "h0", "c": 20}, {"host": "h1", "c": 20}]
        assert stats, "query response carried no stats block"

        # the sync tick must land parquet in the SHARED store (cross-process
        # durability, not just staging fan-in)
        deadline = time.monotonic() + 60
        store = tmp_path / "shared-store"
        while time.monotonic() < deadline:
            if list(store.rglob("*.parquet")):
                break
            time.sleep(0.5)
        assert list(store.rglob("*.parquet")), (
            f"ingestor never uploaded parquet; logs: {ing.log_path.read_text()[-2000:]}"
        )

        # post-sync: counts still exact (no dupes from staging+parquet union)
        assert count_rows() == 40

        # both processes still healthy end-to-end
        assert ing.alive() and q.alive()


def test_blackbox_kill_ingestor_recover_orphans(tmp_path):
    """Failure scenario (ROADMAP item 1): SIGKILL an ingestor mid-ingest,
    restart it on the SAME staging dir, and assert the restarted node's
    `recover_orphans` salvage makes every row acked before the kill
    queryable over HTTP again.

    The kill lands in the narrow crash window the salvage branch exists
    for — the writer closed its IPC footer but died before the
    `.part.arrows` -> `.arrows` rename. A SIGKILL can't be scheduled
    inside that microsecond window from outside, so the scenario
    reconstructs the exact on-disk state the window leaves behind:
    flush over HTTP (the staging fan-in route forces IPC footers), kill
    -9, then rename the finished files back to `.part.arrows`."""
    bb = _load_blackbox()
    with bb.ClusterHarness(tmp_path) as cluster:
        # long sync intervals: nothing leaves staging on its own
        frozen = {
            "P_LOCAL_SYNC_INTERVAL": "3600",
            "P_STORAGE_UPLOAD_INTERVAL": "3600",
        }
        ing = cluster.spawn("ingest", "ing0", env_extra=frozen)
        cluster.wait_live(ing)

        rows = [{"host": f"h{i % 2}", "v": float(i)} for i in range(30)]
        cluster.ingest(ing, "bb", rows)  # 30 rows ACKED over HTTP

        # force the staging flush over HTTP (the querier fan-in route calls
        # staging_batches -> flush(forced=True)): IPC footers land on disk.
        # The response body is Arrow IPC, so read it raw rather than as JSON.
        import urllib.request

        req = urllib.request.Request(f"{ing.url}/api/v1/internal/staging/bb")
        for k, v in bb.AUTH_HEADER.items():
            req.add_header(k, v)
        with urllib.request.urlopen(req, timeout=30.0) as resp:
            assert resp.status in (200, 204)
            resp.read()

        ing.kill()  # SIGKILL: no shutdown hooks, no sync
        assert not ing.alive()

        staging = tmp_path / "staging-ing0"
        finished = [
            f for f in staging.rglob("*.arrows")
            if not f.name.endswith(".part.arrows")
        ]
        assert finished, "flush left no finished staging files"
        # reconstruct the close-before-rename crash window state
        for f in finished:
            f.rename(f.with_name(f.name[: -len("arrows")] + "part.arrows"))
        assert not list(staging.rglob("*.data.arrows"))

        # restart on the SAME staging dir, with fast sync so salvaged rows
        # convert + upload; discovery via the stream-list route triggers
        # load_streams_from_storage -> get_or_create -> recover_orphans
        ing2 = cluster.spawn(
            "ingest",
            "ing0",
            env_extra={
                "P_LOCAL_SYNC_INTERVAL": "1",
                "P_STORAGE_UPLOAD_INTERVAL": "1",
            },
        )
        q = cluster.spawn("query", "q0")
        cluster.wait_live(ing2)
        cluster.wait_live(q)
        status, _ = bb.http_json("GET", f"{ing2.url}/api/v1/logstream")
        assert status == 200

        def count_rows() -> int:
            try:
                recs, _ = cluster.query(q, "SELECT count(*) c FROM bb", "10m", "now")
            except RuntimeError:
                return -1
            return int(recs[0]["c"]) if recs else 0

        deadline = time.monotonic() + 120
        seen = count_rows()
        while time.monotonic() < deadline and seen != 30:
            time.sleep(0.5)
            seen = count_rows()
        assert seen == 30, (
            f"post-restart count {seen} != 30 acked pre-kill; "
            f"logs: {ing2.log_path.read_text()[-2000:]}"
        )
        assert ing2.alive() and q.alive()


def test_blackbox_edge_kill_keepalive_midbody(tmp_path):
    """ISSUE 17: SIGKILL an ingestor that has open edge keep-alive
    connections parked MID-BODY, restart it on the same staging dir, and
    prove the books still balance — every row acked over the edge before
    the kill is queryable again, and the half-received bodies (never
    acked, never parsed) added nothing. The edge's C-side buffers die with
    the process; only acked work may survive, exactly like the aiohttp
    tier."""
    import base64
    import socket

    bb = _load_blackbox()
    auth = "Basic " + base64.b64encode(b"admin:admin").decode()
    with bb.ClusterHarness(tmp_path) as cluster:
        edge_port = bb.free_port()
        frozen = {
            "P_LOCAL_SYNC_INTERVAL": "3600",
            "P_STORAGE_UPLOAD_INTERVAL": "3600",
            "P_EDGE_PORT": str(edge_port),
        }
        ing = cluster.spawn("ingest", "ing0", env_extra=frozen)
        cluster.wait_live(ing)

        def edge_post(sock: socket.socket, rows: bytes) -> None:
            sock.sendall(
                b"POST /api/v1/ingest HTTP/1.1\r\nHost: t\r\n"
                b"Authorization: " + auth.encode() + b"\r\n"
                b"X-P-Stream: ek\r\n"
                b"Content-Length: %d\r\n\r\n" % len(rows) + rows
            )
            resp = b""
            while b"\r\n\r\n" not in resp:
                chunk = sock.recv(65536)
                if not chunk:
                    raise ConnectionError("edge closed mid-response")
                resp += chunk
            assert resp.startswith(b"HTTP/1.1 200"), resp[:200]

        # 30 rows ACKED over ONE edge keep-alive connection
        acked = 0
        deadline = time.monotonic() + 30
        while True:
            try:
                ka = socket.create_connection(("127.0.0.1", edge_port), timeout=30)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        with ka:
            for i in range(10):
                batch = b'[{"host": "h%d", "v": %d.0}, {"host": "x", "v": 0.0}, {"host": "y", "v": 1.0}]' % (i % 2, i)
                edge_post(ka, batch)
                acked += 3

            # force IPC footers onto disk (staging fan-in flushes forced)
            import urllib.request

            req = urllib.request.Request(f"{ing.url}/api/v1/internal/staging/ek")
            for k, v in bb.AUTH_HEADER.items():
                req.add_header(k, v)
            with urllib.request.urlopen(req, timeout=30.0) as resp:
                assert resp.status in (200, 204)
                resp.read()

            # two MORE keep-alive connections parked mid-body: headers sent,
            # Content-Length promises 4096 bytes, only half arrive
            hung = []
            for _ in range(2):
                h = socket.create_connection(("127.0.0.1", edge_port), timeout=30)
                h.sendall(
                    b"POST /api/v1/ingest HTTP/1.1\r\nHost: t\r\n"
                    b"Authorization: " + auth.encode() + b"\r\n"
                    b"X-P-Stream: ek\r\nContent-Length: 4096\r\n\r\n"
                    + b'[{"half": "' + b"z" * 2000
                )
                hung.append(h)

            ing.kill()  # SIGKILL with the keep-alive + mid-body conns open
            assert not ing.alive()
            for h in hung:
                h.close()

        # restart on the SAME staging dir and edge port, fast sync now
        ing2 = cluster.spawn(
            "ingest",
            "ing0",
            env_extra={
                "P_LOCAL_SYNC_INTERVAL": "1",
                "P_STORAGE_UPLOAD_INTERVAL": "1",
                "P_EDGE_PORT": str(edge_port),
            },
        )
        q = cluster.spawn("query", "q0")
        cluster.wait_live(ing2)
        cluster.wait_live(q)
        status, _ = bb.http_json("GET", f"{ing2.url}/api/v1/logstream")
        assert status == 200

        def count_rows() -> int:
            try:
                recs, _ = cluster.query(q, "SELECT count(*) c FROM ek", "10m", "now")
            except RuntimeError:
                return -1
            return int(recs[0]["c"]) if recs else 0

        deadline = time.monotonic() + 120
        seen = count_rows()
        while time.monotonic() < deadline and seen != acked:
            time.sleep(0.5)
            seen = count_rows()
        assert seen == acked, (
            f"post-restart count {seen} != {acked} acked via edge pre-kill; "
            f"logs: {ing2.log_path.read_text()[-2000:]}"
        )

        # the restarted edge must be serving again on the same port
        with socket.create_connection(("127.0.0.1", edge_port), timeout=30) as s:
            edge_post(s, b'[{"host": "post-restart", "v": 1.0}]')
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and count_rows() != acked + 1:
            time.sleep(0.5)
        assert count_rows() == acked + 1
        assert ing2.alive() and q.alive()
