"""Black-box multi-process cluster harness (ROADMAP: "multi-process
black-box cluster harness + failure-scenario suite").

Boots REAL `parseable_tpu.server` processes — query / ingest modes over a
shared LocalFS object store — and drives them purely over HTTP, the way the
reference tests against running containers (docker-compose-distributed-test).
Used by `tests/test_blackbox.py` and importable from future failure
scenarios: kill a node mid-sync, rolling restarts, querier LB with a dead
peer.

Processes boot cheaply: ~a few seconds each (the JAX import dominates), and
`ClusterHarness` tears everything down with terminate -> kill escalation so
a failed run can't leak servers.
"""

from __future__ import annotations

import base64
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

AUTH_HEADER = {"Authorization": "Basic " + base64.b64encode(b"admin:admin").decode()}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Node:
    """One running server process."""

    def __init__(
        self,
        proc: subprocess.Popen,
        mode: str,
        port: int,
        log_path: Path,
        flight_port: int = 0,
    ):
        self.proc = proc
        self.mode = mode
        self.port = port
        self.log_path = log_path
        self.flight_port = flight_port  # 0 = HTTP-only data plane

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self, timeout: float = 10.0) -> None:
        if not self.alive():
            return
        self.proc.terminate()
        try:
            self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(5)

    def kill(self) -> None:
        """Hard kill — the crash-recovery scenarios' failure injection."""
        if self.alive():
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait(5)


def http_json(
    method: str,
    url: str,
    body: dict | list | None = None,
    headers: dict | None = None,
    timeout: float = 30.0,
):
    """One JSON round trip; returns (status, parsed-or-None)."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    for k, v in {**AUTH_HEADER, **(headers or {})}.items():
        req.add_header(k, v)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        try:
            return resp.status, json.loads(raw) if raw else None
        except json.JSONDecodeError:
            return resp.status, None


def http_json_headers(
    method: str,
    url: str,
    body: dict | list | None = None,
    headers: dict | None = None,
    timeout: float = 30.0,
):
    """Like http_json but also returns response headers — trace-stitching
    scenarios need X-P-Trace-Id off the query response."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    for k, v in {**AUTH_HEADER, **(headers or {})}.items():
        req.add_header(k, v)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        try:
            parsed = json.loads(raw) if raw else None
        except json.JSONDecodeError:
            parsed = None
        return resp.status, parsed, dict(resp.headers)


class ClusterHarness:
    """Spawn + drive a real multi-process cluster over one LocalFS store."""

    def __init__(self, workdir: Path):
        self.workdir = Path(workdir)
        self.store = self.workdir / "shared-store"
        self.nodes: list[Node] = []

    def spawn(
        self,
        mode: str,
        name: str,
        env_extra: dict | None = None,
        port: int | None = None,
        flight: bool = False,
    ) -> Node:
        port = port or free_port()
        flight_port = free_port() if flight else 0
        staging = self.workdir / f"staging-{name}"
        staging.mkdir(parents=True, exist_ok=True)
        log_dir = self.workdir / "logs"
        log_dir.mkdir(parents=True, exist_ok=True)
        log_path = log_dir / f"{name}.log"
        env = dict(os.environ)
        env.update(
            {
                "P_MODE": mode,
                "P_ADDR": f"127.0.0.1:{port}",
                "P_FS_DIR": str(self.store),
                "P_STAGING_DIR": str(staging),
                "P_CHECK_UPDATE": "false",
                "P_SEND_ANONYMOUS_USAGE_DATA": "false",
                "P_QUERY_ENGINE": "cpu",
                "JAX_PLATFORMS": "cpu",
                "PYTHONUNBUFFERED": "1",
            }
        )
        if flight_port:
            env["P_FLIGHT_PORT"] = str(flight_port)
        env.update(env_extra or {})
        # append: a re-spawned node (rolling restart, crash-recovery
        # scenarios) keeps its pre-kill log instead of truncating it
        log = open(log_path, "ab")
        log.write(f"--- spawn {name} mode={mode} port={port} ---\n".encode())
        log.flush()
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "parseable_tpu.server"],
                cwd=str(REPO_ROOT),
                env=env,
                stdout=log,
                stderr=subprocess.STDOUT,
            )
        finally:
            log.close()  # the child inherited the fd
        node = Node(proc, mode, port, log_path, flight_port=flight_port)
        self.nodes.append(node)
        return node

    def wait_live(self, node: Node, timeout: float = 90.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not node.alive():
                raise RuntimeError(
                    f"{node.mode} node died during boot; log tail:\n"
                    + node.log_path.read_text()[-2000:]
                )
            try:
                status, _ = http_json("GET", f"{node.url}/api/v1/liveness", timeout=2.0)
                if status == 200:
                    return
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.25)
        raise TimeoutError(
            f"{node.mode} node on :{node.port} not live after {timeout}s; log tail:\n"
            + node.log_path.read_text()[-2000:]
        )

    def ingest(self, node: Node, stream: str, rows: list[dict]) -> None:
        status, _ = http_json(
            "POST",
            f"{node.url}/api/v1/ingest",
            rows,
            headers={"X-P-Stream": stream},
        )
        if status != 200:
            raise RuntimeError(f"ingest to :{node.port} failed: {status}")

    def query(
        self,
        node: Node,
        sql: str,
        start: str | None = None,
        end: str | None = None,
        timeout: float = 60.0,
    ) -> tuple[list[dict], dict]:
        """POST /api/v1/query with fields=true -> (records, stats)."""
        body: dict = {"query": sql, "fields": True}
        if start:
            body["startTime"] = start
        if end:
            body["endTime"] = end
        status, out = http_json("POST", f"{node.url}/api/v1/query", body, timeout=timeout)
        if status != 200 or out is None:
            raise RuntimeError(f"query on :{node.port} failed: {status} {out}")
        return out["records"], out.get("stats", {})

    def query_traced(
        self,
        node: Node,
        sql: str,
        start: str | None = None,
        end: str | None = None,
        timeout: float = 60.0,
    ) -> tuple[list[dict], dict, str]:
        """query() + the X-P-Trace-Id the server minted for this request."""
        body: dict = {"query": sql, "fields": True}
        if start:
            body["startTime"] = start
        if end:
            body["endTime"] = end
        status, out, headers = http_json_headers(
            "POST", f"{node.url}/api/v1/query", body, timeout=timeout
        )
        if status != 200 or out is None:
            raise RuntimeError(f"query on :{node.port} failed: {status} {out}")
        return out["records"], out.get("stats", {}), headers.get("X-P-Trace-Id", "")

    def cluster_trace(self, node: Node, trace_id: str, timeout: float = 30.0) -> dict:
        """GET the stitched cross-node span tree for one trace."""
        status, out = http_json(
            "GET", f"{node.url}/api/v1/cluster/trace/{trace_id}", timeout=timeout
        )
        if status != 200 or out is None:
            raise RuntimeError(f"cluster trace on :{node.port} failed: {status} {out}")
        return out

    def audit(
        self,
        node: Node,
        scope: str = "cluster",
        quiesce: bool = True,
        timeout: float = 60.0,
    ) -> dict:
        """Run the conservation-law audit and return its report."""
        url = (
            f"{node.url}/api/v1/cluster/audit"
            f"?scope={scope}&quiesce={'1' if quiesce else '0'}"
        )
        status, out = http_json("GET", url, timeout=timeout)
        if status != 200 or out is None:
            raise RuntimeError(f"audit on :{node.port} failed: {status} {out}")
        return out

    def log_tails(self, limit: int = 2000) -> str:
        """Per-node log tails, for attaching to failure reports."""
        chunks = []
        seen: set[Path] = set()
        for node in self.nodes:
            if node.log_path in seen:
                continue
            seen.add(node.log_path)
            try:
                text = node.log_path.read_text(errors="replace")[-limit:]
            except OSError as e:
                text = f"(log unreadable: {e})"
            chunks.append(f"--- {node.log_path.name} ({node.mode}:{node.port}) ---\n{text}")
        return "\n".join(chunks)

    def stop_all(self) -> None:
        for node in self.nodes:
            node.stop()
        self.nodes.clear()

    def __enter__(self) -> "ClusterHarness":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None and self.nodes:
            # scenario failed: surface what every node was doing before
            # teardown destroys the processes (logs stay on disk under
            # workdir/logs/ either way)
            sys.stderr.write(
                f"\n[blackbox] scenario failed ({exc_type.__name__}); "
                f"node log tails:\n{self.log_tails()}\n"
            )
        self.stop_all()
