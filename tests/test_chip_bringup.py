"""Bring-up rules (PR 21): where the compile cache goes, who may touch a JAX
backend, and chip_smoke.py's own failure modes. The chip itself is reached
only through chip_smoke.py on a machine that has one; here the script runs
its explicit CPU rehearsal."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _verdict_and_detail(stdout: str) -> tuple[dict, dict]:
    """The last stdout line (the verdict the driver reads) and the record
    in the `{"detail": ...}` line before it."""
    lines = stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2])["detail"]


def test_compile_cache_dir_is_fixed_or_left_to_the_environment(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory in code
    (JAX reads the variable itself). Unset: the fixed path inside the
    checkout — the path is part of the cache key, so it is never derived
    from a temporary name, a pid or the time."""
    import jax

    from parseable_tpu.utils import compile_cache as CC

    updates: list[tuple] = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert CC.configure_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in dict(updates)

    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert CC.configure_compile_cache() == str(REPO / ".jax_cache")
    assert dict(updates)["jax_compilation_cache_dir"] == str(REPO / ".jax_cache")
    assert CC.configure_compile_cache() == str(REPO / ".jax_cache")  # stable across calls
    # sub-second compiles (one per column slice of a packed block) are stored too
    assert dict(updates)["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_metrics_scrape_never_initialises_a_backend(monkeypatch):
    """One process per chip: a /metrics scrape on a node that has not run
    the TPU engine (an ingestor next to its querier) asks JAX nothing;
    gauges come only from the devices the engine itself resolved."""
    import jax

    from parseable_tpu.ops import device as D
    from parseable_tpu.utils import metrics

    def forbidden(*a, **kw):
        raise AssertionError("a metrics scrape asked JAX for its devices")

    monkeypatch.setattr(jax, "local_devices", forbidden)
    monkeypatch.setattr(jax, "devices", forbidden)
    monkeypatch.setattr(D, "_ENGINE_DEVICES", [])
    D.collect_device_gauges()  # no engine ran: nothing to report, nothing asked

    class FakeDevice:
        id = 7

        def memory_stats(self):
            return {"bytes_in_use": 123, "peak_bytes_in_use": 456}

    D.note_engine_devices([FakeDevice()])
    D.collect_device_gauges()
    value = lambda name: metrics.REGISTRY.get_sample_value(name, {"device": "7"})
    assert value("parseable_tpu_device_memory_in_use") == 123
    assert value("parseable_tpu_device_memory_peak") == 456


def test_chip_smoke_refuses_to_pass_without_a_tpu():
    """Without the rehearsal flag a CPU-only machine is a failure: non-zero
    exit, no result on stdout, the reason on stderr, no data loaded first."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "JAX found no TPU" in proc.stderr


def test_chip_smoke_alone_in_a_directory_prints_no_result(tmp_path):
    """The script without the repository around it has nothing to drive."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not the repository it drives" in proc.stderr


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal():
    """The whole script at a tiny size on 4 virtual CPU devices: loader,
    served queries against the numpy reference, route checks, mesh path,
    kernel check through the Pallas interpreter. Stamped as a rehearsal."""
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--cpu-rehearsal",
         "--rows", "400000", "--batch-rows", "100000"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    verdict, detail = _verdict_and_detail(proc.stdout)
    assert proc.returncode == 0 and verdict["ok"] is True, proc.stdout[-3000:]
    # the verdict line is exactly the contract's object, the device as JAX reports it
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu" and verdict["device"]["count"] == 4
    assert detail["rehearsal"] is True and detail["platform"] == "cpu"
    assert detail["mesh"] == "data:4" and detail["mesh_programs_built"] > 0
