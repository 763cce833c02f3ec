"""dlint (parseable_tpu/analysis/device/) — per-rule TP/TN/suppression
fixtures, fingerprint stability, CLI contract, the P_DLINT tripwire, and
the live-tree gate.

Fixture trees are synthetic minimal repos written into tmp_path at device
-layer rel paths (the rules are path-scoped): each rule is exercised
against the disciplined shape (true-negative), the same shape with the
discipline broken (true-positive), and the broken shape with an inline
``# dlint: disable`` suppression.  The live-tree test at the bottom is the
acceptance gate: the real repo must report zero findings against an EMPTY
.dlint-baseline.json.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import pytest

from parseable_tpu.analysis.device import run_device_analysis
from parseable_tpu.analysis.device.rules_jit import (
    DonationHazardRule,
    DtypePromotionRule,
    JitCacheDisciplineRule,
    TracedControlFlowRule,
)
from parseable_tpu.analysis.device.rules_sync import (
    HostSyncRule,
    TransferDisciplineRule,
)

REPO_ROOT = Path(__file__).resolve().parents[1]

# the executor file IS the device layer for path-scoped rules; fixtures
# impersonate it inside their synthetic tree
EXEC_REL = "parseable_tpu/query/executor_tpu.py"
OPS_REL = "parseable_tpu/ops/kernels.py"


def _tree(root: Path, files: dict[str, str]) -> Path:
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text), encoding="utf-8")
    return root


# ------------------------------------------------------ jit-cache-discipline

_CACHED_JIT_OK = """\
import jax

_PROGRAMS = {}  # jit-cache: demo


def dense(xs, key):
    prog = _PROGRAMS.get(key)
    if prog is None:
        def body(x):
            return x + 1
        prog = jax.jit(body)  # jit-cache: demo.dense
        _PROGRAMS[key] = prog
    return prog(xs)
"""


def test_jit_cache_tn_full_discipline(tmp_path):
    root = _tree(tmp_path, {EXEC_REL: _CACHED_JIT_OK})
    report = run_device_analysis(root, rules=[JitCacheDisciplineRule()])
    assert report.findings == []


def test_jit_cache_tp_unannotated_call_time_jit(tmp_path):
    bare = """\
    import jax


    def dense(xs):
        def body(x):
            return x + 1
        prog = jax.jit(body)
        return prog(xs)
    """
    root = _tree(tmp_path, {EXEC_REL: bare})
    report = run_device_analysis(root, rules=[JitCacheDisciplineRule()])
    assert len(report.findings) == 1
    f = report.findings[0]
    assert f.rule == "jit-cache-discipline"
    assert "builds a program on every" in f.message


def test_jit_cache_tp_undeclared_family_and_missing_store(tmp_path):
    undeclared = """\
    import jax


    def dense(xs):
        def body(x):
            return x + 1
        prog = jax.jit(body)  # jit-cache: ghost.dense
        return prog(xs)
    """
    root = _tree(tmp_path, {EXEC_REL: undeclared})
    report = run_device_analysis(root, rules=[JitCacheDisciplineRule()])
    assert len(report.findings) == 1
    assert "no module-level declaration" in report.findings[0].message

    no_store = """\
    import jax

    _PROGRAMS = {}  # jit-cache: demo


    def dense(xs, key):
        prog = _PROGRAMS.get(key)
        if prog is None:
            def body(x):
                return x + 1
            prog = jax.jit(body)  # jit-cache: demo.dense
        return prog(xs)
    """
    root2 = _tree(tmp_path / "b", {EXEC_REL: no_store})
    report = run_device_analysis(root2, rules=[JitCacheDisciplineRule()])
    assert len(report.findings) == 1
    assert "stored into" in report.findings[0].message


def test_jit_cache_suppression(tmp_path):
    suppressed = """\
    import jax


    def dense(xs):
        def body(x):
            return x + 1
        prog = jax.jit(body)  # dlint: disable=jit-cache-discipline
        return prog(xs)
    """
    root = _tree(tmp_path, {EXEC_REL: suppressed})
    report = run_device_analysis(root, rules=[JitCacheDisciplineRule()])
    assert report.findings == []


# ------------------------------------------------------- traced-control-flow


def test_traced_control_flow_tp_decorator_and_call_time(tmp_path):
    src = """\
    import jax
    import jax.numpy as jnp


    @jax.jit
    def clamp(x, lim):
        if x > lim:
            return lim
        return x


    def run(xs):
        def body(v):
            while v.sum() > 0:
                v = v - 1
            return v
        return jax.jit(body)(xs)
    """
    root = _tree(tmp_path, {OPS_REL: src})
    report = run_device_analysis(root, rules=[TracedControlFlowRule()])
    kinds = sorted((f.line, f.message.split("`")[1]) for f in report.findings)
    assert len(report.findings) == 2, [f.message for f in report.findings]
    assert [k for _, k in kinds] == ["if", "while"]


def test_traced_control_flow_tn_static_and_structural(tmp_path):
    src = """\
    from functools import partial

    import jax


    @partial(jax.jit, static_argnums=(1,))
    def pad(x, n):
        if n > 4:
            return x
        return x


    @jax.jit
    def shape_gate(x, extra):
        if x.shape[0] > 2:
            return x
        if extra is None:
            return x
        return x + extra
    """
    root = _tree(tmp_path, {OPS_REL: src})
    report = run_device_analysis(root, rules=[TracedControlFlowRule()])
    assert report.findings == [], [f.message for f in report.findings]


def test_traced_control_flow_suppression(tmp_path):
    src = """\
    import jax


    @jax.jit
    def clamp(x, lim):
        if x > lim:  # dlint: disable=traced-control-flow
            return lim
        return x
    """
    root = _tree(tmp_path, {OPS_REL: src})
    report = run_device_analysis(root, rules=[TracedControlFlowRule()])
    assert report.findings == []


# --------------------------------------------------------- dtype-promotion


def test_dtype_promotion_tp_in_traced_body_and_x64_flip(tmp_path):
    src = """\
    import jax
    import numpy as np

    jax.config.update("jax_enable_x64", True)


    @jax.jit
    def widen(x):
        return x.astype(np.float64)
    """
    root = _tree(tmp_path, {OPS_REL: src})
    report = run_device_analysis(root, rules=[DtypePromotionRule()])
    msgs = [f.message for f in report.findings]
    assert len(report.findings) == 2, msgs
    assert any("float64 reference" in m for m in msgs)
    assert any("jax_enable_x64" in m for m in msgs)


def test_dtype_promotion_tn_host_side_and_explicit_off(tmp_path):
    src = """\
    import jax
    import numpy as np

    jax.config.update("jax_enable_x64", False)


    def host_summary(arr):
        return np.asarray(arr, dtype=np.float64).mean()
    """
    root = _tree(tmp_path, {OPS_REL: src})
    report = run_device_analysis(root, rules=[DtypePromotionRule()])
    assert report.findings == [], [f.message for f in report.findings]


# --------------------------------------------------------- donation-hazard


def test_donation_hazard_tp_use_after_donate(tmp_path):
    src = """\
    import jax


    def fold(acc, x):
        def step(a, b):
            return a + b
        f = jax.jit(step, donate_argnums=(0,))
        out = f(acc, x)
        return out + acc
    """
    root = _tree(tmp_path, {EXEC_REL: src})
    report = run_device_analysis(root, rules=[DonationHazardRule()])
    assert len(report.findings) == 1
    assert "no longer exists after dispatch" in report.findings[0].message


def test_donation_hazard_tn_rebound_before_read(tmp_path):
    src = """\
    import jax


    def fold(acc, x):
        def step(a, b):
            return a + b
        f = jax.jit(step, donate_argnums=(0,))
        out = f(acc, x)
        acc = out
        return acc
    """
    root = _tree(tmp_path, {EXEC_REL: src})
    report = run_device_analysis(root, rules=[DonationHazardRule()])
    assert report.findings == []


def test_donation_missed_is_advisory_and_comment_silences(tmp_path):
    bare = """\
    import jax


    def fold(x):
        def step(a):
            return a + 1
        f = jax.jit(step)
        return f(x)
    """
    root = _tree(tmp_path, {EXEC_REL: bare})
    report = run_device_analysis(root, rules=[DonationHazardRule()])
    assert report.findings == []  # advisory only: never gates
    assert report.clean
    assert len(report.advisories) == 1
    assert "without donate_argnums" in report.advisories[0].message

    documented = bare.replace(
        "        f = jax.jit(step)",
        "        # no donate: the input outlives the call\n"
        "        f = jax.jit(step)",
    )
    root2 = _tree(tmp_path / "b", {EXEC_REL: documented})
    report = run_device_analysis(root2, rules=[DonationHazardRule()])
    assert report.advisories == []


# --------------------------------------------------------------- host-sync

_HOT_CHAIN = """\
import jax.numpy as jnp


def dispatch(tables):
    for t in tables:  # device-hot: per-block dispatch
        consume(t)


def consume(t):
    return finish(t)


def finish(t):
    x = jnp.sum(t)
    return float(x)
"""


def test_host_sync_tp_three_deep_call_chain(tmp_path):
    root = _tree(tmp_path, {EXEC_REL: _HOT_CHAIN})
    report = run_device_analysis(root, rules=[HostSyncRule()])
    assert len(report.findings) == 1, [f.message for f in report.findings]
    f = report.findings[0]
    assert f.rule == "host-sync"
    assert "float() on a device array" in f.message
    # the chain from the device-hot root is part of the message
    assert "dispatch -> consume -> finish" in f.message


def test_host_sync_tn_declared_boundary_and_no_root(tmp_path):
    declared = _HOT_CHAIN.replace(
        "    return float(x)",
        "    # sync-boundary: priced readback probe\n    return float(x)",
    )
    root = _tree(tmp_path, {EXEC_REL: declared})
    report = run_device_analysis(root, rules=[HostSyncRule()])
    assert report.findings == []

    # same sync, no `# device-hot` root anywhere: unreachable, no finding
    unrooted = _HOT_CHAIN.replace("  # device-hot: per-block dispatch", "")
    root2 = _tree(tmp_path / "b", {EXEC_REL: unrooted})
    report = run_device_analysis(root2, rules=[HostSyncRule()])
    assert report.findings == []


def test_host_sync_item_and_block_until_ready_flagged(tmp_path):
    src = """\
    def dispatch(xs):
        for x in xs:  # device-hot: dispatch
            step(x)


    def step(x):
        x.block_until_ready()
        return x.item()
    """
    root = _tree(tmp_path, {EXEC_REL: src})
    report = run_device_analysis(root, rules=[HostSyncRule()])
    msgs = sorted(f.message for f in report.findings)
    assert len(msgs) == 2, msgs
    assert any(".block_until_ready()" in m for m in msgs)
    assert any(".item()" in m for m in msgs)


# ------------------------------------------------------- transfer-discipline

_UNPRICED_PUT = """\
import jax


def ship(host, sharding):
    dev = jax.device_put(host, sharding)
    return dev
"""


def test_transfer_tp_unpriced_put(tmp_path):
    root = _tree(tmp_path, {EXEC_REL: _UNPRICED_PUT})
    report = run_device_analysis(root, rules=[TransferDisciplineRule()])
    assert len(report.findings) == 1
    assert "not priced into" in report.findings[0].message


def test_transfer_tn_priced_and_annotated(tmp_path):
    priced = """\
    import jax


    def ship(host, sharding, stats):
        stats["h2d_bytes"] += int(host.nbytes)
        return jax.device_put(host, sharding)


    def ship_elsewhere(host, sharding):
        # link-priced: caller tallies nbytes into the scan tick
        return jax.device_put(host, sharding)
    """
    root = _tree(tmp_path, {EXEC_REL: priced})
    report = run_device_analysis(root, rules=[TransferDisciplineRule()])
    assert report.findings == [], [f.message for f in report.findings]


def test_transfer_lambda_is_opaque_to_function_pricing(tmp_path):
    src = """\
    import jax


    def ship_all(parts, sharding, stats):
        stats["h2d_bytes"] += 1
        put = lambda a: jax.device_put(a, sharding)
        return [put(p) for p in parts]
    """
    root = _tree(tmp_path, {EXEC_REL: src})
    report = run_device_analysis(root, rules=[TransferDisciplineRule()])
    assert len(report.findings) == 1
    assert "inside a lambda" in report.findings[0].message


# ------------------------------------------------------ fingerprint stability


def test_fingerprint_stable_under_line_shift(tmp_path):
    root = _tree(tmp_path / "a", {EXEC_REL: _UNPRICED_PUT})
    before = run_device_analysis(root, rules=[TransferDisciplineRule()]).findings
    assert len(before) == 1

    shifted = "# one\n# two\n# three\n" + _UNPRICED_PUT
    root2 = _tree(tmp_path / "b", {EXEC_REL: shifted})
    after = run_device_analysis(root2, rules=[TransferDisciplineRule()]).findings
    assert len(after) == 1
    assert after[0].line == before[0].line + 3
    assert after[0].fingerprint == before[0].fingerprint


# ----------------------------------------------------------- CLI contract


def _dlint_cli(root: Path, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [
            sys.executable,
            "-m",
            "parseable_tpu.analysis.device",
            "--root",
            str(root),
            *args,
        ],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_cli_exit_codes_json_and_baseline(tmp_path):
    root = _tree(tmp_path, {EXEC_REL: _UNPRICED_PUT})
    r = _dlint_cli(root, "--json")
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["clean"] is False
    assert len(doc["findings"]) == 1
    assert doc["findings"][0]["rule"] == "transfer-discipline"
    assert doc["findings"][0]["fingerprint"]
    assert doc["advisories"] == []

    # acknowledge into the baseline -> clean run
    r = _dlint_cli(root, "--write-baseline")
    assert r.returncode == 0, r.stdout + r.stderr
    assert (root / ".dlint-baseline.json").is_file()
    r = _dlint_cli(root)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 baselined" in r.stdout


def test_cli_json_out_artifact(tmp_path):
    root = _tree(tmp_path, {EXEC_REL: _UNPRICED_PUT})
    out = tmp_path / "dlint.json"
    r = _dlint_cli(root, "--json-out", str(out))
    assert r.returncode == 1
    doc = json.loads(out.read_text())
    assert doc["findings"][0]["rule"] == "transfer-discipline"


def test_cli_rule_selection_and_catalog(tmp_path):
    root = _tree(tmp_path, {EXEC_REL: _UNPRICED_PUT})
    # restricting to an unrelated rule hides the transfer finding
    r = _dlint_cli(root, "--rule", "host-sync")
    assert r.returncode == 0, r.stdout + r.stderr
    r = _dlint_cli(root, "--rule", "no-such-rule")
    assert r.returncode == 2

    r = _dlint_cli(root, "--list-rules")
    assert r.returncode == 0
    for name in (
        "jit-cache-discipline",
        "host-sync",
        "traced-control-flow",
        "transfer-discipline",
        "dtype-promotion",
        "donation-hazard",
    ):
        assert name in r.stdout
    assert "bench-sync" not in r.stdout

    r = _dlint_cli(root, "--explain", "transfer-discipline")
    assert r.returncode == 0
    assert "# dlint: disable=transfer-discipline" in r.stdout


# --------------------------------------------------------- P_DLINT tripwire


def _plugin(budget: int = 1):
    from parseable_tpu.analysis.device.tripwire import DlintPytestPlugin

    p = DlintPytestPlugin()
    p.budget = budget
    p._nodeid = "tests/test_x.py::test_demo"
    return p


def test_tripwire_declared_name_reads_annotation(tmp_path):
    src = tmp_path / "site.py"
    src.write_text(
        "import jax\n"
        "prog = jax.jit(fn)  # jit-cache: fam.same_line\n"
        "# jit-cache: fam.line_above\n"
        "prog2 = jax.jit(fn)\n",
        encoding="utf-8",
    )
    p = _plugin()
    assert p._declared_name(str(src), 2) == "fam.same_line"
    assert p._declared_name(str(src), 4) == "fam.line_above"
    assert p._declared_name(str(src), 1) is None


def test_tripwire_duplicate_creation_budget(monkeypatch):
    p = _plugin(budget=1)
    site = ("parseable_tpu/query/executor_tpu.py", 10, "q", "dupe.prog", "('k', 8)")
    monkeypatch.setattr(p, "_site", lambda: site)
    # budget+1 creations for one (program, key, test) are tolerated (one
    # benign cold-key race); the next one is the per-call-jit bug
    p._record_creation()
    p._record_creation()
    assert p.violations == []
    p._record_creation()
    assert len(p.violations) == 1
    v = p.violations[0]
    assert v["kind"] == "duplicate-creation" and v["program"] == "dupe.prog"
    rep = p.assemble_report()
    assert rep["clean"] is False
    assert rep["programs"]["dupe.prog"]["creations"] == 3
    assert rep["programs"]["dupe.prog"]["distinct_keys"] == 1


def test_tripwire_recompile_budget_and_metric():
    from parseable_tpu.utils import metrics

    p = _plugin(budget=1)
    program = "triptest.metric"
    site = ("parseable_tpu/query/executor_tpu.py", 20, "q", program, "('k',)")

    def sample():
        return (
            metrics.REGISTRY.get_sample_value(
                "parseable_tpu_recompiles_total", {"program": program}
            )
            or 0.0
        )

    before = sample()
    p._record_compile(site, total=1, delta=1)
    assert p.violations == []
    p._record_compile(site, total=2, delta=1)
    assert len(p.violations) == 1
    assert p.violations[0]["kind"] == "recompile"
    assert sample() == before + 1


def test_tripwire_undeclared_sites_tracked_never_enforced(monkeypatch):
    p = _plugin(budget=1)
    site = ("parseable_tpu/ops/kernels.py", 5, "<module>", None, "")
    monkeypatch.setattr(p, "_site", lambda: site)
    for _ in range(5):
        p._record_creation()
    p._record_compile(site, total=5, delta=1)
    assert p.violations == []
    rep = p.assemble_report()
    assert rep["clean"] is True
    assert rep["undeclared"]["parseable_tpu/ops/kernels.py:5"]["creations"] == 5


def test_tripwire_proxy_detects_real_compiles():
    """End-to-end compile detection: one proxy called with two different
    shape classes really compiles twice, tripping the budget."""
    import jax
    import jax.numpy as jnp

    from parseable_tpu.analysis.device.tripwire import _JitProxy

    p = _plugin(budget=1)
    site = ("tests/test_analysis_device.py", 1, "t", "triptest.proxy", "('k',)")
    jitted = jax.jit(lambda v: v + 1)
    proxy = _JitProxy(jitted, p, site)
    proxy(jnp.ones((4,), dtype=jnp.float32))
    proxy(jnp.ones((8,), dtype=jnp.float32))  # new shape: second real compile
    assert proxy.compiles >= 2
    assert any(v["kind"] == "recompile" for v in p.violations)


def test_tripwire_sessionfinish_writes_artifact_and_flips_exit(tmp_path):
    p = _plugin(budget=1)
    p.json_path = str(tmp_path / "trip.json")
    p._violate("recompile", "x.y", "synthetic")
    session = SimpleNamespace(exitstatus=0)
    p.pytest_sessionfinish(session, 0)
    assert session.exitstatus == 1
    doc = json.loads((tmp_path / "trip.json").read_text())
    assert doc["clean"] is False
    assert doc["violations"][0]["program"] == "x.y"


_TRIP_CONFTEST = """\
import os


def pytest_configure(config):
    if os.environ.get("P_DLINT") == "1" and not config.pluginmanager.has_plugin(
        "dlint"
    ):
        from parseable_tpu.analysis.device.tripwire import DlintPytestPlugin

        config.pluginmanager.register(DlintPytestPlugin(), "dlint")
"""


def _run_tripwire_session(tmp_path, test_src: str) -> tuple[int, dict]:
    _tree(tmp_path, {"conftest.py": _TRIP_CONFTEST, "test_trip.py": test_src})
    json_path = tmp_path / "trip.json"
    env = {
        **os.environ,
        "P_DLINT": "1",
        "P_DLINT_JSON": str(json_path),
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(REPO_ROOT) + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    r = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytest",
            "test_trip.py",
            "-q",
            "-p",
            "no:cacheprovider",
        ],
        cwd=tmp_path,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
    )
    return r.returncode, json.loads(json_path.read_text())


def test_tripwire_session_trips_on_per_call_jit(tmp_path):
    """The motivating bug, reproduced: a jit built per call for the same
    cache key blows the creation budget and turns the session red."""
    rc, doc = _run_tripwire_session(
        tmp_path,
        textwrap.dedent(
            """\
            import jax
            import jax.numpy as jnp


            def test_per_call_jit_same_key():
                for _ in range(3):
                    key = ("demo", 8)
                    prog = jax.jit(lambda v: v + 1)  # jit-cache: demo.loop
                    out = prog(jnp.ones((4,), dtype=jnp.float32))
                    assert key and out.shape == (4,)
            """
        ),
    )
    assert rc == 1
    assert doc["clean"] is False
    assert doc["programs"]["demo.loop"]["creations"] == 3
    assert any(
        v["kind"] == "duplicate-creation" and v["program"] == "demo.loop"
        for v in doc["violations"]
    )


def test_tripwire_session_clean_for_cached_program(tmp_path):
    """The disciplined shape: one cached program serving three warm calls
    compiles once and the session stays green."""
    rc, doc = _run_tripwire_session(
        tmp_path,
        textwrap.dedent(
            """\
            import jax
            import jax.numpy as jnp

            _PROGRAMS = {}  # jit-cache: demo


            def test_cached_program_compiles_once():
                for _ in range(3):
                    key = ("demo", 4)
                    prog = _PROGRAMS.get(key)
                    if prog is None:
                        prog = jax.jit(lambda v: v + 1)  # jit-cache: demo.cached
                        _PROGRAMS[key] = prog
                    out = prog(jnp.ones((4,), dtype=jnp.float32))
                    assert out.shape == (4,)
            """
        ),
    )
    assert rc == 0
    assert doc["clean"] is True
    assert doc["programs"]["demo.cached"]["creations"] == 1
    assert doc["programs"]["demo.cached"]["compiles"] == 1


# ------------------------------------------------------------ live-tree gate


def test_live_tree_clean_with_empty_baseline():
    """The acceptance gate: the real repository reports ZERO device-path
    findings (and zero advisories) against an EMPTY baseline — every true
    finding dlint surfaced was fixed in-tree, none parked."""
    baseline = REPO_ROOT / ".dlint-baseline.json"
    assert baseline.is_file(), "ship .dlint-baseline.json (empty) at the root"
    doc = json.loads(baseline.read_text())
    assert doc.get("findings") == [], "the dlint baseline must stay empty"

    report = run_device_analysis(REPO_ROOT, baseline_path=baseline)
    assert report.unbaselined == [], [
        f"{f.path}:{f.line}: {f.rule}: {f.message}" for f in report.unbaselined
    ]
    assert report.baselined == []
    assert report.advisories == [], [
        f"{f.path}:{f.line}: {f.rule}: {f.message}" for f in report.advisories
    ]
    assert report.parse_errors == []
    assert report.files_checked > 50
