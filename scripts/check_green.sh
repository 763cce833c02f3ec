#!/usr/bin/env bash
# Pre-snapshot gate: run the tier-1 verify command from ROADMAP.md and exit
# nonzero on ANY failure ("go green and stay green"). Run this before every
# snapshot/PR; a red tier-1 must block the commit, not ride along.
#
# Usage: scripts/check_green.sh
set -o pipefail
cd "$(dirname "$0")/.."

# With a toolchain present, a native fastpath that fails to compile must be
# a test failure, not a silent pure-Python-fallback green (the columnar
# ingest tier, xxh64, and HLL would all quietly degrade). Tests read this
# in conftest pytest_sessionstart; native/__init__.py also hard-raises.
if command -v g++ >/dev/null 2>&1; then
  export P_NATIVE_REQUIRED=1
fi

# P_DLINT=1 arms the device-path recompilation tripwire for the tier-1 run
# itself: jax.jit is wrapped session-wide and any cached program compiling
# past its per-shape-class budget turns the run red (report:
# /tmp/dlint_tripwire.json). DLINT=0 disarms it along with the static gate.
t1_dlint="${DLINT:-1}"
if [ "$t1_dlint" != "0" ]; then t1_dlint=1; fi
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu P_DLINT="$t1_dlint" python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
if [ "$rc" -ne 0 ]; then
  echo "check_green: TIER-1 RED (pytest rc=$rc)" >&2
  exit "$rc"
fi
if grep -aqE '^[0-9]+ (failed|error)|, [0-9]+ (failed|error)' /tmp/_t1.log; then
  echo "check_green: TIER-1 RED (failures in log despite rc=0)" >&2
  exit 1
fi
echo "check_green: tier-1 GREEN"

# static-analysis gate: the tree must lint clean (zero unbaselined plint
# findings) before snapshot — concurrency/invariant bugs are cheapest here.
# Default: --changed (findings reported only for files differing from
# `git merge-base HEAD main`, whole tree still analyzed) + the mtime result
# cache, so the gate stays fast as the rule count grows. PLINT_FULL=1 runs
# the authoritative full-tree report. The JSON report lands at
# /tmp/plint.json either way (gate artifact).
plint_args=(--json-out /tmp/plint.json)
if [ "${PLINT_FULL:-0}" != "1" ]; then
  plint_args+=(--changed)
fi
if ! python -m parseable_tpu.analysis "${plint_args[@]}"; then
  echo "check_green: PLINT RED (unbaselined findings; see above and /tmp/plint.json)" >&2
  exit 1
fi
echo "check_green: plint GREEN (report: /tmp/plint.json)"

# wire-contract gate: wlint (parseable_tpu/analysis/wire/) diffs both sides
# of every wire contract — client path literals vs the aiohttp route table
# (and the C++ edge classifier's route strings), X-P-* header produce/consume
# across Python AND fastpath.cpp, Flight ticket kinds and ptpu.* schema
# metadata, metric families vs ticks vs README, stats.stages.* produce/
# consume, and FFI pointer custody against the nsan ownership tables.
# Always a full-tree run (every rule is cross-file; sub-second). Opt out
# with WLINT=0; the JSON report lands at /tmp/wlint.json either way it runs.
if [ "${WLINT:-1}" != "0" ]; then
  if ! python -m parseable_tpu.analysis.wire --json-out /tmp/wlint.json; then
    echo "check_green: WLINT RED (unbaselined findings; see above and /tmp/wlint.json)" >&2
    exit 1
  fi
  echo "check_green: wlint GREEN (report: /tmp/wlint.json)"
else
  echo "check_green: wlint SKIPPED (WLINT=0)"
fi

# device-path gate: dlint (parseable_tpu/analysis/device/) — jit sites on
# query paths must ride a declared program cache, host syncs reachable from
# `# device-hot` loops must be `# sync-boundary` annotated, device_put/get
# must be priced into byte accounting, plus traced-control-flow, dtype
# promotion and donation hazards. Full-tree run
# (the host-sync rule walks the cross-file call graph; sub-second). Opt out
# with DLINT=0 — which also disarms the P_DLINT tripwire on the tier-1 run
# above; the JSON report lands at /tmp/dlint.json either way it runs.
if [ "${DLINT:-1}" != "0" ]; then
  if ! python -m parseable_tpu.analysis.device --json-out /tmp/dlint.json; then
    echo "check_green: DLINT RED (unbaselined findings; see above and /tmp/dlint.json)" >&2
    exit 1
  fi
  echo "check_green: dlint GREEN (report: /tmp/dlint.json)"
else
  echo "check_green: dlint SKIPPED (DLINT=0)"
fi

# dynamic-analysis gate: the same tier-1 suite again under the psan runtime
# concurrency sanitizer (P_PSAN=1) — Eraser lockset races on guarded-by
# attrs, runtime lock-order vs the declared hierarchy, event-loop blocking,
# per-test thread/executor leaks. Opt out with PSAN=0 (e.g. on a machine
# where the double run is too slow); the JSON report lands at /tmp/psan.json
# alongside /tmp/plint.json either way the pass runs. Like PLINT_FULL=1
# above, running both full gates is the authoritative pre-snapshot check.
if [ "${PSAN:-1}" != "0" ]; then
  rm -f /tmp/_t1_psan.log
  timeout -k 10 870 env JAX_PLATFORMS=cpu P_PSAN=1 python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1_psan.log
  rc=${PIPESTATUS[0]}
  if [ "$rc" -ne 0 ]; then
    echo "check_green: PSAN RED (rc=$rc; findings above and in /tmp/psan.json)" >&2
    exit "$rc"
  fi
  echo "check_green: psan GREEN (report: /tmp/psan.json)"
else
  echo "check_green: psan SKIPPED (PSAN=0)"
fi

# native gate: nsan (parseable_tpu/analysis/nsan/) — ABI drift between
# fastpath.cpp's extern "C" surface and the ctypes bindings, clang-tidy
# when installed, and the fuzz-corpus replay under the ASan/UBSan
# instrumented build; then the native-touching test files again with
# P_NSAN=1 (the same tests, loaded against the sanitized library, with a
# ptpu_cols_live==0 session gate). Opt out with NSAN=0. The CLI writes
# /tmp/nsan.json first; the pytest pass merges its section into it.
if [ "${NSAN:-1}" != "0" ]; then
  if ! python -m parseable_tpu.analysis.nsan --json-out /tmp/nsan.json; then
    echo "check_green: NSAN RED (unbaselined findings; see above and /tmp/nsan.json)" >&2
    exit 1
  fi
  # the sanitized pytest pass runs UBSan-instrumented (the only mode sound
  # under late dlopen; see analysis/nsan/__init__.py) — probe that the
  # toolchain's UBSan actually links instead of guessing from `command -v`
  if echo 'int main(){return 0;}' | g++ -fsanitize=undefined -x c++ - -o /tmp/_nsan_probe 2>/dev/null; then
    rm -f /tmp/_nsan_probe /tmp/_t1_nsan.log
    timeout -k 10 600 env JAX_PLATFORMS=cpu P_NSAN=1 python -m pytest -q -m 'not slow' \
      tests/test_native_ingest.py tests/test_native_otel.py \
      tests/test_native_parity_fuzz.py tests/test_native_and_formats.py \
      tests/test_native_telem.py \
      tests/test_hll_distinct.py tests/test_nsan_fuzz.py \
      --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
      2>&1 | tee /tmp/_t1_nsan.log
    rc=${PIPESTATUS[0]}
    if [ "$rc" -ne 0 ]; then
      echo "check_green: NSAN RED (sanitized test run rc=$rc; see /tmp/nsan.json)" >&2
      exit "$rc"
    fi
    echo "check_green: nsan GREEN (report: /tmp/nsan.json)"
    # edge smoke, same UBSan leg: one real server process booted with
    # P_EDGE_PORT against the sanitized library (P_NSAN_LIB), a keep-alive
    # happy-path ack pair, a forced decline relayed byte-identical to the
    # aiohttp tier, and the conservation audit's edge section drained at
    # quiesce. Opt out with EDGE=0 (boots 1 process; ~half a minute). Only
    # meaningful when the library exports the edge ABI — skipped otherwise.
    if [ "${EDGE:-1}" != "0" ]; then
      if python -c 'from parseable_tpu import native; import sys; sys.exit(0 if native.edge_available() else 1)' 2>/dev/null; then
        san_lib=$(python -c 'import parseable_tpu, pathlib; from parseable_tpu.analysis.nsan import build_san_lib; from parseable_tpu.config import nsan_options; p = build_san_lib(pathlib.Path(parseable_tpu.__file__).resolve().parent.parent, nsan_options()["san_mode"]); print(p or "")' 2>/dev/null)
        if ! timeout -k 10 300 env JAX_PLATFORMS=cpu P_NSAN_LIB="$san_lib" python scripts/edge_smoke.py; then
          echo "check_green: EDGE RED (native ingest edge smoke failed under UBSan)" >&2
          exit 1
        fi
        echo "check_green: edge GREEN (sanitized lib: ${san_lib:-none})"
      else
        echo "check_green: edge SKIPPED (native edge ABI unavailable)"
      fi
    else
      echo "check_green: edge SKIPPED (EDGE=0)"
    fi
  else
    echo "check_green: nsan GREEN — ABI+corpus only (no UBSan-capable toolchain for the sanitized test pass)"
  fi
else
  echo "check_green: nsan SKIPPED (NSAN=0)"
fi

# observability gate: the multi-process cluster smoke — distributed trace
# stitching (one cross-node span tree per query) and the conservation-law
# audit (zero violations at quiesce) over REAL server processes, with the
# ingestors serving the Arrow Flight data plane (the smoke asserts the
# scatter rode it). FLIGHT=0 pins the smoke to the HTTP tier — the escape
# hatch if gRPC misbehaves on a box. Opt out entirely with OBS_CLUSTER=0
# (boots 3 processes; ~half a minute on a warm cache).
if [ "${OBS_CLUSTER:-1}" != "0" ]; then
  if ! timeout -k 10 420 env JAX_PLATFORMS=cpu FLIGHT="${FLIGHT:-1}" python scripts/obs_smoke.py --cluster; then
    echo "check_green: OBS CLUSTER RED (trace stitching / audit smoke failed)" >&2
    exit 1
  fi
  echo "check_green: obs cluster GREEN"
else
  echo "check_green: obs cluster SKIPPED (OBS_CLUSTER=0)"
fi

# merged artifact: one /tmp/analysis_summary.json rolling up the five
# static/dynamic analysis reports (plint, psan, nsan, wlint, dlint) so a snapshot
# reviewer reads one file. Skipped gates simply have no section; the merge
# itself never turns the gate red.
python - <<'PY' || echo "check_green: analysis summary merge failed (non-fatal)" >&2
import json, pathlib
out = {}
for name in ("plint", "psan", "nsan", "wlint", "dlint"):
    p = pathlib.Path(f"/tmp/{name}.json")
    if not p.exists():
        continue
    try:
        doc = json.loads(p.read_text())
    except (OSError, ValueError):
        continue
    findings = doc.get("findings", [])
    baselined = doc.get("baselined", [])
    out[name] = {
        "artifact": str(p),
        "files_checked": doc.get("files_checked"),
        "findings": len(findings),
        "baselined": len(baselined),
        "unbaselined": max(0, len(findings) - len(baselined)),
        "clean": bool(doc.get("clean", not findings)),
    }
pathlib.Path("/tmp/analysis_summary.json").write_text(
    json.dumps({"version": 1, "gates": out}, indent=2) + "\n"
)
print(f"check_green: analysis summary -> /tmp/analysis_summary.json ({', '.join(out) or 'no artifacts'})")
PY
