"""dlint — device-path discipline static analysis for the TPU layer.

plint watches Python concurrency, psan runtime behavior, nsan native
memory, wlint cross-boundary wire contracts.  None of them sees the layer
the paper's TPU-native thesis actually rests on: the hand-rolled JAX
device mapping in ``query/executor_tpu.py`` and ``ops/``, where a
recompile-per-query closure, an implicit device->host sync, or one f64
leak silently eats the MFU the hardware roadmap item needs to prove.  The
reference architecture gets this discipline for free from static plans;
we enforce it with a linter.

Rules (each is one discipline):

- jit-cache-discipline  call-time jax.jit must ride a declared, keyed
                        program cache (``# jit-cache: <family>.<program>``)
- host-sync             undeclared device->host syncs reachable from
                        ``# device-hot`` roots via the call graph
                        (``# sync-boundary: <why>`` declares one)
- traced-control-flow   Python if/while/assert on traced values in jit'd
                        bodies, resolved from jit sites through local defs
- transfer-discipline   device_put/device_get must be priced into
                        route_stats byte accounting
                        (``# link-priced: <where>`` points elsewhere)
- dtype-promotion       float64 inside traced bodies; jax_enable_x64 flips
- donation-hazard       use-after-donate errors; undocumented missed
                        donation as advisory

The dynamic companion is the ``P_DLINT=1`` pytest tripwire
(``parseable_tpu.analysis.device.tripwire``): it hooks ``jax.jit``,
attributes every real XLA compile to its declared program-cache name, and
enforces a compiles-per-shape-class budget over the tier-1 session,
exporting ``tpu_recompiles_total{program}``.

Reuses plint's Finding/fingerprint/baseline machinery verbatim; the
suppression marker is ``# dlint: disable[=rule,...]`` so a plint/wlint
suppression never silences a device finding or vice versa.  Run as
``python -m parseable_tpu.analysis.device``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field, replace
from pathlib import Path

from parseable_tpu.analysis.framework import (
    AnalysisReport,
    Finding,
    Project,
    Rule,
    SourceFile,
    iter_python_files,
    load_baseline,
    write_baseline,
)
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

DLINT_VERSION = "1"

DEVICE_RULES: list[type[Rule]] = [
    JitCacheDisciplineRule,
    HostSyncRule,
    TracedControlFlowRule,
    TransferDisciplineRule,
    DtypePromotionRule,
    DonationHazardRule,
]

# tests/ deliberately touch device arrays (that is what device tests do);
# the discipline applies to shipped code.
DEFAULT_PATHS = ["parseable_tpu", "scripts"]

_SUPPRESS_RE = re.compile(r"dlint:\s*disable(?:=([A-Za-z0-9_,-]+))?")


@dataclass
class DeviceReport(AnalysisReport):
    """plint's report shape plus non-gating advisories (missed-donation
    notes): printed as notes, serialized under their own key, never part
    of the exit code."""

    advisories: list[Finding] = field(default_factory=list)

    def to_json(self) -> dict:
        doc = super().to_json()
        doc["advisories"] = [f.to_json() for f in self.advisories]
        return doc


def _dlint_suppressions(sf: SourceFile) -> dict[int, set[str] | None]:
    """SourceFile's own suppression table answers to `plint:` markers;
    device findings answer only to `dlint:` ones, scanned from the same
    comments."""
    out: dict[int, set[str] | None] = {}
    for line, comment in sf.comments.items():
        m = _SUPPRESS_RE.search(comment)
        if m:
            names = m.group(1)
            out[line] = (
                {s.strip() for s in names.split(",") if s.strip()} if names else None
            )
    return out


def run_device_analysis(
    root: Path,
    paths: list[str] | None = None,
    rules: list[Rule] | None = None,
    baseline_path: Path | None = None,
    report_only: set[str] | None = None,
) -> DeviceReport:
    """Analyze `paths` under `root` with the device rules. Same contract as
    framework.run_analysis; differences: analyzer sources are excluded from
    the project outright (the host-sync reachability pass never sees them),
    and suppression/baseline use dlint's own marker and file."""
    root = Path(root)
    rules = rules if rules is not None else [cls() for cls in DEVICE_RULES]
    paths = paths or DEFAULT_PATHS
    project = Project(root=root)
    parse_errors: list[str] = []
    for p in iter_python_files(root, paths):
        rel = p.relative_to(root).as_posix()
        if rel.startswith("parseable_tpu/analysis/"):
            continue  # the analyzer does not lint itself
        try:
            project.files.append(SourceFile.from_path(root, p))
        except (SyntaxError, UnicodeDecodeError) as e:
            parse_errors.append(f"{p}: {e}")

    by_rel = {sf.rel: sf for sf in project.files}
    suppress = {sf.rel: _dlint_suppressions(sf) for sf in project.files}

    def suppressed(f: Finding) -> bool:
        table = suppress.get(f.path)
        if table is None or f.line not in table:
            return False
        names = table[f.line]
        return names is None or f.rule in names

    def finish(f: Finding) -> Finding:
        if f.snippet:
            return f
        src = by_rel.get(f.path)
        return replace(f, snippet=src.snippet(f.line)) if src is not None else f

    findings: list[Finding] = []
    advisories: list[Finding] = []
    for sf in project.files:
        for rule in rules:
            if not rule.applies(sf.rel):
                continue
            for f in rule.check(sf):
                if not suppressed(f):
                    findings.append(finish(f))
    for rule in rules:
        for f in rule.finalize(project):
            if not suppressed(f):
                findings.append(finish(f))
        advise = getattr(rule, "advisories", None)
        if advise is not None:
            for f in advise(project):
                if not suppressed(f):
                    advisories.append(finish(f))

    if report_only is not None:
        findings = [f for f in findings if f.path in report_only]
        advisories = [f for f in advisories if f.path in report_only]
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    advisories.sort(key=lambda f: (f.path, f.line, f.rule))
    baseline = load_baseline(baseline_path)
    baselined = [
        f
        for f in findings
        if f.fingerprint in baseline or f.legacy_fingerprint in baseline
    ]
    unbaselined = [
        f
        for f in findings
        if f.fingerprint not in baseline and f.legacy_fingerprint not in baseline
    ]
    return DeviceReport(
        findings=findings,
        baselined=baselined,
        unbaselined=unbaselined,
        files_checked=len(project.files),
        parse_errors=parse_errors,
        advisories=advisories,
    )


__all__ = [
    "DLINT_VERSION",
    "DEVICE_RULES",
    "DEFAULT_PATHS",
    "DeviceReport",
    "run_device_analysis",
    "write_baseline",
]
