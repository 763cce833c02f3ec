"""dlint CLI: `python -m parseable_tpu.analysis.device [paths...]`.

Exit codes: 0 = no unbaselined findings, 1 = findings, 2 = usage/parse
error — plint/wlint's contract exactly, so check_green.sh treats the
gates identically. `--json` emits a machine-diffable report (stable
ordering, content fingerprints); `--json-out FILE` writes the same report
as a gate artifact while keeping human-readable output on stdout.
Advisories (missed-donation) print as notes and never affect the exit
code.

No --changed / result cache here: host-sync is a whole-graph reachability
rule (the sync and the hot loop that reaches it are rarely in the same
file), so a changed-files scope would be exactly the blind spot the gate
exists to close, and a full run is already sub-second.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from parseable_tpu.analysis.device import (
    DEFAULT_PATHS,
    DEVICE_RULES,
    run_device_analysis,
    write_baseline,
)

DEFAULT_BASELINE = ".dlint-baseline.json"


def explain(rule_name: str) -> int:
    for cls in DEVICE_RULES:
        if cls.name == rule_name:
            print(f"{cls.name}: {cls.description}")
            print(f"why: {cls.rationale}")
            doc = (cls.__doc__ or "").strip()
            if doc:
                print()
                print(doc)
            print()
            print(f"suppress one line with:  # dlint: disable={cls.name}")
            return 0
    known = ", ".join(cls.name for cls in DEVICE_RULES)
    print(f"unknown rule {rule_name!r}; known rules: {known}", file=sys.stderr)
    return 2


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m parseable_tpu.analysis.device",
        description="dlint: device-path discipline checks (jit caching, "
        "host syncs, traced control flow, transfer pricing, dtype, donation)",
    )
    p.add_argument(
        "paths",
        nargs="*",
        default=None,
        help=f"files/dirs relative to --root (default: {' '.join(DEFAULT_PATHS)})",
    )
    p.add_argument("--root", default=".", help="repository root (default: cwd)")
    p.add_argument("--json", action="store_true", help="machine-readable output")
    p.add_argument(
        "--json-out",
        metavar="FILE",
        default=None,
        help="also write the JSON report to FILE (gate artifact)",
    )
    p.add_argument(
        "--baseline",
        default=DEFAULT_BASELINE,
        help=f"baseline file relative to --root (default: {DEFAULT_BASELINE})",
    )
    p.add_argument(
        "--write-baseline",
        action="store_true",
        help="acknowledge every current finding into the baseline file",
    )
    p.add_argument(
        "--rule",
        action="append",
        default=None,
        metavar="NAME",
        help="run only these rules (repeatable)",
    )
    p.add_argument("--list-rules", action="store_true", help="print the rule catalog")
    p.add_argument(
        "--explain",
        metavar="RULE",
        default=None,
        help="print one rule's rationale, discipline, and suppression syntax",
    )
    args = p.parse_args(argv)

    if args.list_rules:
        for cls in DEVICE_RULES:
            print(f"{cls.name:30s} {cls.description}")
            print(f"{'':30s}   why: {cls.rationale}")
        return 0

    if args.explain:
        return explain(args.explain)

    rules = [cls() for cls in DEVICE_RULES]
    if args.rule:
        known = {r.name for r in rules}
        unknown = set(args.rule) - known
        if unknown:
            print(f"unknown rule(s): {', '.join(sorted(unknown))}", file=sys.stderr)
            return 2
        rules = [r for r in rules if r.name in set(args.rule)]

    root = Path(args.root).resolve()
    baseline_path = root / args.baseline

    started = time.monotonic()
    report = run_device_analysis(
        root,
        paths=args.paths or None,
        rules=rules,
        baseline_path=baseline_path,
    )

    if args.write_baseline:
        write_baseline(baseline_path, report.findings)
        print(
            f"baseline written: {len(report.findings)} finding(s) -> {baseline_path}"
        )
        return 0

    if report.parse_errors:
        for e in report.parse_errors:
            print(f"parse error: {e}", file=sys.stderr)
        return 2

    doc = report.to_json()
    doc["elapsed_seconds"] = round(time.monotonic() - started, 3)

    if args.json_out:
        Path(args.json_out).write_text(json.dumps(doc, indent=2) + "\n", encoding="utf-8")

    if args.json:
        print(json.dumps(doc, indent=2))
    else:
        for f in doc["findings"]:
            ctx = f" [{f['context']}]" if f.get("context") else ""
            print(f"{f['path']}:{f['line']}: {f['rule']}{ctx}: {f['message']}")
        for f in doc["advisories"]:
            print(
                f"note: {f['path']}:{f['line']}: {f['rule']}: {f['message']}"
            )
        n_base = len(doc.get("baselined", []))
        base_note = f" ({n_base} baselined)" if n_base else ""
        adv_note = (
            f", {len(doc['advisories'])} advisory(ies)" if doc["advisories"] else ""
        )
        print(
            f"dlint: {len(doc['findings'])} finding(s){base_note}{adv_note} "
            f"across {doc['files_checked']} files"
        )
    return 0 if doc["clean"] else 1


if __name__ == "__main__":
    sys.exit(main())
