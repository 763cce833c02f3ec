"""Where `execute_ms` goes, from the requests a benchmark run keeps.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds 51 --trace 1 --out-dir <dir>
    python3 scripts/execute_split.py <dir>/<cell>.<n>.requests.jsonl

Reads `stats.stages.execute` (the TPU executor's phase clock, query/session.py
`_execute_stage`) of every request of the window and prints one JSON object:

  metrics    the seven per-layer readings ISSUE 27 defines and PERF.md section 7
             queues for a `benchmark` issue (a reader of `benchmark/metrics/` would
             return the same number from `run["responses"]`); None where no
             response has the split
  by_query   by SQL text: requests, the median of every stage and phase, and of the
             block-local merge (ISSUE 28): `merge_device` the requests whose merge ran
             on the device, `merge_entries` and `merge_survivors` their medians (0
             from a run of a program that has no such counters)
  gap        the host time between two requests' device work, made up from the
             clocks of consecutive requests: `tail_ms` of one, its
             `http_overhead_ms` (latency less `total_ms`: the way in and the way
             out, `response.encode` among it), the client's turn-around, and
             `parse_ms + plan_ms + head_ms` of the next (`head_ms` is on the wall
             clock, so the scan's waits before the first dispatch lie inside it)
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from benchmark.readers import quantile  # noqa: E402  (nearest rank, as execute_tail_ms takes it)

# executor_tpu.PHASES, written out: reading a JSON file imports no JAX
PHASES = ("encode", "prepare", "dispatch", "device_wait", "readback", "partial", "merge", "finalize")


def split_of(stages: dict | None) -> dict | None:
    """A response's `stages.execute` beside its `execute_ms`, or None where it has no split."""
    ex = (stages or {}).get("execute")
    if not isinstance(ex, dict) or not isinstance((stages or {}).get("execute_ms"), (int, float)):
        return None
    return {"execute_ms": stages["execute_ms"], **{p: ex[f"{p}_ms"] for p in PHASES}}


def metrics(all_stages: list) -> dict:
    """`all_stages`: one `stats.stages` per response of the window."""
    names = ("execute_tail_host_prepare_ms", "execute_tail_device_wait_ms", "execute_tail_readback_ms",
             "execute_tail_host_merge_ms", "execute_device_wait_ms", "execute_host_ms", "execute_unaccounted_share")
    splits = [s for s in map(split_of, all_stages) if s is not None]
    if not splits:
        return dict.fromkeys(names)
    # the response whose execute_ms is the nearest-rank 95th percentile: the one execute_tail_ms reports
    tail_ms = quantile([s["execute_ms"] for s in splits], 0.95)
    tail = next(s for s in splits if s["execute_ms"] == tail_ms)
    left = sum(s["execute_ms"] - sum(s[p] for p in PHASES) for s in splits)
    return {
        "execute_tail_host_prepare_ms": tail["encode"] + tail["prepare"] + tail["dispatch"],
        "execute_tail_device_wait_ms": tail["device_wait"],
        "execute_tail_readback_ms": tail["readback"],
        "execute_tail_host_merge_ms": tail["partial"] + tail["merge"] + tail["finalize"],
        "execute_device_wait_ms": statistics.median(s["device_wait"] for s in splits),
        "execute_host_ms": statistics.median(s["execute_ms"] - s["device_wait"] for s in splits),
        "execute_unaccounted_share": 100.0 * left / sum(s["execute_ms"] for s in splits),
    }


def by_query(rows: list) -> dict:
    out = {}
    for name in sorted({r["query"] for r in rows}):
        mine = [r for r in rows if r["query"] == name and split_of(r.get("stages"))]
        if not mine:
            continue

        def med(f, mine=mine):
            return round(statistics.median(f(r) for r in mine), 3)

        out[name] = {
            "requests": len(mine),
            "latency_ms": med(lambda r: r["latency_ms"]),
            **{k: med(lambda r, k=k: r["stages"][k]) for k in ("parse_ms", "plan_ms", "scan_ms", "execute_ms", "total_ms")},
            **{k: med(lambda r, k=k: r["stages"]["execute"][k] or 0.0) for k in
               (*(f"{p}_ms" for p in PHASES), "head_ms", "tail_ms", "blocks", "readbacks")},
            "unaccounted_ms": med(lambda r: r["stages"]["execute_ms"] - sum(r["stages"]["execute"][f"{p}_ms"] for p in PHASES)),
            "merge_device": sum(r["stages"]["execute"].get("merge_device", 0) for r in mine),
            **{k: med(lambda r, k=k: r["stages"]["execute"].get(k, 0)) for k in ("merge_entries", "merge_survivors")},
        }
    return out


def gap(rows: list, pause_ms: float = 100.0) -> dict | None:
    """Consecutive requests of one client; a turn-around over `pause_ms` is the harness's pause around a trace."""
    rows = sorted((r for r in rows if split_of(r.get("stages"))), key=lambda r: r["sent_s"])
    parts = []
    for a, b in zip(rows, rows[1:]):
        turn = (b["sent_s"] - a["sent_s"]) * 1000.0 - a["latency_ms"]
        ea, sb = a["stages"]["execute"], b["stages"]
        if not 0 <= turn < pause_ms or ea["tail_ms"] is None or sb["execute"]["head_ms"] is None:
            continue
        parts.append({"tail_ms": ea["tail_ms"], "http_overhead_ms": a["latency_ms"] - a["stages"]["total_ms"], "turn_around_ms": turn,
                      "parse_plan_ms": sb["parse_ms"] + sb["plan_ms"], "head_ms": sb["execute"]["head_ms"]})
    if not parts:
        return None
    out = {k: round(statistics.median(p[k] for p in parts), 3) for k in parts[0]}
    out["pairs"] = len(parts)
    out["gap_ms"] = round(statistics.median(sum(p.values()) for p in parts), 3)
    return out


def main(argv: list) -> int:
    rows = [json.loads(ln) for ln in open(argv[1]) if ln.strip()]
    rows = [r for r in rows if r.get("status") == 200]
    print(json.dumps({"metrics": metrics([r.get("stages") for r in rows]), "by_query": by_query(rows), "gap": gap(rows)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
