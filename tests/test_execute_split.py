"""`scripts/execute_split.py`: the seven per-layer readings ISSUE 27 defines, computed from
the `stats.stages` a benchmark run keeps per request (`requests.jsonl`). PERF.md section 7
queues them as readers of `benchmark/metrics/` for a `benchmark` issue; until then this is
where their definitions are held to hand-made responses."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
spec = importlib.util.spec_from_file_location("execute_split", ROOT / "scripts" / "execute_split.py")
split = importlib.util.module_from_spec(spec)
spec.loader.exec_module(split)


def stages(execute_ms: float, **phases) -> dict:
    ex = {f"{p}_ms": float(phases.get(p, 0.0)) for p in split.PHASES}
    ex.update(head_ms=phases.get("head", 1.0), tail_ms=phases.get("tail", 1.0), blocks=2, readbacks=1)
    return {"parse_ms": 0.2, "plan_ms": 0.3, "scan_ms": 1.0, "execute_ms": execute_ms, "total_ms": execute_ms + 2.0, "execute": ex}


# nineteen short requests and one long one: the nearest-rank 95th percentile of twenty is the nineteenth
SHORT = stages(100.0, encode=1, prepare=9, dispatch=2, device_wait=80, readback=1, partial=2, merge=0, finalize=1)
LONG = stages(12_000.0, encode=10, prepare=1_500, dispatch=90, device_wait=700, readback=400, partial=1_000, merge=8_000, finalize=100)
TAIL = stages(900.0, encode=4, prepare=30, dispatch=6, device_wait=840, readback=2, partial=3, merge=5, finalize=1)
MERGE = ("merge_device", "merge_entries", "merge_survivors")


def test_the_tail_metrics_are_parts_of_the_one_request_execute_tail_ms_reports():
    window = [SHORT] * 18 + [TAIL, LONG]
    got = split.metrics(window)
    assert got["execute_tail_host_prepare_ms"] == 4 + 30 + 6
    assert got["execute_tail_device_wait_ms"] == 840
    assert got["execute_tail_readback_ms"] == 2
    assert got["execute_tail_host_merge_ms"] == 3 + 5 + 1
    # with what no phase covers they add up to that request's execute_ms
    left = 900.0 - sum(TAIL["execute"][f"{p}_ms"] for p in split.PHASES)
    assert sum(got[k] for k in got if k.startswith("execute_tail_")) + left == 900.0
    # the medians are the short class's; for one response wait and host add up to its execute_ms
    assert got["execute_device_wait_ms"] == 80 and got["execute_host_ms"] == 20
    want = 100.0 * (18 * 4.0 + left + (12_000.0 - 11_800.0)) / (18 * 100.0 + 900.0 + 12_000.0)
    assert got["execute_unaccounted_share"] == pytest.approx(want)


def test_responses_without_the_split_read_none():
    parent = {"parse_ms": 0.2, "plan_ms": 0.3, "scan_ms": 1.0, "execute_ms": 100.0, "total_ms": 102.0}
    cpu_engine = dict(parent, execute=None)
    got = split.metrics([parent, cpu_engine, None])
    assert len(got) == 7 and set(got.values()) == {None}
    # and those that have it are read past the ones that do not
    assert split.metrics([parent, SHORT])["execute_device_wait_ms"] == 80


def test_the_table_by_sql_text_and_the_gap_made_up_from_consecutive_requests(tmp_path, capsys):
    rows, t = [], 0.0
    for i in range(6):
        st = SHORT if i % 2 else TAIL
        latency = st["total_ms"] + 2.5  # the way in and the way out
        rows.append({"query": "a" if i % 2 else "b", "lookback": 32, "status": 200, "good": True, "sent_s": t,
                     "latency_ms": latency, "stages": st})
        t += latency / 1000.0 + (0.0004 if i != 2 else 3.0)  # the third turn-around is the harness's pause around a trace
    f = tmp_path / "requests.jsonl"
    f.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert split.main(["execute_split.py", str(f)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["by_query"]["a"]["requests"] == 3 and out["by_query"]["a"]["device_wait_ms"] == 80
    # a run of a program without the merge counters reads 0 for them
    assert [out["by_query"]["a"][k] for k in MERGE] == [0, 0, 0]
    assert out["by_query"]["b"]["unaccounted_ms"] == pytest.approx(9.0)
    gap = out["gap"]
    assert gap["pairs"] == 4  # five pairs less the pause
    # tail 1 + overhead 2.5 + turn-around 0.4 + parse and plan 0.5 + head 1
    assert gap["gap_ms"] == pytest.approx(5.4, abs=1e-6)


@pytest.mark.parametrize("merged", [0, 2, 3])
def test_the_merge_counters_by_sql_text(merged):
    """`merge_device` counts the requests whose merge ran on the device; entries and survivors are medians."""
    rows = []
    for i in range(3):
        st = json.loads(json.dumps(TAIL))
        on_device = i < merged
        st["execute"].update(merge_device=int(on_device), merge_entries=16_777_216 if on_device else 0,
                             merge_survivors=10 + i if on_device else 0)
        rows.append({"query": "topk", "lookback": 16, "status": 200, "good": True, "sent_s": float(i), "latency_ms": 905.0, "stages": st})
    got = split.by_query(rows)["topk"]
    assert got["merge_device"] == merged
    assert got["merge_entries"] == (16_777_216 if merged >= 2 else 0)
    assert got["merge_survivors"] == {0: 0, 2: 10, 3: 11}[merged]
