"""`operand_puts_per_query` (ISSUE 31): the mean of the program's `device_routes.operand_puts` over the window's responses, in
the cells its manifest entry lists, and nothing against a program that has no such counter (the parent's)."""

import json
from pathlib import Path

from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]


def response(**routes) -> dict:
    return {"stats": {"device_routes": routes}}


def test_the_reader_takes_the_mean_over_the_windows_responses():
    run = {"responses": [response(operand_puts=32), response(operand_puts=16), response(operand_puts=16), response(operand_puts=0)], "after": {}}
    assert harness.read_metric("operand_puts_per_query", run) == 16.0


def test_a_program_without_the_counter_reads_nothing_and_not_zero():
    bare = {"responses": [response(cpu_fallback=0, h2d_bytes=79_000), {"stats": {}}], "after": {}}
    assert harness.read_metric("operand_puts_per_query", bare) is None
    assert harness.read_metric("operand_puts_per_query", {"responses": [], "after": {}}) is None


def test_the_entry_is_the_counters_and_its_cells_are_the_manifests_own():
    """The fixed fields of the entry; which cells it lists is the manifest's to say (`test_manifest` holds every listed
    cell to the rate it moves), so a later PR's cell is an entry appended there and no edit here."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in manifest["per_layer"] if m["name"] == "operand_puts_per_query")
    fixed = {k: v for k, v in entry.items() if k != "workloads"}
    assert fixed == {"name": "operand_puts_per_query", "unit": "transfers", "better": "lower", "source": "program_counter",
                     "layer": "query/executor_tpu", "moves": "scan_rows_per_s"}
    assert entry["workloads"]
    for cell in entry["workloads"]:
        assert "operand_puts_per_query" in {m["name"] for m in harness.load_cell(cell)["per_layer"]}
