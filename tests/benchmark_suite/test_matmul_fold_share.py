"""`matmul_fold_share` (ISSUE 33): of the blocks the window's device programs folded, the share whose additive reduction ran
as one-hot products (`device_routes.fold_onehot_blocks` + `fold_factored_blocks`) and not as a scatter-add
(`fold_scatter_blocks`), in every cell that reports the tail it moves, and nothing against a program without the counters."""

import json
from pathlib import Path

import pytest

from benchmark import run as harness

ROOT = Path(__file__).resolve().parents[2]


def response(onehot: int, factored: int, scatter: int, **more) -> dict:
    return {"stats": {"device_routes": {"fold_onehot_blocks": onehot, "fold_factored_blocks": factored, "fold_scatter_blocks": scatter, **more}}}


CASES = {
    # a deck of flog_lowcard.dash before the factored route: two texts on the plain one-hot, the top-K on the scatter
    "a_third_on_the_scatter": ([response(32, 0, 0), response(32, 0, 0), response(0, 0, 32)], pytest.approx(200 / 3)),
    "the_topk_on_the_factored_product": ([response(32, 0, 0), response(32, 0, 0), response(0, 32, 0)], 100.0),
    "nothing_on_the_mxu": ([response(0, 0, 16)], 0.0),
    "a_response_without_the_counters_is_passed_over": ([response(8, 0, 8), {"stats": {"device_routes": {"cpu_fallback": 0}}}, {"stats": {}}], 50.0),
    "a_window_that_folded_no_block": ([response(0, 0, 0)], None),
    "a_program_without_the_counters": ([{"stats": {"device_routes": {"cpu_fallback": 0, "operand_puts": 16}}}], None),
    "no_response": ([], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_reader_takes_the_share_of_the_windows_folded_blocks(case):
    responses, want = CASES[case]
    got = harness.read_metric("matmul_fold_share", {"responses": responses, "after": {}})
    assert got == want and (want is None or isinstance(got, float))


def test_the_entry_lists_no_cells_so_every_cell_that_reports_the_tail_reads_it():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in manifest["per_layer"] if m["name"] == "matmul_fold_share")
    assert entry == {"name": "matmul_fold_share", "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "ops/kernels programs", "moves": "query_p95_ms"}
    for cell in manifest["workloads"]:
        assert "matmul_fold_share" in {m["name"] for m in harness.load_cell(cell["name"])["per_layer"]}
