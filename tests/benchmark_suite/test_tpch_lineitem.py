"""The `tpch_lineitem` configuration and its two SQL texts (ISSUE 30): the generator makes LINEITEM's columns with the
distributions the specification gives them and draws a row's dependent columns jointly; the two references' partials add up
to a row-by-row answer; and the product's CPU engine, over the same rows through its own ingest path, agrees with them.
(`test_reference.py` runs these checks for the configurations that share the access log's columns; a text that names
LINEITEM's columns has them here.)"""

import json
from datetime import UTC, datetime, timedelta
from pathlib import Path

import numpy as np
import pytest

from benchmark import gen, refcore, roofline, traffic
from benchmark.gens import tpch_lineitem as kind

ROOT = Path(__file__).resolve().parents[2]
SEED = 3_000_000_019
DAY = 86_400_000
TEXTS = ["tpch_q1", "tpch_q6"]


def small(minutes: int = 4, rows: int = 20_000) -> dict:
    cfg = gen.load_config("tpch_lineitem")
    cfg["minutes"], cfg["rows_per_minute"] = minutes, rows
    return cfg


def day(y: int, m: int, d: int) -> int:
    return int(datetime(y, m, d, tzinfo=UTC).timestamp() * 1000)


def test_the_file_states_the_source_every_column_the_cut_and_the_guarantees():
    cfg = json.loads((ROOT / "benchmark" / "configs" / "tpch_lineitem.json").read_text())
    assert cfg["source"].startswith("TPC-H spec rev 3.0.1: LINEITEM") and len(cfg["source"]) <= 200
    assert cfg["reduced"] == ["tables"] and cfg["tables"] == ["lineitem"] and cfg["fallback_taken"] is False
    assert cfg["rows"] == cfg["minutes"] * cfg["rows_per_minute"] == 60_000_000 and cfg["scale_factor"] == 10
    names = [c["name"] for c in cfg["columns"]]
    assert names == ["p_timestamp", "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity", "l_extendedprice", "l_discount",
                     "l_tax", "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct", "l_shipmode",
                     "l_comment"]
    assert all(c["distribution"] for c in cfg["columns"][1:])
    widths = {c["name"]: c["width_bytes"] for c in cfg["columns"]}
    assert {widths[n] for n in ("l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode")} == {1} and widths["l_comment"] == 4
    assert all(widths[n] == 4 for n in names if n.startswith(("p_", "l_")) and widths[n] != 1)
    flog = json.loads((ROOT / "benchmark" / "configs" / "flog_lowcard.json").read_text())
    assert all(cfg["guarantees"][k] == v for k, v in flog["guarantees"].items())  # those of the other configurations word for word
    assert "millisecond" in cfg["guarantees"]["dates"] and set(cfg["assumed"]) >= {"rows", "load_order", "l_comment", "answers", "from_memory"}
    assert cfg["env"] == {} and cfg["deployment"] == flog["deployment"] and cfg["precision"] == "HIGHEST"


def test_a_minute_is_a_function_of_seed_and_minute_whichever_column_is_asked_first():
    cfg = small()
    a, b = gen.gen_minute(cfg, SEED, 2), gen.gen_minute(cfg, SEED, 2)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["l_partkey"], gen.gen_minute(cfg, SEED + 1, 2)["l_partkey"])
    assert not np.array_equal(a["l_partkey"], gen.gen_minute(cfg, SEED, 3)["l_partkey"])
    # one column alone, from a generator of its own: the same joint draw, so the same rows once the timestamp has drawn
    cols = gen.columns(cfg)
    rng = np.random.default_rng([SEED, 2])
    gen.kind(cols["p_timestamp"]).draw(cols["p_timestamp"], cfg, rng, 2, cfg["rows_per_minute"])
    assert np.array_equal(kind.draw(cols["l_tax"], cfg, rng, 2, cfg["rows_per_minute"]), a["l_tax"])


def test_columns_follow_the_specifications_distributions_and_depend_on_each_other_as_it_says():
    cfg = small(minutes=2, rows=200_000)
    b = gen.gen_minute(cfg, SEED, 1)
    n = cfg["rows_per_minute"]
    assert set(np.unique(b["l_quantity"])) == set(range(1, 51)) and set(np.round(np.unique(b["l_discount"]) * 100)) == set(range(11))
    assert set(np.round(np.unique(b["l_tax"]) * 100)) == set(range(9))
    part = b["l_partkey"].astype(np.int64)
    assert part.min() >= 1 and part.max() <= 2_000_000 and abs(part.mean() - 1_000_000) < 10_000
    retail = (90_000 + (part // 10) % 20_001 + 100 * (part % 1000)) / 100
    assert np.allclose(b["l_extendedprice"], b["l_quantity"] * retail, rtol=0, atol=1e-6)
    supp = b["l_suppkey"].astype(np.int64)
    assert supp.min() >= 1 and supp.max() <= 100_000
    i = np.arange(4)[None, :]
    assert ((part[:, None] + i * (25_000 + (part[:, None] - 1) // 100_000)) % 100_000 + 1 == supp[:, None]).any(axis=1).all()
    # orders: sparse keys (the first 8 of every 32), 1 to 7 lines, numbered from 1, one order date for all its lines
    key = b["l_orderkey"].astype(np.int64)
    assert ((key - 1) % 32 < 8).all() and (np.diff(key) >= 0).all()
    uniq, first, counts = np.unique(key, return_index=True, return_counts=True)
    assert counts.max() <= 7 and 3.8 < counts.mean() < 4.2
    assert (b["l_linenumber"][first] == 1).all() and (b["l_linenumber"] == np.arange(n) - np.repeat(first, counts) + 1).all()
    assert key.min() > gen.gen_minute(cfg, SEED, 0)["l_orderkey"].max()  # unique and rising through the stream
    ship, commit, receipt = b["l_shipdate"], b["l_commitdate"], b["l_receiptdate"]
    assert all((d % DAY == 0).all() for d in (ship, commit, receipt))
    assert (1 <= (receipt - ship) // DAY).all() and ((receipt - ship) // DAY <= 30).all()
    # a line ships 1..121 days after its order's date: all lines of an order within 120 days of each other
    assert (np.maximum.reduceat(ship, first) - np.minimum.reduceat(ship, first)).max() <= 120 * DAY
    assert ship.min() >= day(1992, 1, 2) and ship.max() <= day(1998, 8, 2) + 121 * DAY and ship.max() > day(1998, 9, 2)
    assert (commit - ship).min() >= (30 - 121) * DAY and (commit - ship).max() <= (90 - 1) * DAY
    current = day(1995, 6, 17)
    flag, status = b["l_returnflag"], b["l_linestatus"]
    assert ((flag == 1) == (receipt > current)).all() and ((status == 1) == (ship > current)).all()
    early = flag[receipt <= current]
    assert 0.48 < (early == 0).mean() < 0.52 and set(np.unique(early)) == {0, 2}  # A or R at even odds
    assert 0.002 < ((flag == 1) & (status == 0)).mean() < 0.02  # N/F: shipped by the date, received after it
    cols = gen.columns(cfg)
    assert [gen.cardinality(cols[c]) for c in ("l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode", "l_comment")] == [3, 2, 4, 7, 65_536]
    texts = gen.distinct(cols["l_comment"])
    assert len(set(texts)) == 65_536 and min(map(len, texts)) >= 10 and max(map(len, texts)) <= 43
    # about 1.9 % of the rows pass Q6's predicate: a year in 6.8, 3 discounts of 11, 23 quantities of 50
    q6 = (ship >= day(1994, 1, 1)) & (ship < day(1995, 1, 1)) & (b["l_discount"] >= 0.05) & (b["l_discount"] <= 0.07) & (b["l_quantity"] < 24)
    assert 0.015 < q6.mean() < 0.023


def test_arrow_table_is_what_parseable_lands():
    import pyarrow as pa

    cfg = small(minutes=1, rows=5000)
    b = gen.gen_minute(cfg, SEED, 0)
    t = gen.to_arrow(cfg, 0, b)
    cols = gen.columns(cfg)
    for name, col in cols.items():
        want = {"timestamp": pa.timestamp("ms"), "float": pa.float64(), "string": pa.string()}[col["type"]]
        assert t.schema.field(name).type == want, name
    assert t["l_returnflag"].to_pylist()[:100] == [["A", "N", "R"][i] for i in b["l_returnflag"][:100]]
    assert t["l_comment"].to_pylist()[:20] == [gen.distinct(cols["l_comment"])[i] for i in b["l_comment"][:20]]
    assert t["l_shipdate"].cast(pa.int64()).to_numpy().tolist() == b["l_shipdate"].tolist()
    assert t["l_extendedprice"].to_numpy().tolist() == b["l_extendedprice"].tolist()


def direct(text: str, cfg: dict, batches: dict) -> list:
    """Row by row in plain Python over the decoded values."""
    rows = []
    if text == "tpch_q1":
        groups: dict = {}
        for b in batches.values():
            for i in range(len(b["l_shipdate"])):
                if b["l_shipdate"][i] > day(1998, 9, 2):
                    continue
                g = groups.setdefault(("ANR"[b["l_returnflag"][i]], "FO"[b["l_linestatus"][i]]), [0, 0.0, 0.0, 0.0, 0.0, 0.0])
                q, p, d, t = (float(b[c][i]) for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"))
                for j, v in enumerate((1, q, p, p * (1 - d), p * (1 - d) * (1 + t), d)):
                    g[j] += v
        for (flag, status), (n, q, p, dp, ch, d) in sorted(groups.items()):
            rows.append({"l_returnflag": flag, "l_linestatus": status, "sum_qty": q, "sum_base_price": p, "sum_disc_price": dp, "sum_charge": ch,
                         "avg_qty": q / n, "avg_price": p / n, "avg_disc": d / n, "count_order": n})
        return rows
    revenue = 0.0
    for b in batches.values():
        for i in range(len(b["l_shipdate"])):
            if day(1994, 1, 1) <= b["l_shipdate"][i] < day(1995, 1, 1) and 0.05 <= b["l_discount"][i] <= 0.07 and b["l_quantity"][i] < 24:
                revenue += float(b["l_extendedprice"][i]) * float(b["l_discount"][i])
    return [{"revenue": revenue}]


@pytest.mark.parametrize("text", TEXTS)
def test_partials_add_up_to_the_direct_answer(text):
    cfg = small(minutes=4, rows=6000)
    q, ref = refcore.load_text(text, cfg), refcore.module_of(text)
    assert "{" not in q["sql"] and "FROM lineitem" in q["sql"]
    batches = {m: gen.gen_minute(cfg, SEED, m) for m in range(4)}
    parts = {m: ref.partial(q, cfg, m, b) for m, b in batches.items()}
    for lookback in (1, 2, 4):
        minutes = range(4 - lookback, 4)
        want = ref.merge(q, cfg, {m: parts[m] for m in minutes})
        records = direct(text, cfg, {m: batches[m] for m in minutes})
        v = ref.compare(q, cfg, records, want)
        assert v.mismatches == 0 and v.floats >= 1 and v.float_rel_err < 1e-12, v.notes
    # what has to be exact is held: a count off by one, a group left out, rows out of order
    if text == "tpch_q1":
        assert ref.compare(q, cfg, [dict(records[0], count_order=records[0]["count_order"] + 1), *records[1:]], want).mismatches == 1
        assert ref.compare(q, cfg, records[1:], want).mismatches == 1
        assert ref.compare(q, cfg, records[::-1], want).mismatches == 1
    # a control's lower precision holds the INPUTS at that dtype: the answer moves, keys and counts do not
    low = ref.merge(q, cfg, {m: ref.partial(q, cfg, m, batches[m], "bfloat16") for m in range(4)})
    assert np.array_equal(low["keys"], want["keys"]) and np.array_equal(low["count"], want["count"])
    name = "sum_revenue" if text == "tpch_q6" else "sum_charge"
    assert 1e-5 < np.abs(low[name] / want[name] - 1).max() < 1e-2


def test_the_roofline_charges_each_text_the_columns_it_names():
    cfg = gen.load_config("tpch_lineitem")
    for text, width, groups in (("tpch_q1", 22, 6), ("tpch_q6", 16, 1)):
        q = refcore.load_text(text, cfg)
        named = refcore.module_of(text).named_columns(q)
        assert sum(gen.columns(cfg)[c]["width_bytes"] for c in named) == width
        answer = groups * (sum(gen.columns(cfg)[k]["width_bytes"] for k in q["group_by"]) + 8 * len(q["aggs"]))
        assert roofline.required_bytes(cfg, q, named, 60) == 60_000_000 * width + answer
        # the memory's bound, on one chip's peak (peaks.json: 819 GB/s)
        assert roofline.least_seconds(cfg, q, named, 60, "TPU v5 lite", 1) == pytest.approx((60_000_000 * width + answer) / 819e9)
        assert roofline.least_seconds(cfg, q, named, 60, "TPU v5 lite", 4) == pytest.approx(roofline.least_seconds(cfg, q, named, 60, "TPU v5 lite", 1) / 4)


def test_the_mix_is_one_stream_of_both_texts_over_the_whole_table():
    cfg, mix = gen.load_config("tpch_lineitem"), traffic.load_mix("q1q6")
    assert (mix["loop"], mix["clients"], mix["queries"], mix["shares"], mix["lookback_fractions"]) == ("closed", 1, TEXTS, [1, 1], [1.0])
    assert all(mix["provenance"][k] for k in ("queries", "shares", "clients", "lookback_fractions"))
    sent = [r for r, _ in zip(traffic.sequence(cfg, mix, 5), range(8))]
    assert all(r["lookback"] == 60 for r in sent) and len({r["endTime"] for r in sent}) == 8
    assert all(sorted(r["query"] for r in sent[i:i + 2]) == TEXTS for i in range(0, 8, 2))


@pytest.fixture(scope="module")
def cpu_engine(tmp_path_factory):
    """80,000 generated rows through the product's own ingest path, and its CPU engine over them."""
    from parseable_tpu.config import Options, StorageOptions
    from parseable_tpu.core import Parseable
    from parseable_tpu.event import Event
    from parseable_tpu.query.session import QuerySession

    base = tmp_path_factory.mktemp("cpu_engine_tpch")
    opts = Options()
    opts.local_staging_path = base / "staging"
    p = Parseable(opts, StorageOptions(backend="local-store", root=base / "data"))
    cfg = small(minutes=4, rows=20_000)
    stream = p.create_stream_if_not_exists(cfg["stream"])
    t0 = datetime.fromtimestamp(cfg["base_ms"] / 1000, UTC)
    for minute in range(cfg["minutes"]):
        for batch in gen.to_arrow(cfg, minute, gen.gen_minute(cfg, SEED, minute)).to_batches():
            Event(stream_name=cfg["stream"], rb=batch, origin_size=batch.num_rows * 150, is_first_event=minute == 0,
                  parsed_timestamp=t0 + timedelta(minutes=minute)).process(stream, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()
    yield QuerySession(p, engine="cpu"), cfg
    p.shutdown()


@pytest.mark.parametrize("text", TEXTS)
def test_reference_agrees_with_the_products_cpu_engine(cpu_engine, text):
    session, cfg = cpu_engine
    q, ref = refcore.load_text(text, cfg), refcore.module_of(text)
    parts = {m: ref.partial(q, cfg, m, gen.gen_minute(cfg, SEED, m)) for m in range(cfg["minutes"])}
    for lookback in (1, 4):
        req = traffic.request(cfg, text, lookback, 7)
        records = session.query(q["sql"], req["startTime"], req["endTime"]).to_json_rows()
        want = ref.merge(q, cfg, {m: parts[m] for m in range(cfg["minutes"] - lookback, cfg["minutes"])})
        v = ref.compare(q, cfg, records, want)
        assert v.mismatches == 0 and records, v.notes
        assert v.float_rel_err < 1e-9  # the CPU engine sums in f64


def test_the_two_readers_read_the_programs_counters_and_nothing_where_it_has_none():
    from benchmark import run as harness

    routes = lambda d, h: {"stats": {"device_routes": {"expr_aggs_device": d, "expr_aggs_host": h}}}  # noqa: E731
    run = {"responses": [routes(2, 0), routes(1, 0), routes(0, 1)], "after": {"parseable_tpu_encode_declined_total": {'reason="time_span"': 2.0, 'reason="nested"': 0.0}}}
    assert harness.read_metric("expr_fold_share", run) == 75.0 and harness.read_metric("encode_declined_columns", run) == 2.0
    # the parent's program has neither counter, and a window of plain aggregates asks for no expression: nothing, never 0
    bare = {"responses": [{"stats": {"device_routes": {"cpu_fallback": 0}}}], "after": {}}
    assert harness.read_metric("expr_fold_share", bare) is None and harness.read_metric("encode_declined_columns", bare) is None
    assert harness.read_metric("expr_fold_share", {"responses": [routes(0, 0)], "after": {}}) is None
    cell = harness.load_cell("tpch_lineitem.q1q6")
    assert {"expr_fold_share", "encode_declined_columns", "program_roofline", "warm_block_share"} <= {m["name"] for m in cell["per_layer"]}
    assert [m["name"] for m in cell["end_to_end"]] == ["query_p95_ms", "scan_rows_per_s", "setup_s"]


def test_a_program_that_does_not_count_expression_aggregates_is_refused_before_any_work(monkeypatch, capsys):
    """The parent of PR 30 answers both texts through the CPU engine with every route counter at 0, and a traced run of it
    holds no device operation: the cell's run ends in phase `arguments` there (exit 20, no line), as for an unknown cell."""
    import sys

    from prometheus_client import CollectorRegistry

    from benchmark import needs
    from benchmark import run as harness
    from parseable_tpu.utils import metrics

    needs.counted_expression_aggregates("tpch_q1")  # this program counts them
    monkeypatch.setattr(metrics, "REGISTRY", CollectorRegistry())  # one that publishes no such family
    for text in TEXTS:  # a text states its need when its reference is imported
        monkeypatch.delitem(sys.modules, f"benchmark.reference.{text}")
    with pytest.raises(ValueError, match="parseable_tpu_expr_aggregates_total.*tpch_q6"):
        refcore.load_text("tpch_q6", small())
    capsys.readouterr()
    assert harness.main(["--workload", "tpch_lineitem.q1q6", "--seed", "7", "--seconds", "1", "--trace", "1"]) == harness.EXIT_CODES["arguments"] == 20
    said = capsys.readouterr()
    assert said.out == "" and said.err.strip().splitlines()[-1].startswith("benchmark/run.py: FAILED in phase 'arguments': ValueError: the program")
    assert harness.load_cell("flog_lowcard.dash")["name"] == "flog_lowcard.dash"  # a cell that sends neither text asks nothing
