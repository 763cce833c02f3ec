"""The `tsbs_cpu_only` configuration, its two SQL texts and its cell (ISSUE 34): the generator makes TSBS's `cpu-only` rows
(every host in every tick, 10 s apart, tags fixed per host, ten clamped random walks written as whole numbers and continuous
across minutes of ingest); the two references' partials add up to a row-by-row answer; the product's CPU engine, over the
same rows through its own ingest path, agrees with them; the roofline charges the columns the texts name; the cell's control
comes out not correct; and a program that does not bin an event-time column on the device is refused before any work.
(`test_reference.py` runs such checks for the configurations that share the access log's columns.)"""

import json
import sys
from datetime import UTC, datetime, timedelta
from pathlib import Path

import numpy as np
import pytest

from benchmark import gen, refcore, roofline, traffic
from benchmark.gens import tsbs_cpu as kind
from benchmark.reference import tsbs_cpu_max_all_8 as eight

ROOT = Path(__file__).resolve().parents[2]
SEED = 3_000_000_019
HOUR = 3_600_000
TEXTS = ["tsbs_cpu_max_all_8", "tsbs_cpu_max_all_1"]
FIELDS = list(kind.FIELDS)
TAGS = ["hostname", *kind.TAGS]


def small(scale: int = 50, interval_ms: int = 100_000, minutes: int = 36) -> dict:
    """The day at a size a test can walk: `scale` hosts, a tick every `interval_ms`, still 40 minutes of samples a minute of
    ingest and 36 of those a day, so the texts' eight hours are the last 12 minutes here too."""
    cfg = gen.load_config("tsbs_cpu_only")
    cfg["scale"], cfg["interval_ms"], cfg["minutes"] = scale, interval_ms, minutes
    cfg["rows_per_minute"] = scale * (2_400_000 // interval_ms)
    cfg["rows"] = cfg["rows_per_minute"] * minutes
    next(c for c in cfg["columns"] if c["name"] == "hostname")["gen"]["scale"] = scale
    return cfg


def small_text(text: str, cfg: dict) -> dict:
    """The text over hosts the small stream has: host_<n> of the file's list becomes host_<n mod 47>."""
    q = refcore.load_text(text, cfg)
    renamed = {h: f"host_{int(h.split('_')[1]) % 47}" for h in q["hosts"]}
    assert len(set(renamed.values())) == len(renamed)
    q["hosts"] = [renamed[h] for h in q["hosts"]]
    for old, new in renamed.items():
        q["sql"] = q["sql"].replace(f"'{old}'", f"'{new}'")
    return q


def test_the_file_states_the_source_the_shapes_the_cut_and_the_guarantees():
    cfg = json.loads((ROOT / "benchmark" / "configs" / "tsbs_cpu_only.json").read_text())
    assert cfg["source"].startswith("timescale/tsbs (Time Series Benchmark Suite): use case cpu-only, scale 4000, interval 10 s") and len(cfg["source"]) <= 200
    assert cfg["reduced"] == ["days"] and cfg["days"] == 1 and cfg["fallback_taken"] is False and "330 s" in cfg["reduced_why"]
    assert (cfg["scale"], cfg["interval_ms"], cfg["rows_per_minute"], cfg["minutes"]) == (4000, 10_000, 960_000, 36)
    assert cfg["rows"] == cfg["minutes"] * cfg["rows_per_minute"] == 34_560_000 == cfg["scale"] * 86_400_000 // cfg["interval_ms"]
    assert cfg["time_end_ms"] - cfg["time_start_ms"] == 86_400_000 and cfg["time_start_ms"] == int(datetime(2016, 1, 1, tzinfo=UTC).timestamp() * 1000)
    names = [c["name"] for c in cfg["columns"]]
    assert names == ["p_timestamp", "time", *TAGS, *FIELDS] and len(names) == 22
    assert all(c["distribution"] for c in cfg["columns"][1:])
    types = {c["name"]: c["type"] for c in cfg["columns"]}
    assert {types[n] for n in TAGS} == {"string"} and {types[n] for n in FIELDS} == {"float"} and types["time"] == types["p_timestamp"] == "timestamp"
    cols = gen.columns(cfg)
    for name, col in cols.items():  # the canonical widths the roofline charges
        want = roofline.canonical_width(gen.cardinality(col)) if name in TAGS else 4
        assert col["width_bytes"] == want, name
    assert [gen.cardinality(cols[t]) for t in TAGS] == [4000, 9, 23, 100, 3, 2, 4, 20, 2, 3]
    assert set(cfg["assumed"]) >= {"layout", "time_column", "hosts_in_the_texts", "windows", "clients", "answers", "from_memory"}
    assert cfg["assumed"]["from_memory"].count("[from memory]") == 4 and "MaxAllDuration = 8 h" in cfg["assumed"]["from_memory"]
    flog = json.loads((ROOT / "benchmark" / "configs" / "flog_lowcard.json").read_text())
    assert cfg["env"] == {} and cfg["deployment"] == flog["deployment"]
    g = cfg["guarantees"]
    assert g["window"] == flog["guarantees"]["window"] and g["freshness"] == flog["guarantees"]["freshness"] and g["note"] == flog["guarantees"]["note"]
    assert "exact" in g["answers"] and "no tolerance" in g["answers"] and "CPU engine" in g["engine"] and "rounded" in g["bins"]
    assert cfg["control"]["values"] == "float8_e4m3" and cfg["limits"]["f32_err_ulps"] == 1.0


def test_every_host_reports_in_every_tick_ten_seconds_apart_from_the_first_of_january():
    cfg = gen.load_config("tsbs_cpu_only")
    cols = gen.columns(cfg)
    n = cfg["rows_per_minute"]
    for minute in (0, 17, 35):
        time = kind.draw(cols["time"], cfg, None, minute, n)
        host = kind.draw(cols["hostname"], cfg, None, minute, n)
        assert time[0] == cfg["time_start_ms"] + minute * 2_400_000 and time[-1] == time[0] + 239 * 10_000
        assert (time.reshape(240, 4000) == time[::4000, None]).all() and (np.diff(time[::4000]) == 10_000).all()
        assert (host.reshape(240, 4000) == np.arange(4000)).all()
    assert time[-1] + 10_000 == cfg["time_end_ms"]  # the last tick of the day
    assert gen.distinct(cols["hostname"])[:2] == ["host_0", "host_1"] and gen.distinct(cols["hostname"])[-1] == "host_3999"
    # a stream cut shorter keeps the day's end, where the texts' window lies
    cut = small(scale=10, minutes=18)
    assert kind.draw(gen.columns(cut)["time"], cut, None, 17, cut["rows_per_minute"])[-1] + cut["interval_ms"] == cut["time_end_ms"]
    assert kind.draw(gen.columns(cut)["time"], cut, None, 0, cut["rows_per_minute"])[0] == cut["time_start_ms"] + 12 * HOUR


def test_tags_are_fixed_per_host_and_a_datacenter_lies_in_its_region():
    cfg = small(scale=400, interval_ms=600_000)
    a, b = gen.gen_minute(cfg, SEED, 1), gen.gen_minute(cfg, SEED, 30)
    ticks = cfg["rows_per_minute"] // cfg["scale"]
    for tag in kind.TAGS:
        per_host = a[tag].reshape(ticks, cfg["scale"])
        assert (per_host == per_host[0]).all() and np.array_equal(per_host[0], b[tag][: cfg["scale"]]), tag
        assert len(np.unique(per_host[0])) == len(kind.TAGS[tag]) or tag == "rack"
    regions, dcs = list(kind.REGIONS), kind.TAGS["datacenter"]
    assert all(dcs[d].startswith(regions[r]) for r, d in zip(a["region"][: cfg["scale"]], a["datacenter"][: cfg["scale"]]))
    assert not np.array_equal(a["region"], gen.gen_minute(cfg, SEED + 1, 1)["region"])  # drawn from the seed


def test_fields_are_clamped_random_walks_written_as_whole_numbers_and_continuous_across_minutes():
    cfg = small(scale=300, interval_ms=10_000, minutes=3)
    ticks = cfg["rows_per_minute"] // cfg["scale"]
    minutes = [gen.gen_minute(cfg, SEED, m) for m in range(3)]
    states = np.concatenate([kind.walk(SEED, cfg["scale"], ticks, m).copy() for m in range(3)])  # [3 x 240, hosts, fields]
    for j, f in enumerate(FIELDS):
        written = np.concatenate([b[f] for b in minutes]).reshape(3 * ticks, cfg["scale"])
        assert written.dtype == np.float64 and (written == np.rint(written)).all() and written.min() >= 0 and written.max() <= 100
        assert np.array_equal(written, np.rint(states[:, :, j]))
    assert states.min() >= 0 and states.max() <= 100 and (states == 0).any() and (states == 100).any()  # clamped, and it binds
    steps = np.diff(states, axis=0)
    free = (states[1:] > 0) & (states[1:] < 100)  # a step that met no bound is the normal(0, 1) draw itself
    assert abs(steps[free].mean()) < 0.01 and 0.98 < steps[free].std() < 1.02 and np.abs(steps).max() < 7
    assert np.abs(steps[ticks - 1]).max() < 7 and np.abs(steps[2 * ticks - 1]).max() < 7  # across the minutes' edges too
    start = states[0] - steps[0]  # not observable; the first states lie a step from a uniform start
    assert 45 < states[0].mean() < 55 and states[0].std() > 25 and start.shape == states[0].shape


def test_a_minute_is_a_function_of_seed_and_minute_in_whatever_order_it_is_asked():
    cfg = small(scale=60, interval_ms=60_000)
    a = gen.gen_minute(cfg, SEED, 5)
    gen.gen_minute(cfg, SEED, 2)  # an earlier minute in between: the walk is redone from the start
    b = gen.gen_minute(cfg, SEED, 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    kind._walk.clear()
    c = gen.gen_minute(cfg, SEED, 5)  # and with nothing kept at all
    assert all(np.array_equal(a[k], c[k]) for k in a)
    other, later = gen.gen_minute(cfg, SEED + 1, 5), gen.gen_minute(cfg, SEED, 6)
    assert not np.array_equal(a["usage_user"], other["usage_user"]) and not np.array_equal(a["usage_user"], later["usage_user"])
    assert np.array_equal(a["hostname"], other["hostname"]) and (later["time"] == a["time"] + 2_400_000).all()
    ts = a["p_timestamp"]  # the minute of ingest, on the harness's clock
    assert (np.diff(ts) >= 0).all() and ts[0] >= cfg["base_ms"] + 5 * 60_000 and ts[-1] < cfg["base_ms"] + 6 * 60_000
    assert kind.seed_of(np.random.default_rng([2**63 + 5, 3])) == 2**63 + 5


def test_arrow_table_is_what_parseable_lands():
    import pyarrow as pa

    cfg = small(scale=40)
    b = gen.gen_minute(cfg, SEED, 3)
    t = gen.to_arrow(cfg, 3, b)
    assert t.num_rows == cfg["rows_per_minute"] and t.column_names == [c["name"] for c in cfg["columns"]]
    for name, col in gen.columns(cfg).items():
        want = {"timestamp": pa.timestamp("ms"), "float": pa.float64(), "string": pa.string()}[col["type"]]
        assert t.schema.field(name).type == want, name
    assert t["hostname"].to_pylist()[:45] == [f"host_{i % 40}" for i in range(45)]
    assert t["region"].to_pylist()[:40] == [kind.TAGS["region"][i] for i in b["region"][:40]]
    assert t["time"].cast(pa.int64()).to_numpy().tolist() == b["time"].tolist() and t["usage_idle"].to_numpy().tolist() == b["usage_idle"].tolist()


def direct(q: dict, cfg: dict, batches: dict) -> list:
    """Row by row in plain Python over the decoded values."""
    hosts = gen.distinct(gen.columns(cfg)["hostname"])
    bins: dict = {}
    for b in batches.values():
        for i in range(len(b["time"])):
            t = int(b["time"][i])
            if hosts[b["hostname"][i]] in q["hosts"] and eight.WINDOW_MS[0] <= t < eight.WINDOW_MS[1]:
                top = bins.setdefault(t - t % HOUR, [float("-inf")] * 10)
                for j, f in enumerate(FIELDS):
                    top[j] = max(top[j], float(b[f][i]))
    return [{"hour": datetime.fromtimestamp(h / 1000, UTC).isoformat(), **{f"max_{f}": top[j] for j, f in enumerate(FIELDS)}}
            for h, top in sorted(bins.items())]


@pytest.mark.parametrize("text", TEXTS)
def test_partials_add_up_to_the_direct_answer(text):
    cfg = small()
    q, ref = small_text(text, cfg), refcore.module_of(text)
    assert "{" not in q["sql"] and "FROM cpu " in q["sql"] and len(q["hosts"]) == (8 if text.endswith("8") else 1)
    assert all(f"max({f}) AS max_{f}" in q["sql"] for f in FIELDS) and "date_bin(interval '1 hour', time) AS hour" in q["sql"]
    assert "time >= '2016-01-01T16:00:00Z' AND time < '2016-01-02T00:00:00Z'" in q["sql"] and q["sql"].endswith("GROUP BY hour ORDER BY hour")
    batches = {m: gen.gen_minute(cfg, SEED, m) for m in range(20, 36)}
    parts = {m: ref.partial(q, cfg, m, b) for m, b in batches.items()}
    for lookback in (1, 12, 16):  # the last block alone; the eight hours exactly; four blocks more, which the window leaves out
        minutes = range(36 - lookback, 36)
        want = ref.merge(q, cfg, {m: parts[m] for m in minutes})
        records = direct(q, cfg, {m: batches[m] for m in minutes})
        assert len(records) == min(8, -(-lookback * 2 // 3)) == len(want["keys"])
        v = ref.compare(q, cfg, records, want)
        assert v.mismatches == 0 and v.floats == 10 * len(records) and v.f32_err_ulps == 0.0, v.notes
    assert int(want["count"].sum()) == len(q["hosts"]) * 8 * 36  # 36 ticks an hour here, every host in each
    # what has to be exact is held: a maximum off by one, a bin left out, rows out of order, a bin that is no hour
    off = ref.compare(q, cfg, [dict(records[0], max_usage_idle=records[0]["max_usage_idle"] + 1), *records[1:]], want)
    assert off.mismatches == 1 and off.f32_err_ulps > 100 * cfg["limits"]["f32_err_ulps"]
    assert ref.compare(q, cfg, records[1:], want).mismatches == 1 and ref.compare(q, cfg, records[::-1], want).mismatches == 1
    assert ref.compare(q, cfg, [dict(records[0], hour="2016-01-01T16:30:00"), *records[1:]], want).mismatches == 1
    # the control's precision holds the metrics at float8 first: the maxima move, keys and counts do not
    low = ref.merge(q, cfg, {m: ref.partial(q, cfg, m, batches[m], "float8_e4m3") for m in range(24, 36)})
    assert np.array_equal(low["keys"], want["keys"]) and np.array_equal(low["count"], want["count"])
    assert (low["sum_max_usage_user"] != want["sum_max_usage_user"]).any()
    assert np.array_equal(low["sum_max_usage_user"], eight.held_at(want["sum_max_usage_user"], "float8_e4m3"))  # rounding keeps the order
    # bfloat16 holds every whole number up to 256: the other cells' control would pass this one
    same = ref.merge(q, cfg, {m: ref.partial(q, cfg, m, batches[m], "bfloat16") for m in range(24, 36)})
    assert all(np.array_equal(same[k], want[k]) for k in want if k != "span")


def test_float8_holds_whole_numbers_up_to_sixteen():
    got = eight.held_at(np.array([0.0, 1, 15, 16, 17, 18, 19, 33, 97, 99, 100, 104]), "float8_e4m3")
    assert got.tolist() == [0, 1, 15, 16, 16, 18, 20, 32, 96, 96, 96, 104]
    assert np.array_equal(eight.held_at(np.arange(101.0), "bfloat16"), np.arange(101.0)) and eight.held_at(np.arange(3.0), None) is not None


def test_the_roofline_charges_each_text_the_columns_it_names_over_the_look_backs_rows():
    cfg = gen.load_config("tsbs_cpu_only")
    for text in TEXTS:
        q = refcore.load_text(text, cfg)
        named = refcore.module_of(text).named_columns(q)
        assert named == ["time", "hostname", *FIELDS]
        assert sum(gen.columns(cfg)[c]["width_bytes"] for c in named) == 46  # time 4, hostname 2, ten metrics at 4
        answer = 8 * (4 + 8 * 10)  # eight hours: a 4-byte key and ten maxima each
        assert roofline.required_bytes(cfg, q, named, 12) == 11_520_000 * 46 + answer
        assert roofline.least_seconds(cfg, q, named, 12, "TPU v5 lite", 1) == pytest.approx((11_520_000 * 46 + answer) / 819e9)


def test_the_mix_is_one_client_both_texts_in_equal_shares_over_the_last_eight_hours():
    cfg, mix = gen.load_config("tsbs_cpu_only"), traffic.load_mix("cpumax")
    assert (mix["loop"], mix["clients"], mix["queries"], mix["shares"], mix["lookback_fractions"]) == ("closed", 1, TEXTS, [1, 1], [0.3333])
    assert all(mix["provenance"][k] for k in ("queries", "shares", "clients", "lookback_fractions", "cold_pass"))
    assert traffic.lookbacks(mix, cfg["minutes"]) == [12] and traffic.pairs(mix, 36) == [(t, 12) for t in TEXTS]
    sent = [r for r, _ in zip(traffic.sequence(cfg, mix, 5), range(8))]
    assert all(r["lookback"] == 12 for r in sent) and len({r["endTime"] for r in sent}) == 8
    assert all(sorted(r["query"] for r in sent[i:i + 2]) == sorted(TEXTS) for i in range(0, 8, 2))
    # the minutes of ingest the request's bounds select hold exactly the text's eight hours
    first = kind.draw(gen.columns(cfg)["time"], cfg, None, 24, cfg["rows_per_minute"])[0]
    assert (first, cfg["time_end_ms"]) == eight.WINDOW_MS and sent[0]["startTime"] == traffic.iso(cfg["base_ms"] + 24 * 60_000)
    orders = {tuple(r["query"] for r, _ in zip(traffic.sequence(cfg, mix, seed), range(12))) for seed in range(6)}
    assert len(orders) > 1  # the order comes from the seed


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The small day through the product's own ingest path, and its two engines over it."""
    from parseable_tpu.config import Options, StorageOptions
    from parseable_tpu.core import Parseable
    from parseable_tpu.event import Event
    from parseable_tpu.query.session import QuerySession

    base = tmp_path_factory.mktemp("cpu_engine_tsbs")
    opts = Options()
    opts.local_staging_path = base / "staging"
    p = Parseable(opts, StorageOptions(backend="local-store", root=base / "data"))
    cfg = small()
    stream = p.create_stream_if_not_exists(cfg["stream"])
    t0 = datetime.fromtimestamp(cfg["base_ms"] / 1000, UTC)
    for minute in range(cfg["minutes"]):
        for batch in gen.to_arrow(cfg, minute, gen.gen_minute(cfg, SEED, minute)).to_batches():
            Event(stream_name=cfg["stream"], rb=batch, origin_size=batch.num_rows * 150, is_first_event=minute == 0,
                  parsed_timestamp=t0 + timedelta(minutes=minute)).process(stream, commit_schema=p.commit_schema)
    p.local_sync(shutdown=True)
    p.sync_all_streams()
    yield {engine: QuerySession(p, engine=engine) for engine in ("cpu", "tpu")}, cfg, p
    p.shutdown()


@pytest.mark.parametrize("engine", ["cpu", "tpu"])
@pytest.mark.parametrize("text", TEXTS)
def test_reference_agrees_with_the_products_engines_through_the_ingest_path(served, text, engine):
    """The CPU engine is the second independent implementation; the TPU engine (on the CPU backend here) is the served path's,
    and it folds every block itself: none goes to the CPU engine."""
    sessions, cfg, _ = served
    q, ref = small_text(text, cfg), refcore.module_of(text)
    parts = {m: ref.partial(q, cfg, m, gen.gen_minute(cfg, SEED, m)) for m in range(cfg["minutes"])}
    for lookback in (12, 36):  # the window's request, and the cold pass's over the whole stream
        req = traffic.request(cfg, text, lookback, 7)
        res = sessions[engine].query(q["sql"], req["startTime"], req["endTime"])
        records = res.to_json_rows()
        want = ref.merge(q, cfg, {m: parts[m] for m in range(cfg["minutes"] - lookback, cfg["minutes"])})
        v = ref.compare(q, cfg, records, want)
        assert v.mismatches == 0 and len(records) == 8 and v.f32_err_ulps == 0.0, v.notes
        if engine == "tpu":
            routes = res.stats["device_routes"]
            blocks = routes["device_warm"] + routes["device_cold"]
            assert routes["cpu_fallback"] + routes["cpu_adaptive"] == 0 and blocks == 12  # the cold pass's other 24 files are pruned
            assert routes["timebin_offorigin_device_blocks"] == routes["fold_minmax_scatter_blocks"] == blocks


def test_the_landed_types_are_the_configurations(served):
    import pyarrow as pa

    _, cfg, p = served
    schema = p.streams.get(cfg["stream"]).metadata.schema
    fields = schema if isinstance(schema, dict) else {f.name: f for f in schema}
    for name, col in gen.columns(cfg).items():
        want = {"timestamp": pa.timestamp("ms"), "float": pa.float64(), "string": pa.string()}[col["type"]]
        got = fields[name].type if hasattr(fields[name], "type") else fields[name]
        assert got == want or (col["type"] == "string" and pa.types.is_string(got)), (name, got)


def test_the_two_readers_read_the_programs_counters_and_nothing_where_it_has_none():
    from benchmark import run as harness

    routes = lambda **kw: {"stats": {"device_routes": kw}}  # noqa: E731
    run = {"responses": [routes(timebin_offorigin_device_blocks=12, timebin_offorigin_host_blocks=0, fold_minmax_scatter_blocks=12),
                         routes(timebin_offorigin_device_blocks=9, timebin_offorigin_host_blocks=3, fold_minmax_scatter_blocks=9)], "after": {}}
    assert harness.read_metric("offorigin_timebin_share", run) == 87.5 and harness.read_metric("minmax_scatter_share", run) == 100.0
    # a later program's other route counts under a key of the same form, and the share falls with no edit to the reader
    later = {"responses": [routes(fold_minmax_scatter_blocks=3, fold_minmax_compare_blocks=9)], "after": {}}
    assert harness.read_metric("minmax_scatter_share", later) == 25.0
    # the parent's program has none of the counters, and a window that bins no such column or folds no min or max: nothing, never 0
    bare = {"responses": [{"stats": {"device_routes": {"cpu_fallback": 0, "fold_onehot_blocks": 4}}}], "after": {}}
    assert harness.read_metric("offorigin_timebin_share", bare) is None and harness.read_metric("minmax_scatter_share", bare) is None
    none = {"responses": [routes(timebin_offorigin_device_blocks=0, timebin_offorigin_host_blocks=0, fold_minmax_scatter_blocks=0)], "after": {}}
    assert harness.read_metric("offorigin_timebin_share", none) is None and harness.read_metric("minmax_scatter_share", none) is None
    cell = harness.load_cell("tsbs_cpu_only.cpumax")
    names = {m["name"] for m in cell["per_layer"]}
    assert {"offorigin_timebin_share", "minmax_scatter_share", "program_device_ms", "program_roofline", "device_idle_share", "matmul_fold_share"} <= names
    assert "operand_puts_per_query" not in names and "expr_fold_share" not in names  # those keep their own lists of cells
    assert [m["name"] for m in cell["end_to_end"]] == ["query_p95_ms", "scan_rows_per_s", "setup_s"]
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert manifest["configs"][-1]["name"] == "tsbs_cpu_only" and manifest["workloads"][-1]["name"] == "tsbs_cpu_only.cpumax"
    assert [m["name"] for m in manifest["per_layer"][-2:]] == ["offorigin_timebin_share", "minmax_scatter_share"]
    assert all(m["workloads"] == ["tsbs_cpu_only.cpumax"] and m["moves"] == "scan_rows_per_s" for m in manifest["per_layer"][-2:])
    assert next(m for m in manifest["end_to_end"] if m["name"] == "scan_rows_per_s")["workloads"][-1] == "tsbs_cpu_only.cpumax"


def test_the_cells_own_file_and_its_control():
    from benchmark import run as harness

    own = json.loads((ROOT / "benchmark" / "cells" / "tsbs_cpu_only.cpumax.json").read_text())
    tpch = json.loads((ROOT / "benchmark" / "cells" / "tpch_lineitem.q1q6.json").read_text())
    assert own["chips"] == 1 and own["pin"] == tpch["pin"]  # the one-chip pinning word for word
    assert own["close"] == "whole_deck" and isinstance(own["trace_decks"], int) and own["control"] == "values_float8" and "bfloat16" in own["why"]
    control = harness.load_control("values_float8")
    cfg = gen.load_config("tsbs_cpu_only")
    assert control.value_dtype(cfg) == "float8_e4m3" and control.partial_dtype(cfg) is None


def test_a_program_that_does_not_bin_event_time_on_the_device_is_refused_before_any_work(monkeypatch, capsys):
    """The parent of PR 34 declares every `date_bin` over `time` and folds every block of every request on its CPU engine; a
    traced run of it holds no device operation: the cell's run ends in phase `arguments` there (exit 20, no line)."""
    from prometheus_client import CollectorRegistry

    from benchmark import needs_event_time
    from benchmark import run as harness
    from parseable_tpu.utils import metrics

    needs_event_time.device_time_bins_off_the_origin("tsbs_cpu_max_all_8")  # this program counts them
    monkeypatch.setattr(metrics, "REGISTRY", CollectorRegistry())  # one that publishes no such family
    for text in TEXTS:  # a text states its need when its reference is imported
        monkeypatch.delitem(sys.modules, f"benchmark.reference.{text}", raising=False)
    with pytest.raises(ValueError, match="parseable_tpu_timebin_offorigin_total.*tsbs_cpu_max_all_"):  # the first of the two to be imported says so
        refcore.load_text("tsbs_cpu_max_all_1", small())
    for trace in ("0", "1"):
        capsys.readouterr()
        assert harness.main(["--workload", "tsbs_cpu_only.cpumax", "--seed", "7", "--seconds", "1", "--trace", trace]) == harness.EXIT_CODES["arguments"] == 20
        said = capsys.readouterr()
        assert said.out == "" and said.err.strip().splitlines()[-1].startswith("benchmark/run.py: FAILED in phase 'arguments': ValueError: the program")
    assert harness.load_cell("flog_lowcard.dash")["name"] == "flog_lowcard.dash"  # a cell that sends neither text asks nothing
