"""TSBS DevOps query `cpu-max-all-8` (timescale/tsbs, `MaxAllCPU` with 8 hosts): the maximum of each of the ten CPU
metrics per hour, for eight hosts, over eight hours.

Plain reference of `benchmark/sql/tsbs_cpu_max_all_8.sql`, numpy f64 over the generated values, independent of the
program: mask by host and by `time`, bin by hour, the maximum of every metric per bin, rows in `hour` order. `refcore`'s
`SPEC` can say none of that (an IN list, a range on a second time column, an hour bin, a maximum), so `partial`, `merge`
(by maximum), `compare` and `named_columns` are this module's own. What the harness itself reads of a text keeps
`refcore`'s form, because its stand-in server, `judge()`'s control and `roofline.required_bytes` know no other:

- a bin's key is the hour's start in whole minutes from the configuration's `base_ms`, which is how `refcore` keys a
  `minute` group (negative here: 2016 lies before the harness's clock of ingest), shown under the alias `hour`;
- `group_by: ["minute"]` with `limit: 8` is how `roofline.required_bytes` is told "eight groups keyed by one 4-byte
  timestamp": the spec has no word for an hour bin, and the answer's 8 x (4 + 10 x 8) bytes are then exact;
- each maximum travels in the slot `refcore.agg_values` reads for `fn: "sum"` (`sum_max_<metric>`): the spec's three
  words for an aggregate are count, sum and avg, and `judge()` builds a control's stand-in through that function.

Maxima of whole numbers in 0..100 are exact in f32 (and in bfloat16): the comparison holds every key and every maximum
to equality (`mismatches`), and reports each maximum's gap in `refcore`'s f32 units as well, which reads 0.0 on a sound
run. A control's `value_dtype` holds the metrics at that precision before the maxima are taken (`float8_e4m3`: this
module's own rounding, three mantissa bits, whole numbers up to 16; anything else is `refcore.round_to`'s)."""

import numpy as np

from benchmark import gen, needs_event_time, refcore
from benchmark.gens.tsbs_cpu import FIELDS  # the ten metrics' names, in the row's order

needs_event_time.device_time_bins_off_the_origin("tsbs_cpu_max_all_8")  # or the run ends here, exit 20: needs_event_time.py says why

HOUR_MS = 3_600_000
WINDOW_MS = (1_451_664_000_000, 1_451_692_800_000)  # [2016-01-01T16:00:00Z, 2016-01-02T00:00:00Z): the text's literals
HOSTS = ["host_1287", "host_1290", "host_1458", "host_1515", "host_1950", "host_3008", "host_3298", "host_3507"]

SPEC = {"hosts": HOSTS, "window_ms": WINDOW_MS, "group_by": ["minute"], "key_alias": {"minute": "hour"}, "limit": 8,
        "aggs": [{"as": f"max_{f}", "fn": "sum", "col": f"max_{f}"} for f in FIELDS]}


def named_columns(q: dict) -> list:
    return ["time", "hostname", *FIELDS]


def held_at(values: np.ndarray, dtype: str | None) -> np.ndarray:
    if dtype != "float8_e4m3":
        return refcore.round_to(values, dtype)
    mantissa, exponent = np.frexp(values)  # four significant bits, round to nearest even
    return np.ldexp(np.rint(mantissa * 16) / 16, exponent)


def greatest(inv: np.ndarray, values: np.ndarray, groups: int) -> np.ndarray:
    """The maximum of `values` in each of `groups` groups, `inv` saying which group a value is of."""
    top = np.full(groups, -np.inf)
    np.maximum.at(top, inv, values)
    return top


def partial(q: dict, cfg: dict, minute: int, batch: dict, value_dtype: str | None = None) -> dict:
    codes = gen.code_of(gen.columns(cfg)["hostname"])
    time = batch["time"]
    mask = np.isin(batch["hostname"], [codes[h] for h in q["hosts"] if h in codes]) & (time >= q["window_ms"][0]) & (time < q["window_ms"][1])
    hour_ms = time[mask] // HOUR_MS * HOUR_MS
    uniq, inv = np.unique((hour_ms - cfg["base_ms"]) // 60_000, return_inverse=True)
    out = {"keys": uniq, "count": np.bincount(inv, minlength=len(uniq)).astype(np.int64)}
    for f in FIELDS:
        out[f"sum_max_{f}"] = greatest(inv, held_at(batch[f][mask], value_dtype), len(uniq))
    return out


def merge(q: dict, cfg: dict, partials: dict) -> dict:
    """The answer over the minutes in `partials`: a bin that several minutes of ingest hold takes the greatest."""
    minutes = sorted(partials)
    uniq, inv = np.unique(np.concatenate([partials[m]["keys"] for m in minutes]), return_inverse=True)
    out = {"keys": uniq, "span": 1}
    for name in partials[minutes[0]]:
        if name == "keys":
            continue
        vals = np.concatenate([partials[m][name] for m in minutes])
        if name == "count":
            out[name] = np.bincount(inv, weights=vals, minlength=len(uniq)).astype(np.int64)
        else:
            out[name] = greatest(inv, vals, len(uniq))
    return out


def compare(q: dict, cfg: dict, records: list, want: dict, stand_in: dict | None = None) -> refcore.Verdict:
    v = refcore.Verdict()
    if len(records) != len(want["keys"]):
        v.wrong(f"{q['name']}: {len(records)} rows, want {len(want['keys'])}")
    hours, seen = [], set()
    for r in records:
        try:
            hour_ms = refcore.parse_ts_ms(r["hour"])
        except (KeyError, ValueError, TypeError) as e:
            v.wrong(f"{q['name']}: row {r} has no hour ({e!r})")
            continue
        hours.append(hour_ms)
        key, rest = divmod(hour_ms - cfg["base_ms"], 60_000)
        i = int(np.searchsorted(want["keys"], key))
        if rest or hour_ms % HOUR_MS or i >= len(want["keys"]) or want["keys"][i] != key or key in seen:
            v.wrong(f"{q['name']}: hour {r['hour']} is not in the reference, or came twice")
            continue
        seen.add(key)
        for a in q["aggs"]:
            got = float(stand_in[a["as"]][i]) if stand_in is not None else r.get(a["as"])
            top = float(want[f"sum_{a['col']}"][i])
            if got != top:
                v.wrong(f"{q['name']}: {a['as']} of {r['hour']} is {got!r}, want {top}")
            if isinstance(got, (int, float)) and not isinstance(got, bool):
                v.gap(got, top, int(want["count"][i]), f"{q['name']}.{a['as']}")
    if hours != sorted(hours):
        v.wrong(f"{q['name']}: rows are not in the order of hour")
    return v
