"""TPC-H Q1, "Pricing Summary Report" (specification rev 3.0.1, clause 2.4.1), DELTA = 90: lines shipped on or before
1998-09-02, by return flag and line status.

Plain reference of `benchmark/sql/tpch_q1.sql`, numpy f64 over the generated values. `refcore.SPEC` can say neither a
range predicate nor a sum over an expression, so `partial` is this module's own: its sums are named as `refcore.merge`,
`refcore.agg_values` and the controls expect (`sum_<col>`, where `col` of an expression aggregate is the expression's
name), which lets the merge and the comparison stay `refcore`'s; `compare` adds the text's ORDER BY. With a control's
`value_dtype` the INPUTS are held at that precision before the expressions are evaluated; the predicate reads the values
as they are, since keys and counts stay the response's own."""

import numpy as np

from benchmark import needs, refcore

needs.counted_expression_aggregates("tpch_q1")  # or the run ends here, exit 20: needs.py says why

SHIPPED_BY_MS = 904_694_400_000  # 1998-09-02T00:00:00Z: 1998-12-01 less 90 days

# the count first: it is the aggregate a planted fault alters, and it has to be exact
SPEC = {"group_by": ["l_returnflag", "l_linestatus"],
        "aggs": [{"as": "count_order", "fn": "count"},
                 {"as": "sum_qty", "fn": "sum", "col": "l_quantity"},
                 {"as": "sum_base_price", "fn": "sum", "col": "l_extendedprice"},
                 {"as": "sum_disc_price", "fn": "sum", "col": "disc_price"},
                 {"as": "sum_charge", "fn": "sum", "col": "charge"},
                 {"as": "avg_qty", "fn": "avg", "col": "l_quantity"},
                 {"as": "avg_price", "fn": "avg", "col": "l_extendedprice"},
                 {"as": "avg_disc", "fn": "avg", "col": "l_discount"}]}


def named_columns(q: dict) -> list:
    return ["l_shipdate", "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice", "l_discount", "l_tax"]


def partial(q: dict, cfg: dict, minute: int, batch: dict, value_dtype: str | None = None) -> dict:
    mask = batch["l_shipdate"] <= SHIPPED_BY_MS
    key = batch["l_returnflag"][mask] * 2 + batch["l_linestatus"][mask]  # mixed radix, as refcore.partial makes it
    uniq, inv = np.unique(key, return_inverse=True)
    qty, price, disc, tax = (refcore.round_to(batch[c][mask], value_dtype) for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (1 - disc)
    values = {"l_quantity": qty, "l_extendedprice": price, "l_discount": disc, "disc_price": disc_price, "charge": disc_price * (1 + tax)}
    out = {"keys": uniq, "count": np.bincount(inv, minlength=len(uniq)).astype(np.int64)}
    for name, v in values.items():
        out[f"sum_{name}"] = np.bincount(inv, weights=v, minlength=len(uniq))
    return out


merge = refcore.merge


def compare(q: dict, cfg: dict, records: list, want: dict, stand_in: dict | None = None) -> refcore.Verdict:
    v = refcore.compare(q, cfg, records, want, stand_in)
    order = [(r.get("l_returnflag"), r.get("l_linestatus")) for r in records]
    if all(isinstance(a, str) and isinstance(b, str) for a, b in order) and order != sorted(order):
        v.wrong(f"{q['name']}: rows are not in the order of l_returnflag, l_linestatus: {order}")
    return v
