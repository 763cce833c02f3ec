"""TPC-H Q6, "Forecasting Revenue Change" (specification rev 3.0.1, clause 2.4.6), DATE = 1994-01-01, DISCOUNT = 0.06,
QUANTITY = 24: the revenue that the year's small, lightly discounted lines would have added without their discount.

Plain reference of `benchmark/sql/tpch_q6.sql`, numpy f64 over the generated values: one group, one sum over an
expression. `partial` is this module's own (see `tpch_q1.py`); the merge and the comparison are `refcore`'s. The bounds
of the discount are the literals the SQL text writes (0.05, 0.07), which are the doubles the generator makes of 5/100
and 7/100: no engine's arithmetic on a literal decides a row. A control's `value_dtype` holds the two multiplied inputs
at that precision; the predicate reads the values as they are."""

import numpy as np

from benchmark import needs, refcore

needs.counted_expression_aggregates("tpch_q6")  # or the run ends here, exit 20: needs.py says why

YEAR_MS = (757_382_400_000, 788_918_400_000)  # [1994-01-01, 1995-01-01)

SPEC = {"group_by": [], "aggs": [{"as": "revenue", "fn": "sum", "col": "revenue"}]}


def named_columns(q: dict) -> list:
    return ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]


def partial(q: dict, cfg: dict, minute: int, batch: dict, value_dtype: str | None = None) -> dict:
    ship, disc = batch["l_shipdate"], batch["l_discount"]
    mask = (ship >= YEAR_MS[0]) & (ship < YEAR_MS[1]) & (disc >= 0.05) & (disc <= 0.07) & (batch["l_quantity"] < 24)
    revenue = refcore.round_to(batch["l_extendedprice"][mask], value_dtype) * refcore.round_to(disc[mask], value_dtype)
    # the one group is there in every minute, with no row too: a global aggregate always answers one row
    return {"keys": np.zeros(1, np.int64), "count": np.array([mask.sum()], np.int64), "sum_revenue": np.array([revenue.sum()])}


merge, compare = refcore.merge, refcore.compare
