"""One column of TPC-H's LINEITEM as `dbgen` fills it (TPC-H specification
rev 3.0.1, clause 4.2.3), named by `gen.field`. The columns of a row depend on
each other (the price on the part and the quantity, the three dates on the
order's date, the flags on the dates), so the whole row is drawn jointly, once
for each `rng` a minute bucket is made from, by the first of these columns that
is asked for; the others read what that draw made. The numbers come from `rng`
alone, in the fixed order of `_rows`.

Dates are epoch milliseconds at midnight UTC; the five string columns are codes
of a dictionary that is the same in every minute. `l_comment` indexes a pool of
`gen.pool` seeded pseudo-texts of 10 to 43 characters (dbgen's grammar is not
reproduced: the configuration's `assumed` says so)."""

import functools
from datetime import date

import numpy as np

PER_MINUTE = False
DAY_MS = 86_400_000
EPOCH = date(1970, 1, 1)
START_DAY = (date(1992, 1, 1) - EPOCH).days  # STARTDATE
CURRENT_DAY = (date(1995, 6, 17) - EPOCH).days  # CURRENTDATE
LAST_ORDER_DAY = (date(1998, 8, 2) - EPOCH).days  # ENDDATE (1998-12-31) less 151 days

DICTIONARIES = {
    "l_returnflag": ["A", "N", "R"],
    "l_linestatus": ["F", "O"],
    "l_shipinstruct": ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"],
    "l_shipmode": ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"],
}
DATES = ("l_shipdate", "l_commitdate", "l_receiptdate")
WORDS = ("furiously quickly carefully blithely slyly regular express ironic final pending bold even special silent "
         "deposits requests accounts packages foxes theodolites instructions dependencies platelets pinto beans ideas "
         "sleep nag haggle boost wake cajole integrate use detect among across above along after the").split()


@functools.lru_cache(maxsize=4)
def comments(pool: int) -> list:
    """`pool` distinct texts of 10 to 43 characters, the same in every run."""
    rng = np.random.default_rng(4_2_3)
    picks = rng.integers(0, len(WORDS), (2 * pool, 12))
    lengths = rng.integers(10, 44, 2 * pool)
    texts = dict.fromkeys(" ".join(WORDS[i] for i in row)[:n] for row, n in zip(picks, lengths))
    return list(texts)[:pool]


def _rows(cfg: dict, rng, minute: int, n: int, pool: int) -> dict:
    """The `n` rows of one minute, bulk-loaded in order-key order."""
    sf = cfg["scale_factor"]
    lines = rng.integers(1, 8, n)  # 1 to 7 lines an order; more orders than the minute holds
    ends = np.cumsum(lines)
    orders = int(np.searchsorted(ends, n)) + 1  # the orders that hold the first n lines: the last one may be cut
    order_of = np.repeat(np.arange(orders), lines[:orders])[:n]
    first = np.concatenate([[0], ends[: orders - 1]])
    # the minute's orders take dense indexes of their own (minute * n up), so keys are unique and rise through the
    # stream whatever the earlier minutes drew; of every 32 keys the first 8 are used
    dense = minute * n + order_of
    order_day = rng.integers(START_DAY, LAST_ORDER_DAY + 1, orders)[order_of]
    partkey = rng.integers(1, sf * 200_000 + 1, n)
    suppliers = sf * 10_000
    suppkey = (partkey + rng.integers(0, 4, n) * (suppliers // 4 + (partkey - 1) // suppliers)) % suppliers + 1
    quantity = rng.integers(1, 51, n)
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
    ship_day = order_day + rng.integers(1, 122, n)
    commit_day = order_day + rng.integers(30, 91, n)
    receipt_day = ship_day + rng.integers(1, 31, n)
    return {
        "l_orderkey": (dense // 8 * 32 + dense % 8 + 1).astype(np.float64),
        "l_partkey": partkey.astype(np.float64),
        "l_suppkey": suppkey.astype(np.float64),
        "l_linenumber": (np.arange(n) - first[order_of] + 1).astype(np.float64),
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": quantity * retail_cents / 100.0,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        # R or A at even odds where the line was received on or before CURRENTDATE, else N
        "l_returnflag": np.where(receipt_day <= CURRENT_DAY, rng.integers(0, 2, n) * 2, 1),
        "l_linestatus": (ship_day > CURRENT_DAY).astype(np.int64),
        "l_shipdate": ship_day * DAY_MS,
        "l_commitdate": commit_day * DAY_MS,
        "l_receiptdate": receipt_day * DAY_MS,
        "l_shipinstruct": rng.integers(0, 4, n),
        "l_shipmode": rng.integers(0, 7, n),
        "l_comment": rng.integers(0, pool, n),
    }


_drawn: list = [None, None]  # the generator the last rows were drawn from (held, so that no other can be taken for it), and the rows


def draw(col, cfg, rng, minute, n):
    if _drawn[0] is not rng:
        pool = next(c["gen"]["pool"] for c in cfg["columns"] if c["gen"].get("field") == "l_comment")
        _drawn[:] = rng, _rows(cfg, rng, minute, n, pool)
    return _drawn[1][col["gen"]["field"]]


def dictionary(col, minute):
    field = col["gen"]["field"]
    if field == "l_comment":
        return comments(col["gen"]["pool"])
    if field not in DICTIONARIES:
        raise ValueError(f"column {field} holds values, not codes of a dictionary")
    return list(DICTIONARIES[field])


def cardinality(col):
    return len(dictionary(col, 0))


def arrow(col, minute, values):
    import pyarrow as pa

    field = col["gen"]["field"]
    if field in DATES:
        return pa.array(values, pa.timestamp("ms"))
    if field in DICTIONARIES or field == "l_comment":
        return pa.array(dictionary(col, minute), pa.string()).take(pa.array(values))
    return pa.array(values)
