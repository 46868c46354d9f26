"""Generator pieces that the TPC-H family of schemas share.

TPC-H (spec v3, section 4.2.3) and the Star Schema Benchmark, which is
derived from it, draw their keys and prices by the same rules.  Each rule
is written once here; a configuration file composes them into its tables.
Strings become integer codes at the source's cardinalities, dates become
``yyyymmdd`` integers.
"""
from __future__ import annotations

import numpy as np

#: TPC-H's nation table: nation key -> region key (AFRICA 0, AMERICA 1,
#: ASIA 2, EUROPE 3, MIDDLE EAST 4).
NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0,
                          1, 2, 3, 4, 2, 3, 3, 1], dtype=np.int64)
AMERICA, ASIA = 1, 2

#: the first calendar day of both schemas
START_DAY = np.datetime64("1992-01-01")
#: order dates lie in [START_DAY, ENDDATE - 151 days]: 1992-01-01..1998-08-02
ORDER_DAYS = 2406


def day_keys(day_index) -> np.ndarray:
    """``yyyymmdd`` integers of the days ``START_DAY + day_index``."""
    day_index = np.asarray(day_index, dtype=np.int64)
    if day_index.size > 4096:   # many rows, few distinct days: a table
        return _day_keys(np.arange(int(day_index.max()) + 1))[day_index]
    return _day_keys(day_index)


def _day_keys(day_index: np.ndarray) -> np.ndarray:
    d = START_DAY + day_index.astype("timedelta64[D]")
    y = d.astype("datetime64[Y]")
    m = d.astype("datetime64[M]")
    year = y.astype(np.int64) + 1970
    month = (m - y).astype(np.int64) + 1
    day = (d - m).astype(np.int64) + 1
    return year * 10000 + month * 100 + day


def day_of_year(day_index) -> np.ndarray:
    """0-based day within its year of the days ``START_DAY + day_index``."""
    d = START_DAY + np.asarray(day_index, dtype=np.int64).astype("timedelta64[D]")
    return (d - d.astype("datetime64[Y]")).astype(np.int64)


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents: 90000 + ((key / 10) mod 20001) + 100 (key mod 1000)."""
    partkey = np.asarray(partkey, dtype=np.int64)
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def lines_per_order(rng: np.random.Generator, n_orders: int,
                    n_lines: int) -> np.ndarray:
    """Lines of each order, each in 1..7 as dbgen draws them, adjusted so
    that they add up to exactly ``n_lines``: every seed then has the same
    table sizes, so the same compiled programs serve every seed."""
    if not n_orders <= n_lines <= 7 * n_orders:
        raise ValueError(f"{n_lines} lines cannot spread over {n_orders} "
                         f"orders at 1..7 lines each")
    counts = rng.integers(1, 8, n_orders)
    diff = int(counts.sum()) - n_lines
    while diff:
        # move the surplus (or deficit) one line at a time onto orders that
        # stay inside 1..7, in an order drawn from the seed
        room = np.flatnonzero(counts > 1 if diff > 0 else counts < 7)
        pick = rng.permutation(room)[:abs(diff)]
        counts[pick] -= 1 if diff > 0 else -1
        diff = int(counts.sum()) - n_lines
    return counts


def sparse_orderkeys(n_orders: int) -> np.ndarray:
    """TPC-H's sparse order keys: of every 32 keys only the first 8 are used."""
    j = np.arange(n_orders, dtype=np.int64)
    return (j // 8) * 32 + j % 8 + 1


def partsupp_suppkey(partkey, i, n_supp: int) -> np.ndarray:
    """The i-th (0..3) supplier of a part, by TPC-H's formula:
    (partkey + i (S/4 + (partkey - 1) / S)) mod S + 1."""
    partkey = np.asarray(partkey, dtype=np.int64)
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1
