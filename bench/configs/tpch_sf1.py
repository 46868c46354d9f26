"""TPC-H, scale factor 1: the lineitem ⋈ partsupp step of Q9.

Source: TPC Benchmark H, standard specification v3: section 4.2.3 gives
the key rules the generator follows; Q9 (product type profit measure)
joins lineitem with partsupp on (partkey, suppkey).  At scale factor 1
lineitem holds 6,001,215 rows, partsupp 800,000 and orders 1,500,000.

The composite key is packed into one ``int64`` column ``pskey =
partkey * 2**32 + suppkey`` on both sides.  Such a key is sparse, so the
engine joins it with its sorted ``int64`` join core.  Orders are generated
for the cells that will use them (a Q12-shaped join on the sparse
orderkey); the generator draws them now so that adding such a cell does
not change the data of this one.

The module holds the same pieces as every configuration: ``SOURCE``,
``SIZES``, ``REDUCED``, ``ASSUMED``, ``GUARANTEES``, :func:`generate`,
:func:`build`, :func:`references`, :func:`query_bytes`, :func:`kernel_bytes`.
"""
from __future__ import annotations

import numpy as np

from datagen import (ORDER_DAYS, day_keys, lines_per_order, partsupp_suppkey,
                     retail_price_cents, sparse_orderkeys)
from oracle import exact_segment_sum, lookup, scalar

SOURCE = ("TPC-H standard specification v3, scale factor 1 (keys by "
          "section 4.2.3): lineitem, partsupp, orders; the lineitem-partsupp "
          "join of Q9")

SIZES = {"lineitem": 6_001_215, "partsupp": 800_000, "orders": 1_500_000}
PARTS = 200_000             # P_PARTKEY domain at scale factor 1
SUPPLIERS = 10_000          # S_SUPPKEY domain at scale factor 1
CUSTOMERS = 150_000         # C_CUSTKEY domain at scale factor 1

MIN_ROWS = {"lineitem": 1_000, "partsupp": 400, "orders": 250}

REDUCED = [
    "(partkey, suppkey) is packed into one int64 column pskey = "
    "partkey * 2**32 + suppkey on lineitem and partsupp",
    "strings are int64 codes at their cardinalities (l_shipmode 7, "
    "o_orderpriority 5, l_returnflag 3, ...; comments are random codes); "
    "dates are yyyymmdd integers; money is in cents",
    "the template is Q9's lineitem-partsupp join alone: Q9's joins with "
    "part, supplier, nation and orders, its p_name filter and its profit "
    "expression are left out; it sums ps_supplycost over the join",
]

ASSUMED = [
    "each order has 1..7 lines, adjusted so that lineitem has exactly "
    "6,001,215 rows",
    "l_quantity uniform 1..50, l_discount 0..10, l_tax 0..8 (percent)",
    "ps_supplycost uniform 100..100000 cents",
    "o_orderdate uniform over 1992-01-01..1998-08-02; l_shipdate = "
    "orderdate + 1..121 days, l_commitdate = orderdate + 30..90, "
    "l_receiptdate = shipdate + 1..30",
    "o_custkey uniform over the customer keys that are not multiples of 3",
]

GUARANTEES = ("read-only analytic queries over a static snapshot; every "
              "answer is exact: integer sums of cents, compared for equality")

TEMPLATES = ("Q9.ps_join",)

_READS = {"Q9.ps_join": {"lineitem": ("pskey",),
                         "partsupp": ("pskey", "ps_supplycost")}}


def sizes(scale: float = 1.0) -> dict:
    """Rows of each table; ``scale`` < 1 is for tests on the CPU only."""
    return {t: max(MIN_ROWS[t], round(n * scale)) for t, n in SIZES.items()}


def pack(partkey, suppkey) -> np.ndarray:
    return (np.asarray(partkey, np.int64) << 32) | np.asarray(suppkey,
                                                              np.int64)


def generate(seed: int, scale: float = 1.0) -> dict:
    """All tables as ``{table: {column: int64 array}}``, drawn from ``seed``.
    Every seed gives the same table sizes."""
    n = sizes(scale)
    rng = np.random.default_rng(seed)
    n_parts = n["partsupp"] // 4
    # at least 100 suppliers, so that a part's four stay distinct when a
    # test scales the tables down
    n_supp = max(100, round(SUPPLIERS * n_parts / PARTS))

    ps_part = np.repeat(np.arange(1, n_parts + 1, dtype=np.int64), 4)
    ps_supp = partsupp_suppkey(ps_part, np.tile(np.arange(4), n_parts),
                               n_supp)
    partsupp = {"pskey": pack(ps_part, ps_supp), "ps_partkey": ps_part,
                "ps_suppkey": ps_supp,
                "ps_availqty": rng.integers(1, 10_000, len(ps_part)),
                "ps_supplycost": rng.integers(100, 100_001, len(ps_part)),
                "ps_comment": rng.integers(0, 1 << 40, len(ps_part))}

    n_orders = n["orders"]
    n_cust = max(3, round(CUSTOMERS * n_orders / SIZES["orders"]))
    orderkey = sparse_orderkeys(n_orders)
    order_day = rng.integers(0, ORDER_DAYS, n_orders)
    cust = rng.integers(0, n_cust - n_cust // 3, n_orders)
    rows = n["lineitem"]
    per_order = lines_per_order(rng, n_orders, rows)
    first = np.cumsum(per_order) - per_order
    l_day = np.repeat(order_day, per_order)
    partkey = rng.integers(1, n_parts + 1, rows)
    suppkey = partsupp_suppkey(partkey, rng.integers(0, 4, rows), n_supp)
    quantity = rng.integers(1, 51, rows)
    extended = quantity * retail_price_cents(partkey)
    discount = rng.integers(0, 11, rows)
    tax = rng.integers(0, 9, rows)
    ship = l_day + rng.integers(1, 122, rows)
    receipt = ship + rng.integers(1, 31, rows)
    # TPC-H's CURRENTDATE, 1995-06-17: lines received by then are returned
    # (R or A) or not (N); shipped by then, F, else O
    shipped = day_keys(ship) <= 19950617
    lineitem = {
        "orderkey": np.repeat(orderkey, per_order),
        "pskey": pack(partkey, suppkey),
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": np.arange(rows) - np.repeat(first, per_order) + 1,
        "l_quantity": quantity,
        "l_extendedprice": extended,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": np.where(day_keys(receipt) <= 19950617,
                                 rng.integers(0, 2, rows), 2),
        "l_linestatus": shipped.astype(np.int64),
        "l_shipdate": day_keys(ship),
        "l_commitdate": day_keys(l_day + rng.integers(30, 91, rows)),
        "l_receiptdate": day_keys(receipt),
        "l_shipinstruct": rng.integers(0, 4, rows),
        "l_shipmode": rng.integers(0, 7, rows),
        "l_comment": rng.integers(0, 1 << 40, rows),
    }
    shipped_lines = np.add.reduceat(shipped.astype(np.int64), first)
    orders = {"orderkey": orderkey,
              "o_custkey": cust + cust // 2 + 1,   # skips multiples of 3
              # F: every line shipped, O: none, P: some
              "o_orderstatus": np.where(shipped_lines == per_order, 0,
                                        np.where(shipped_lines == 0, 1, 2)),
              "o_totalprice": np.add.reduceat(
                  extended * (100 - discount) * (100 + tax) // 10000, first),
              "o_orderdate": day_keys(order_day),
              "o_orderpriority": rng.integers(0, 5, n_orders),
              "o_clerk": rng.integers(1, 1001, n_orders),
              "o_shippriority": np.zeros(n_orders, np.int64),
              "o_comment": rng.integers(0, 1 << 40, n_orders)}
    tables = {"lineitem": lineitem, "partsupp": partsupp, "orders": orders}
    return {t: {c: np.ascontiguousarray(v, dtype=np.int64)
                for c, v in cols.items()} for t, cols in tables.items()}


def build(name: str, session, col):
    """Template ``name`` as a ``Query`` of ``session``."""
    if name == "Q9.ps_join":
        return (session.table("lineitem").join("partsupp", on="pskey")
                .aggregate("b_ps_supplycost", "sum"))
    raise KeyError(f"tpch_sf1 has no template {name!r}; it has {TEMPLATES}")


def references(names, tables: dict, segment_sum=exact_segment_sum) -> dict:
    """The answers of the templates ``names``, computed with numpy from
    ``tables``; ``segment_sum`` does the summation, exactly by default."""
    return {n: _reference(n, tables, segment_sum) for n in names}


def _reference(name, tables, segment_sum) -> dict:
    if name == "Q9.ps_join":
        # a join, whatever the keys' multiplicity: each lineitem row adds
        # the supply costs of every partsupp row with its key
        ps = tables["partsupp"]
        keys, gid = np.unique(ps["pskey"], return_inverse=True)
        cost = exact_segment_sum(ps["ps_supplycost"], gid.reshape(-1),
                                 len(keys))
        row, ok = lookup(keys, tables["lineitem"]["pskey"])
        return scalar(cost[row[ok]], segment_sum)
    raise KeyError(f"tpch_sf1 has no template {name!r}; it has {TEMPLATES}")


def query_bytes(name: str, table_rows: dict) -> int:
    """Bytes of every base-table column ``name`` reads, once, at logical
    width (8 bytes), plus its 8-byte scalar result."""
    return 8 + sum(8 * table_rows[t] * len(cols)
                   for t, cols in _READS[name].items())


def kernel_bytes(name: str, table_rows: dict) -> int:
    """The sorted join core runs no Pallas kernel."""
    return 0
