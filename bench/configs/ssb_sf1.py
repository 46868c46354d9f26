"""Star Schema Benchmark, scale factor 1: the deployment, its data and queries.

Source: P. O'Neil, E. O'Neil, X. Chen, "Star Schema Benchmark", revision 3
(2009): the lineorder fact table with the date, customer, supplier and
part dimensions, and query flights 1 to 4.  At scale factor 1 the fact
table holds 6,001,215 rows.

What this file holds, for the harness to find by name:

* ``SOURCE``, ``SIZES``, ``REDUCED``, ``ASSUMED``, ``GUARANTEES``;
* :func:`generate`, the seeded generator;
* :func:`build`, each query template through the engine's ``Query`` API;
* :func:`references`, each template's plain numpy answer (they import
  nothing of the engine);
* :func:`query_bytes` and :func:`kernel_bytes`, the least bytes each
  template's work moves, from the table sizes and the template alone.

Every column is ``int64``; strings are integer codes at the source's
cardinalities.  Answers are dictionaries ``{group key: sum}`` (the key
``"all"`` for a scalar) and are exact.
"""
from __future__ import annotations

import numpy as np

from datagen import (AMERICA, ASIA, NATION_REGION, ORDER_DAYS,
                     day_keys, day_of_year, lines_per_order,
                     retail_price_cents, sparse_orderkeys)
from oracle import exact_segment_sum, grouped, lookup, scalar

SOURCE = ("Star Schema Benchmark rev. 3 (O'Neil, O'Neil, Chen, 2009), "
          "scale factor 1: lineorder, date, customer, supplier, part; "
          "query flights Q1.1, Q2.1, Q3.1, Q4.1")

SIZES = {"lineorder": 6_001_215, "date": 2_556, "customer": 30_000,
         "supplier": 2_000, "part": 200_000}
ORDERS = 1_500_000          # orders whose lines make up lineorder

#: row floors when a test scales the tables down
MIN_ROWS = {"lineorder": 1_000, "date": 2_556, "customer": 300,
            "supplier": 100, "part": 1_000}

REDUCED = [
    "strings are int64 codes at the source's cardinalities (region 5, "
    "nation 25, city 250, mfgr 5, category 25, brand1 1000, ...); dates "
    "are yyyymmdd integers",
    "join keys share one name on both sides: datekey (lo_orderdate, "
    "d_datekey), custkey, suppkey, partkey",
    "Q1.1 sums lo_revenue, not lo_extendedprice * lo_discount",
    "Q2.1 groups by p_brand1 only, not d_year, p_brand1",
    "Q3.1 groups by c_nation only, not c_nation, s_nation, d_year",
    "Q4.1 groups by c_nation only and sums lo_revenue, not "
    "lo_revenue - lo_supplycost",
    "no ORDER BY over the group result",
]

ASSUMED = [
    "date holds the 2,556 days from 1992-01-01; d_datekey is yyyymmdd",
    "an order's lines (1..7 each, 1,500,000 orders adjusted to 6,001,215 "
    "lines) share its order key (sparse, as TPC-H's), date, customer and "
    "priority; lo_ordtotalprice sums its lines with discount and tax",
    "names, addresses, phones, colours, types, containers are random codes "
    "at their cardinalities; lo_commitdate = orderdate + 30..90 days",
    "lo_orderdate uniform over 1992-01-01..1998-08-02 (TPC-H's order dates)",
    "lo_custkey uniform over all 30,000 customers",
    "lo_partkey uniform over 200,000 parts; lo_suppkey uniform over 2,000 "
    "suppliers",
    "lo_quantity uniform 1..50, lo_discount uniform 0..10",
    "lo_extendedprice = quantity * P_RETAILPRICE (TPC-H formula), cents",
    "lo_revenue = extendedprice * (100 - discount) / 100, cents, floored",
    "lo_supplycost = 6 * P_RETAILPRICE / 10, cents, floored",
    "c_nation, s_nation uniform over 25 nations; region by TPC-H's nation "
    "table; city = nation * 10 + uniform 0..9",
    "p_mfgr uniform 1..5; p_category = mfgr * 10 + uniform 1..5; "
    "p_brand1 = category * 100 + uniform 1..40",
    "join order as written below: the supplier join first where there is "
    "one, the date join last",
]

GUARANTEES = ("read-only analytic queries over a static snapshot; every "
              "answer is exact: integer sums of cents, compared for equality")

TEMPLATES = ("Q1.1", "Q2.1", "Q3.1", "Q4.1")

#: base-table columns each template reads, for the byte-work functions
_READS = {
    "Q1.1": {"lineorder": ("datekey", "lo_discount", "lo_quantity",
                           "lo_revenue"),
             "date": ("datekey", "d_year")},
    "Q2.1": {"lineorder": ("suppkey", "partkey", "datekey", "lo_revenue"),
             "supplier": ("suppkey", "s_region"),
             "part": ("partkey", "p_category", "p_brand1"),
             "date": ("datekey",)},
    "Q3.1": {"lineorder": ("suppkey", "custkey", "datekey", "lo_revenue"),
             "supplier": ("suppkey", "s_region"),
             "customer": ("custkey", "c_region", "c_nation"),
             "date": ("datekey", "d_year")},
    "Q4.1": {"lineorder": ("suppkey", "custkey", "partkey", "datekey",
                           "lo_revenue"),
             "supplier": ("suppkey", "s_region"),
             "customer": ("custkey", "c_region", "c_nation"),
             "part": ("partkey", "p_mfgr"),
             "date": ("datekey",)},
}
#: groups each answer has: 40 brands of one category, 5 nations of a region
_GROUPS = {"Q1.1": 1, "Q2.1": 40, "Q3.1": 5, "Q4.1": 5}
#: templates whose supplier join (domain 2,000) takes the Pallas radix probe
_PROBES_SUPPLIER = {"Q1.1": False, "Q2.1": True, "Q3.1": True, "Q4.1": True}


def sizes(scale: float = 1.0) -> dict:
    """Rows of each table; ``scale`` < 1 is for tests on the CPU only."""
    return {t: max(MIN_ROWS[t], round(n * scale)) for t, n in SIZES.items()}


def generate(seed: int, scale: float = 1.0) -> dict:
    """All tables as ``{table: {column: int64 array}}``, drawn from ``seed``.
    Every seed gives the same table sizes."""
    n = sizes(scale)
    rng = np.random.default_rng(seed)

    def codes(card, rows):
        return rng.integers(0, card, rows)

    days = np.arange(n["date"])
    datekey = day_keys(days)
    doy = day_of_year(days)
    dow = (days + 2) % 7                 # 1992-01-01 was a Wednesday
    month = datekey // 100 % 100
    date = {"datekey": datekey, "d_date": datekey, "d_dayofweek": dow,
            "d_month": month, "d_year": datekey // 10000,
            "d_yearmonthnum": datekey // 100, "d_yearmonth": datekey // 100,
            "d_daynuminweek": dow + 1, "d_daynuminmonth": datekey % 100,
            "d_daynuminyear": doy + 1, "d_monthnuminyear": month,
            "d_weeknuminyear": doy // 7 + 1,
            "d_sellingseason": (month % 12) // 3,
            "d_lastdayinweekfl": (dow == 6).astype(np.int64),
            "d_lastdayinmonthfl": (day_keys(days + 1) % 100 == 1
                                   ).astype(np.int64),
            "d_holidayfl": codes(2, n["date"]),
            "d_weekdayfl": (dow < 5).astype(np.int64)}

    def nation_dim(prefix, key, rows):
        nation = codes(25, rows)
        keys = np.arange(1, rows + 1, dtype=np.int64)
        return {key: keys, f"{prefix}_name": keys,
                f"{prefix}_address": codes(1 << 40, rows),
                f"{prefix}_city": nation * 10 + codes(10, rows),
                f"{prefix}_nation": nation,
                f"{prefix}_region": NATION_REGION[nation],
                f"{prefix}_phone": codes(10 ** 12, rows)}

    customer = {**nation_dim("c", "custkey", n["customer"]),
                "c_mktsegment": codes(5, n["customer"])}
    supplier = nation_dim("s", "suppkey", n["supplier"])
    mfgr = rng.integers(1, 6, n["part"])
    category = mfgr * 10 + rng.integers(1, 6, n["part"])
    partkeys = np.arange(1, n["part"] + 1, dtype=np.int64)
    part = {"partkey": partkeys, "p_name": codes(1 << 40, n["part"]),
            "p_mfgr": mfgr, "p_category": category,
            "p_brand1": category * 100 + rng.integers(1, 41, n["part"]),
            "p_color": codes(92, n["part"]), "p_type": codes(150, n["part"]),
            "p_size": rng.integers(1, 51, n["part"]),
            "p_container": codes(40, n["part"])}

    rows = n["lineorder"]
    n_orders = min(round(ORDERS * rows / SIZES["lineorder"]), rows)
    per_order = lines_per_order(rng, n_orders, rows)
    order_of = np.repeat(np.arange(n_orders), per_order)
    first = np.cumsum(per_order) - per_order
    order_day = rng.integers(0, min(ORDER_DAYS, n["date"]), n_orders)
    partkey = rng.integers(1, n["part"] + 1, rows)
    quantity = rng.integers(1, 51, rows)
    discount = rng.integers(0, 11, rows)
    tax = rng.integers(0, 9, rows)
    price = retail_price_cents(partkey)
    extended = quantity * price
    line_total = extended * (100 - discount) * (100 + tax) // 10000
    lineorder = {
        "lo_orderkey": np.repeat(sparse_orderkeys(n_orders), per_order),
        "lo_linenumber": np.arange(rows) - first[order_of] + 1,
        "custkey": np.repeat(rng.integers(1, n["customer"] + 1, n_orders),
                             per_order),
        "partkey": partkey,
        "suppkey": rng.integers(1, n["supplier"] + 1, rows),
        "datekey": np.repeat(datekey[order_day], per_order),
        "lo_orderpriority": np.repeat(codes(5, n_orders), per_order),
        "lo_shippriority": np.zeros(rows, np.int64),
        "lo_quantity": quantity,
        "lo_extendedprice": extended,
        "lo_ordtotalprice": np.repeat(np.add.reduceat(line_total, first),
                                      per_order),
        "lo_discount": discount,
        "lo_revenue": extended * (100 - discount) // 100,
        "lo_supplycost": 6 * price // 10,
        "lo_tax": tax,
        "lo_commitdate": day_keys(np.repeat(order_day, per_order)
                                  + rng.integers(30, 91, rows)),
        "lo_shipmode": codes(7, rows),
    }
    tables = {"lineorder": lineorder, "date": date, "customer": customer,
              "supplier": supplier, "part": part}
    return {t: {c: np.ascontiguousarray(v, dtype=np.int64)
                for c, v in cols.items()} for t, cols in tables.items()}


# ---------------------------------------------------------------------------
# Query templates through the engine
# ---------------------------------------------------------------------------

def build(name: str, session, col):
    """Template ``name`` as a ``Query`` of ``session``; ``col`` is the
    engine's column-expression constructor."""
    lo = session.table("lineorder")
    if name == "Q1.1":
        return (lo.join("date", on="datekey")
                .filter((col("b_d_year") == 1993) & (col("lo_discount") >= 1)
                        & (col("lo_discount") <= 3)
                        & (col("lo_quantity") < 25))
                .aggregate("lo_revenue", "sum"))
    if name == "Q2.1":
        return (lo.join("supplier", on="suppkey")
                .filter(col("b_s_region") == AMERICA)
                .join("part", on="partkey")
                .filter(col("b_p_category") == 12)
                .join("date", on="datekey")
                .group_by("b_p_brand1", {"lo_revenue": "sum"}))
    if name == "Q3.1":
        return (lo.join("supplier", on="suppkey")
                .filter(col("b_s_region") == ASIA)
                .join("customer", on="custkey")
                .filter(col("b_c_region") == ASIA)
                .join("date", on="datekey")
                .filter((col("b_d_year") >= 1992) & (col("b_d_year") <= 1997))
                .group_by("b_c_nation", {"lo_revenue": "sum"}))
    if name == "Q4.1":
        return (lo.join("supplier", on="suppkey")
                .filter(col("b_s_region") == AMERICA)
                .join("customer", on="custkey")
                .filter(col("b_c_region") == AMERICA)
                .join("part", on="partkey")
                .filter((col("b_p_mfgr") == 1) | (col("b_p_mfgr") == 2))
                .join("date", on="datekey")
                .group_by("b_c_nation", {"lo_revenue": "sum"}))
    raise KeyError(f"ssb_sf1 has no template {name!r}; it has {TEMPLATES}")


# ---------------------------------------------------------------------------
# Plain numpy reference
# ---------------------------------------------------------------------------

def references(names, tables: dict, segment_sum=exact_segment_sum) -> dict:
    """The answers of the templates ``names``, computed with numpy from
    ``tables``.  ``segment_sum(values, group_ids, n_groups)`` does the
    summation; the exact int64 one by default."""
    lo = tables["lineorder"]
    rows: dict = {}

    def dim(table, key):
        # each dimension is looked up once for all the templates
        if table not in rows:
            rows[table] = lookup(tables[table][key], lo[key])
        return rows[table]

    return {n: _reference(n, tables, dim, segment_sum) for n in names}


def _reference(name, tables, dim, segment_sum) -> dict:
    lo = tables["lineorder"]

    rev = lo["lo_revenue"]
    d_row, d_ok = dim("date", "datekey")
    if name == "Q1.1":
        year = tables["date"]["d_year"][d_row]
        m = (d_ok & (year == 1993) & (lo["lo_discount"] >= 1)
             & (lo["lo_discount"] <= 3) & (lo["lo_quantity"] < 25))
        return scalar(rev[m], segment_sum)
    s_row, s_ok = dim("supplier", "suppkey")
    if name == "Q2.1":
        p_row, p_ok = dim("part", "partkey")
        part = tables["part"]
        m = (s_ok & (tables["supplier"]["s_region"][s_row] == AMERICA)
             & p_ok & (part["p_category"][p_row] == 12) & d_ok)
        return grouped(part["p_brand1"][p_row][m], rev[m], segment_sum)
    c_row, c_ok = dim("customer", "custkey")
    cust = tables["customer"]
    if name == "Q3.1":
        year = tables["date"]["d_year"][d_row]
        m = (s_ok & (tables["supplier"]["s_region"][s_row] == ASIA)
             & c_ok & (cust["c_region"][c_row] == ASIA)
             & d_ok & (year >= 1992) & (year <= 1997))
        return grouped(cust["c_nation"][c_row][m], rev[m], segment_sum)
    if name == "Q4.1":
        p_row, p_ok = dim("part", "partkey")
        mfgr = tables["part"]["p_mfgr"][p_row]
        m = (s_ok & (tables["supplier"]["s_region"][s_row] == AMERICA)
             & c_ok & (cust["c_region"][c_row] == AMERICA)
             & p_ok & ((mfgr == 1) | (mfgr == 2)) & d_ok)
        return grouped(cust["c_nation"][c_row][m], rev[m], segment_sum)
    raise KeyError(f"ssb_sf1 has no template {name!r}; it has {TEMPLATES}")


# ---------------------------------------------------------------------------
# Byte work: what the template must move at the least
# ---------------------------------------------------------------------------

def query_bytes(name: str, table_rows: dict) -> int:
    """Bytes of every base-table column ``name`` reads, once, at logical
    width (8 bytes), plus its result (8 bytes a scalar, 16 a group)."""
    read = sum(8 * table_rows[t] * len(cols)
               for t, cols in _READS[name].items())
    return read + (8 if _GROUPS[name] == 1 else 16 * _GROUPS[name])


def kernel_bytes(name: str, table_rows: dict) -> int:
    """Least bytes of the template's Pallas radix-probe work: int32 probe
    keys and build keys read, one int32 match row per probe row written.
    The supplier join probes every lineorder row, since it comes first."""
    if not _PROBES_SUPPLIER[name]:
        return 0
    return 4 * table_rows["supplier"] + 8 * table_rows["lineorder"]
