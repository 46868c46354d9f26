"""Star Schema Benchmark, scale factor 1, at the spec's query shapes.

Source: P. O'Neil, E. O'Neil, X. Chen, "Star Schema Benchmark", revision 3
(2009), query flights 1 to 4, first query of each:

* Q1.1: ``sum(lo_extendedprice * lo_discount)`` over 1993's lines with
  discount 1..3 and quantity below 25;
* Q2.1: ``sum(lo_revenue)`` grouped by ``d_year, p_brand1`` for category
  MFGR#12 and suppliers in AMERICA;
* Q3.1: ``sum(lo_revenue)`` grouped by ``c_nation, s_nation, d_year`` for
  customers and suppliers in ASIA, 1992..1997;
* Q4.1: ``sum(lo_revenue - lo_supplycost)`` grouped by ``d_year,
  c_nation`` for customers and suppliers in AMERICA, MFGR#1 or MFGR#2.

The deployment, its data and its generator are ``ssb_sf1``'s, imported
from that file; this file holds the templates at the spec's group keys
and measures, their numpy references (which import nothing of the
engine), their byte work, and :func:`answer_of`, which turns a served
result into the references' form.  Answers are dictionaries: ``{"all":
sum}`` for Q1.1, ``{(key, ...): sum}`` for the grouped queries, keyed by
the tuple of group keys in the query's order.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

from oracle import exact_segment_sum, lookup, scalar


def _load_base():
    path = Path(__file__).with_name("ssb_sf1.py")
    spec = importlib.util.spec_from_file_location("config_ssb_sf1", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_BASE = _load_base()
AMERICA, ASIA = _BASE.AMERICA, _BASE.ASIA

SOURCE = _BASE.SOURCE
SIZES = _BASE.SIZES
MIN_ROWS = _BASE.MIN_ROWS
ASSUMED = _BASE.ASSUMED
GUARANTEES = _BASE.GUARANTEES
TEMPLATES = _BASE.TEMPLATES
sizes = _BASE.sizes
generate = _BASE.generate

REDUCED = [
    "strings are int64 codes at the source's cardinalities (region 5, "
    "nation 25, city 250, mfgr 5, category 25, brand1 1000, ...); dates "
    "are yyyymmdd integers: the engine's columns are numeric",
    "join keys share one name on both sides: datekey (lo_orderdate, "
    "d_datekey), custkey, suppkey, partkey: the engine joins same-named "
    "columns",
    "no ORDER BY over the group result: at most 280 groups, which the "
    "oracle compares as dictionaries, in any order",
]

#: base-table columns each template reads, for the byte-work functions
_READS = {
    "Q1.1": {"lineorder": ("datekey", "lo_discount", "lo_quantity",
                           "lo_extendedprice"),
             "date": ("datekey", "d_year")},
    "Q2.1": {"lineorder": ("suppkey", "partkey", "datekey", "lo_revenue"),
             "supplier": ("suppkey", "s_region"),
             "part": ("partkey", "p_category", "p_brand1"),
             "date": ("datekey", "d_year")},
    "Q3.1": {"lineorder": ("suppkey", "custkey", "datekey", "lo_revenue"),
             "supplier": ("suppkey", "s_region", "s_nation"),
             "customer": ("custkey", "c_region", "c_nation"),
             "date": ("datekey", "d_year")},
    "Q4.1": {"lineorder": ("suppkey", "custkey", "partkey", "datekey",
                           "lo_revenue", "lo_supplycost"),
             "supplier": ("suppkey", "s_region"),
             "customer": ("custkey", "c_region", "c_nation"),
             "part": ("partkey", "p_mfgr"),
             "date": ("datekey", "d_year")},
}
#: group keys of each answer, and the groups it has at most: 7 years x 40
#: brands of one category; 5 x 5 nations of a region x 6 years; 7 years x
#: 5 nations
_KEYS = {"Q1.1": 0, "Q2.1": 2, "Q3.1": 3, "Q4.1": 2}
_GROUPS = {"Q1.1": 1, "Q2.1": 280, "Q3.1": 150, "Q4.1": 35}


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
                .aggregate(("revenue", col("lo_extendedprice")
                            * col("lo_discount")), "sum"))
    if name == "Q2.1":
        return (lo.join("supplier", on="suppkey")
                .filter(col("b_s_region") == AMERICA)
                .join("part", on="partkey")
                .filter(col("b_p_category") == 12)
                .join("date", on="datekey")
                .group_by(("b_d_year", "b_p_brand1"), {"lo_revenue": "sum"}))
    if name == "Q3.1":
        return (lo.join("supplier", on="suppkey")
                .filter(col("b_s_region") == ASIA)
                .join("customer", on="custkey")
                .filter(col("b_c_region") == ASIA)
                .join("date", on="datekey")
                .filter((col("b_d_year") >= 1992) & (col("b_d_year") <= 1997))
                .group_by(("b_c_nation", "b_s_nation", "b_d_year"),
                          {"lo_revenue": "sum"}))
    if name == "Q4.1":
        return (lo.join("supplier", on="suppkey")
                .filter(col("b_s_region") == AMERICA)
                .join("customer", on="custkey")
                .filter(col("b_c_region") == AMERICA)
                .join("part", on="partkey")
                .filter((col("b_p_mfgr") == 1) | (col("b_p_mfgr") == 2))
                .join("date", on="datekey")
                .group_by(("b_d_year", "b_c_nation"),
                          {("profit", col("lo_revenue")
                            - col("lo_supplycost")): "sum"}))
    raise KeyError(f"ssb_sf1_spec has no template {name!r}; it has "
                   f"{TEMPLATES}")


def answer_of(result) -> dict:
    """A served ``QueryResult`` as its reference's form: ``{"all": s}`` for
    a scalar; for a relation of key columns and one value column, ``{key:
    s}`` with one key, ``{(key, ...): s}`` with several."""
    if result.scalar is not None:
        return {"all": float(result.scalar)}
    rel = result.relation
    *keys, value = rel.names
    cols = [np.asarray(rel[k]).tolist() for k in keys]
    vals = np.asarray(rel[value]).tolist()
    if len(keys) == 1:
        return {int(k): float(v) for k, v in zip(cols[0], vals)}
    return {tuple(int(x) for x in ks): float(v)
            for ks, v in zip(zip(*cols), vals)}


# ---------------------------------------------------------------------------
# Plain numpy reference
# ---------------------------------------------------------------------------

def grouped(keys, values: np.ndarray, segment_sum) -> dict:
    """``{(k1, k2, ...): sum of values}`` over the distinct key tuples;
    ``keys`` is one array per group key."""
    uniq, gid = np.unique(np.stack(keys, axis=1), axis=0,
                          return_inverse=True)
    sums = segment_sum(values, gid.reshape(-1), len(uniq))
    return {tuple(int(x) for x in u): float(s)
            for u, s in zip(uniq.tolist(), np.asarray(sums).tolist())}


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
    year = tables["date"]["d_year"][d_row]
    if name == "Q1.1":
        m = (d_ok & (year == 1993) & (lo["lo_discount"] >= 1)
             & (lo["lo_discount"] <= 3) & (lo["lo_quantity"] < 25))
        return scalar((lo["lo_extendedprice"] * lo["lo_discount"])[m],
                      segment_sum)
    s_row, s_ok = dim("supplier", "suppkey")
    supp = tables["supplier"]
    if name == "Q2.1":
        p_row, p_ok = dim("part", "partkey")
        part = tables["part"]
        m = (s_ok & (supp["s_region"][s_row] == AMERICA)
             & p_ok & (part["p_category"][p_row] == 12) & d_ok)
        return grouped([year[m], part["p_brand1"][p_row][m]], rev[m],
                       segment_sum)
    c_row, c_ok = dim("customer", "custkey")
    cust = tables["customer"]
    if name == "Q3.1":
        m = (s_ok & (supp["s_region"][s_row] == ASIA)
             & c_ok & (cust["c_region"][c_row] == ASIA)
             & d_ok & (year >= 1992) & (year <= 1997))
        return grouped([cust["c_nation"][c_row][m],
                        supp["s_nation"][s_row][m], year[m]], rev[m],
                       segment_sum)
    if name == "Q4.1":
        p_row, p_ok = dim("part", "partkey")
        mfgr = tables["part"]["p_mfgr"][p_row]
        m = (s_ok & (supp["s_region"][s_row] == AMERICA)
             & c_ok & (cust["c_region"][c_row] == AMERICA)
             & p_ok & ((mfgr == 1) | (mfgr == 2)) & d_ok)
        return grouped([year[m], cust["c_nation"][c_row][m]],
                       (rev - lo["lo_supplycost"])[m], segment_sum)
    raise KeyError(f"ssb_sf1_spec has no template {name!r}; it has "
                   f"{TEMPLATES}")


# ---------------------------------------------------------------------------
# Byte work: what the template must move at the least
# ---------------------------------------------------------------------------

def query_bytes(name: str, table_rows: dict) -> int:
    """Bytes of every base-table column ``name`` reads, once, at logical
    width (8 bytes), plus its result: 8 bytes a scalar, 8 a key and 8 the
    sum for each group."""
    read = sum(8 * table_rows[t] * len(cols)
               for t, cols in _READS[name].items())
    return read + 8 * (_KEYS[name] + 1) * _GROUPS[name]


def kernel_bytes(name: str, table_rows: dict) -> int:
    """Least bytes of the template's Pallas radix-probe work, as
    ``ssb_sf1``'s: the same joins take the kernel."""
    return _BASE.kernel_bytes(name, table_rows)
