#!/usr/bin/env python3
"""Smoke run of the relational serving path on a TPU, checked bit for bit.

    python chip_smoke.py              # phases A-C on one chip
    python chip_smoke.py --chips 4    # only the sharded Phase B fragment,
                                      # on four chips against one

Everything runs in this one process, through the user entry points
(``Session``, ``QueryServer``), on data generated from ``--seed``:

* **A, star join** — the fig10 schema at Star Schema Benchmark scale
  factor 1: ``orders`` has 6,001,215 rows (SSB lineorder), ``users``
  30,000 (SSB customer: the dense jnp join core) and ``parts`` 2,000 (SSB
  supplier: a code domain the Pallas radix probe takes).  The fig10 query
  must run as chained ``fused_pipeline`` fragments, and a revenue sum and
  its ``group_by(("b_region", "pid"))`` variant, which read the parts'
  price through the radix-probe join, on the tensor path, with no linear
  operator.
* **B, sparse-key join** — the fig15 shape with 2^23 rows per side and
  sparse int64 keys: the sorted int64 join core.
* **C, serving** — a governed ``QueryServer`` over the Phase A tables under
  ``policy="tensor"`` and ``policy="auto"`` at concurrency 4: no failed
  query, no over-budget grant.

Every result is compared with a numpy oracle for equality.  Each phase
prints one JSON line: cold wall time (compiles included), warm wall time,
the device's peak bytes in use so far and the Pallas kernels traced in the
phase.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
a failed check, or a host whose JAX finds no TPU, exits non-zero without it.
These are smoke timings of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

SF1_ORDERS = 6_001_215   # SSB lineorder rows at scale factor 1
SF1_USERS = 30_000       # SSB customer rows at scale factor 1
SF1_PARTS = 2_000        # SSB supplier rows at scale factor 1
SPARSE_ROWS = 1 << 23    # Phase B rows per side
MB = 1 << 20


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _kernels_since(before):
    from repro.core.tensor_engine import kernels_traced

    now = kernels_traced()
    return sorted(k for k, v in now.items() if v > before.get(k, 0))


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Data and oracles
# ---------------------------------------------------------------------------

def star_tables(rng, scale: float):
    """The fig10 schema; ``scale`` cuts the fact table only."""
    import numpy as np

    from repro.core import Relation

    n = max(1, round(SF1_ORDERS * scale))
    orders = Relation({
        "uid": rng.integers(0, SF1_USERS, n).astype(np.int64),
        "pid": rng.integers(0, SF1_PARTS, n).astype(np.int64),
        "w": rng.integers(-50, 50, n).astype(np.int64),
        "payload": rng.integers(0, 1 << 40, n).astype(np.int64),
    })
    users = Relation({
        "uid": np.arange(SF1_USERS, dtype=np.int64),
        "region": rng.integers(0, 4, SF1_USERS).astype(np.int64),
    })
    parts = Relation({
        "pid": np.arange(SF1_PARTS, dtype=np.int64),
        "price": rng.integers(1, 9, SF1_PARTS).astype(np.int64),
    })
    return {"orders": orders, "users": users, "parts": parts}


def star_oracle(tables):
    """Numpy answers of the star queries: every order joins one user and
    one part (keys are dense ids), so the join is the row mask."""
    import numpy as np

    o = tables["orders"]
    region = np.asarray(tables["users"]["region"])[o["uid"]]
    price = np.asarray(tables["parts"]["price"])[o["pid"]]
    mask = (o["w"] > 0) & (region <= 2)
    w, pid, wp = o["w"][mask], o["pid"][mask], (o["w"] * price)[mask]
    # (region, pid) packed as region * parts + pid, in lexicographic order
    coord = region[mask] * SF1_PARTS + pid
    groups = np.unique(coord)
    sums = np.bincount(coord, weights=wp.astype(np.float64),
                       minlength=4 * SF1_PARTS)[groups]
    return {"sum": float(w.sum()), "count": float(mask.sum()),
            "revenue": float(wp.sum()),
            "group_region": groups // SF1_PARTS,
            "group_pid": groups % SF1_PARTS, "group_sum": sums}


def star_queries(sess):
    from repro.core import col

    star = (sess.table("orders")
            .join(sess.table("users"), on="uid")
            .join(sess.table("parts"), on="pid")
            .filter((col("w") > 0) & (col("b_region") <= 2)))
    # revenue and the group's measure read the parts' price, a build-side
    # column of the join the Pallas radix probe takes on the chip
    revenue = ("wp", col("w") * col("b_price"))
    return {"sum": star.sort("uid").aggregate("w", "sum"),
            "count": star.aggregate("w", "count"),
            "revenue": star.aggregate(revenue, "sum"),
            "group": star.group_by(("b_region", "pid"), {revenue: "sum"})}


def sparse_tables(rng, scale: float):
    """fig15's shape: unique build keys over a sparse int64 domain, probes
    drawn from them; payloads bounded so the int64 sum is exact in f64."""
    import numpy as np

    from repro.core import Relation

    n = max(1, round(SPARSE_ROWS * scale))
    bk = rng.permutation(n).astype(np.int64) * 1_000_003 + 17
    build = Relation({"k": bk,
                      "v": rng.integers(0, 1 << 30, n).astype(np.int64)})
    probe = Relation({"k": bk[rng.integers(0, n, n)],
                      "w": rng.integers(0, 1000, n).astype(np.int64)})
    return {"build": build, "probe": probe}


def sparse_oracle(tables) -> float:
    import numpy as np

    bk, bv = tables["build"]["k"], tables["build"]["v"]
    pk, pw = tables["probe"]["k"], tables["probe"]["w"]
    order = np.argsort(bk)
    hit = order[np.searchsorted(bk[order], pk)]
    return float(bv[hit[pw < 500]].sum())


def sparse_query(sess):
    from repro.core import col

    return (sess.table("probe").join("build", on="k")
            .filter(col("w") < 500).aggregate("b_v", "sum"))


def _session(tables, **kw):
    from repro.core import Session

    sess = Session(work_mem=64 * MB, **kw)
    for name, rel in tables.items():
        sess.register(name, rel)
    return sess


def _device_only(res, what: str) -> None:
    check(all(m.path != "linear" for m in res.metrics),
          f"{what}: a linear operator ran: {[m.op for m in res.metrics]}")
    check(all(d.path == "tensor" for d in res.decisions),
          f"{what}: decisions {[d.path for d in res.decisions]}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_a(star, oracle) -> dict:
    import numpy as np

    from repro.core.tensor_engine import kernels_traced

    before = kernels_traced()
    sess = _session(star, policy="tensor")
    qs = star_queries(sess)

    def run():
        return {k: qs[k].collect() for k in ("sum", "revenue", "group")}

    res, cold = _timed(run)
    _, warm = _timed(run)
    s = res["sum"]
    check(s.scalar == oracle["sum"],
          f"A: star-join sum {s.scalar!r} != oracle {oracle['sum']!r}")
    frags = [m.op for m in s.metrics].count("fused_pipeline")
    check(frags >= 2, f"A: star join ran {frags} fused fragments, not >= 2")
    _device_only(s, "A star join")
    r = res["revenue"]
    check(r.scalar == oracle["revenue"],
          f"A: star-join revenue {r.scalar!r} != oracle "
          f"{oracle['revenue']!r}")
    _device_only(r, "A revenue")
    g = res["group"]
    rel = g.relation
    order = np.lexsort((rel["pid"], rel["b_region"]))
    check(np.array_equal(rel["b_region"][order], oracle["group_region"])
          and np.array_equal(rel["pid"][order], oracle["group_pid"])
          and np.array_equal(rel["sum_wp"][order], oracle["group_sum"]),
          "A: group_by(b_region, pid) sums differ from the oracle")
    check(any(m.op == "group_aggregate" and m.path == "tensor"
              for m in g.metrics), "A: the group-by left the tensor path")
    _device_only(g, "A group-by")
    return {"phase": "A", "cold_s": cold, "warm_s": warm,
            "fused_fragments": frags, "groups": len(rel),
            "kernels": _kernels_since(before)}


def phase_b(sparse, oracle: float) -> dict:
    from repro.core.tensor_engine import kernels_traced

    before = kernels_traced()
    q = sparse_query(_session(sparse, policy="tensor"))
    res, cold = _timed(q.collect)
    again, warm = _timed(q.collect)
    for r in (res, again):
        check(r.scalar == oracle,
              f"B: sparse join sum {r.scalar!r} != oracle {oracle!r}")
        _device_only(r, "B sparse join")
    return {"phase": "B", "cold_s": cold, "warm_s": warm,
            "kernels": _kernels_since(before)}


def phase_c(star, oracle) -> dict:
    from repro.core import QueryServer
    from repro.core.tensor_engine import kernels_traced

    before = kernels_traced()
    out = {"phase": "C"}
    for policy in ("tensor", "auto"):
        server = QueryServer(dict(star), total_mem=2048 * MB,
                             work_mem=1024 * MB, policy=policy)
        qs = star_queries(server.session)
        workload = [qs["sum"], qs["count"]]
        want = [oracle["sum"], oracle["count"]]
        rep, wall = _timed(lambda: server.serve(
            workload, concurrency=4, queries_per_worker=3, warmup=1))
        check(not rep.failed, f"C[{policy}]: failed queries "
              f"{[(f.error, f.message) for f in rep.failed]}")
        check(rep.governor.over_budget_events == 0,
              f"C[{policy}]: {rep.governor.over_budget_events} "
              f"over-budget grants")
        check(len(rep.queries) == 12, f"C[{policy}]: served "
              f"{len(rep.queries)} of 12 queries")
        bad = [(q.workload_idx, q.scalar) for q in rep.queries
               if q.scalar != want[q.workload_idx]]
        check(not bad, f"C[{policy}]: scalars differ from the oracle: {bad}")
        out[policy] = {"cold_s": wall, "warm_s": rep.wall_s,
                       "p50_s": rep.latency.p50, "p99_s": rep.latency.p99,
                       "paths": sorted({q.paths for q in rep.queries})}
    out["kernels"] = _kernels_since(before)
    return out


def phase_sharded(sparse, oracle: float, shards: int) -> dict:
    """The Phase B fragment over ``shards`` devices and over one."""
    out = {"phase": f"B-sharded{shards}"}
    for label, kw in (("one_chip", {}), ("sharded", {"max_shards": shards})):
        q = sparse_query(_session(sparse, policy="tensor", **kw))
        res, cold = _timed(q.collect)
        again, warm = _timed(q.collect)
        for r in (res, again):
            check(r.scalar == oracle, f"{label}: sum {r.scalar!r} != "
                  f"oracle {oracle!r}")
            _device_only(r, label)
        devices = [m.devices for m in again.metrics
                   if m.op == "fused_pipeline"]
        want = shards if kw else 1
        check(devices == [want], f"{label}: fused fragment ran on "
              f"{devices} device(s), expected [{want}]")
        out[label] = {"cold_s": cold, "warm_s": warm, "devices": devices[0]}
    return out


def run(chips: int = 1, scale: float = 1.0, seed: int = 0):
    """Run the phases for ``chips`` (1: A-C; 4: the sharded fragment only)
    and return their records; raises :class:`CheckFailed` on a failed
    check."""
    import numpy as np

    rng = np.random.default_rng(seed)
    records = []
    if chips == 1:
        star = star_tables(rng, scale)
        star_ref = star_oracle(star)
        sparse = sparse_tables(rng, scale)
        sparse_ref = sparse_oracle(sparse)
        phases = [lambda: phase_a(star, star_ref),
                  lambda: phase_b(sparse, sparse_ref),
                  lambda: phase_c(star, star_ref)]
    else:
        sparse = sparse_tables(rng, scale)
        sparse_ref = sparse_oracle(sparse)
        phases = [lambda: phase_sharded(sparse, sparse_ref, chips)]
    for phase in phases:
        rec = phase()
        rec["peak_bytes_in_use"] = _peak_bytes()
        emit(**rec)
        records.append(rec)
    return records


def _cache_events():
    """Counts of JAX persistent-cache hits and misses in this process."""
    import jax

    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-C on one chip; 4: only the sharded "
                         "Phase B fragment, against the same on one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit(device=device, compile_cache=cache_dir)
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {device['platform']!r}, "
              f"not a TPU", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    cache = _cache_events()
    try:
        run(chips=args.chips, seed=args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    emit(compile_cache_hits=cache["hits"],
         compile_cache_misses=cache["misses"])
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
