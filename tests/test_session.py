"""Session/Query front-end, rewrite planner, and the logical-IR shim.

Covers the PR acceptance contract: a 3-table star join built with the
fluent API executes as chained fused fragments with filter pushdown,
transfers only referenced columns, and matches the legacy dataclass tree
bit-for-bit; legacy trees still execute unchanged through the lowering
shim.
"""
import numpy as np
import pytest

from repro.core import (Aggregate, Executor, Filter, GroupBy, Join, Project,
                        QueryResult, Relation, Scan, Session, Sort, col,
                        from_physical, plan_program)


def _star_tables(n_orders=20_000, n_users=500, n_parts=200, seed=0):
    """orders(uid, pid, w, fat) ⋈ users(uid, region, fat) ⋈ parts(pid,
    price, fat); the `fat` columns are never referenced by the queries."""
    rng = np.random.default_rng(seed)
    orders = Relation({
        "uid": rng.integers(0, n_users, n_orders).astype(np.int64),
        "pid": rng.integers(0, n_parts, n_orders).astype(np.int64),
        "w": rng.integers(-50, 50, n_orders).astype(np.int64),
        "fat": rng.integers(0, 9, n_orders).astype(np.int64),
    })
    users = Relation({
        "uid": np.arange(n_users, dtype=np.int64),
        "region": rng.integers(0, 4, n_users).astype(np.int64),
        "fat": rng.integers(0, 9, n_users).astype(np.int64),
    })
    parts = Relation({
        "pid": np.arange(n_parts, dtype=np.int64),
        "price": rng.integers(1, 9, n_parts).astype(np.int64),
        "fat": rng.integers(0, 9, n_parts).astype(np.int64),
    })
    return orders, users, parts


def _star_session(policy="tensor", **tables):
    sess = Session(work_mem=1 << 20, policy=policy)
    for name, rel in tables.items():
        sess.register(name, rel)
    return sess


def _star_query(sess):
    return (sess.table("orders")
            .join(sess.table("users"), on="uid")
            .join(sess.table("parts"), on="pid")
            .filter((col("w") > 0) & (col("b_region") <= 2))
            .sort("uid")
            .aggregate("w", "sum"))


def _legacy_star_plan(orders, users, parts):
    """The same query as a seed-style physical dataclass tree."""
    return Aggregate(
        Sort(Filter(Join(Scan(parts),
                         Join(Scan(users), Scan(orders), "uid"), "pid"),
                    lambda r: (r["w"] > 0) & (r["b_region"] <= 2)),
             ["uid"]), "w", "sum")


# ---------------------------------------------------------------------------
# Acceptance: chained fused fragments + pushdown + pruning + parity
# ---------------------------------------------------------------------------

def test_star_join_acceptance():
    orders, users, parts = _star_tables()
    sess = _star_session(orders=orders, users=users, parts=parts)
    q = _star_query(sess)

    # pushdown is visible in the plan: the filter runs in stage 0 (below
    # the top join), not at the root
    lines = q.explain().splitlines()
    assert len(lines) == 2
    assert "filter" in lines[0] and "filter" not in lines[1]

    res = q.collect()
    # ≥ 2 chained fused fragments
    assert [m.op for m in res.metrics] == ["fused_pipeline",
                                           "fused_pipeline"]

    # bit-for-bit vs the legacy dataclass tree, on BOTH legacy paths
    legacy = _legacy_star_plan(orders, users, parts)
    for policy in ("linear", "tensor"):
        ref = Executor(work_mem=1 << 20, policy=policy).execute(legacy)
        assert ref.scalar == res.scalar

    # projection pruning: the never-referenced fat columns stay on host.
    # An unpruned cold run of the same query over fresh (cache-cold)
    # relations pays for them; the pruned run's H2D must be smaller by at
    # least the fat columns' padded footprint.
    o2, u2, p2 = _star_tables()
    # price the never-referenced fat columns as the engine itself would
    # upload them (packed codes under compressed layouts, logical width
    # otherwise) — measured BEFORE the unpruned run so nothing is resident
    from repro.core.table_cache import pending_upload_bytes
    fat_padded = sum(
        pending_upload_bytes(r.select(["fat"]),
                             1 << int(np.ceil(np.log2(len(r)))))
        for r in (o2, u2, p2))
    assert fat_padded > 0
    res_raw = _star_query(
        _star_session(orders=o2, users=u2, parts=p2)).collect(rewrite=False)
    assert res_raw.scalar == res.scalar
    assert res.total_h2d_bytes <= res_raw.total_h2d_bytes - fat_padded


def test_star_join_warm_queries_reupload_no_base_tables():
    orders, users, parts = _star_tables(seed=3)
    sess = _star_session(orders=orders, users=users, parts=parts)
    q = _star_query(sess)
    cold = q.collect()
    warm1 = q.collect()
    warm2 = q.collect()
    assert warm1.scalar == cold.scalar == warm2.scalar
    # warm queries still upload the per-query intermediate, but no base
    # table columns: steady state is strictly cheaper and stable
    assert warm1.total_h2d_bytes < cold.total_h2d_bytes
    assert warm2.total_h2d_bytes == warm1.total_h2d_bytes
    from repro.core.table_cache import pending_upload_bytes
    referenced = {"orders": ["uid", "pid", "w"], "users": ["uid", "region"],
                  "parts": ["pid"]}
    for name, rel in (("orders", orders), ("users", users),
                      ("parts", parts)):
        # every column the query references is device-resident at its padded
        # bucket (the pruned sub-relations share these caches); columns the
        # query never reads (fat; parts.price) were never uploaded
        bucket = 1 << int(np.ceil(np.log2(len(rel))))
        assert pending_upload_bytes(rel.select(referenced[name]),
                                    bucket) == 0
        assert pending_upload_bytes(rel.select(["fat"]), bucket) > 0


@pytest.mark.parametrize("policy", ["linear", "tensor", "auto"])
def test_star_join_policies_agree(policy):
    orders, users, parts = _star_tables(seed=5, n_orders=4000)
    sess = _star_session(policy=policy, orders=orders, users=users,
                         parts=parts)
    got = _star_query(sess).collect()
    ref = Executor(work_mem=1 << 30, policy="linear").execute(
        _legacy_star_plan(orders, users, parts))
    assert got.scalar == ref.scalar


# ---------------------------------------------------------------------------
# Legacy lowering shim: dataclass trees execute unchanged through the IR
# ---------------------------------------------------------------------------

LEGACY_SHAPES = {
    "sort_join": lambda b, p: Sort(Join(Scan(b), Scan(p), "k"), ["k", "w"]),
    "agg_sort_filter_join": lambda b, p: Aggregate(
        Sort(Filter(Join(Scan(b), Scan(p), "k"), lambda r: r["w"] % 2 == 0),
             ["k", "w"]), "w", "sum"),
    "group_by_filter_join": lambda b, p: GroupBy(
        Filter(Join(Scan(b), Scan(p), "k"), lambda r: r["w"] > 0),
        "k", {"w": "sum", "b_v": "min"}),
    "project_join": lambda b, p: Project(
        Join(Scan(b), Scan(p), "k"), ["k", "b_v"]),
    "single_table_chain": lambda b, p: Sort(
        Filter(Scan(p), lambda r: r["w"] > 10), ["w"]),
}


@pytest.mark.parametrize("shape", sorted(LEGACY_SHAPES))
def test_legacy_trees_execute_through_shim(shape):
    rng = np.random.default_rng(11)
    build = Relation({"k": rng.permutation(1500).astype(np.int64),
                      "v": rng.integers(-9, 9, 1500).astype(np.int64)})
    probe = Relation({"k": rng.integers(0, 1500, 2000).astype(np.int64),
                      "w": rng.integers(-99, 99, 2000).astype(np.int64)})
    plan = LEGACY_SHAPES[shape](build, probe)
    direct = Executor(work_mem=1 << 30, policy="linear").execute(plan)

    sess = Session(work_mem=1 << 30, policy="tensor")
    via_shim = sess.execute(LEGACY_SHAPES[shape](build, probe))
    assert isinstance(via_shim, QueryResult)
    if direct.relation is None:
        assert via_shim.scalar == direct.scalar
    else:
        assert via_shim.relation.sort_canonical().equals(
            direct.relation.sort_canonical())
    # the executor itself also accepts logical IR directly
    lowered = from_physical(LEGACY_SHAPES[shape](build, probe))
    via_exec = Executor(work_mem=1 << 30, policy="linear").execute(lowered)
    if direct.relation is None:
        assert via_exec.scalar == direct.scalar
    else:
        assert via_exec.relation.sort_canonical().equals(
            direct.relation.sort_canonical())


# ---------------------------------------------------------------------------
# Multi-key joins (key packing)
# ---------------------------------------------------------------------------

def _twokey_tables(seed, n_left=3000, n_right=400, wide=False):
    """wide=True draws both key columns from sparse pools spanning ~2^40,
    so the combined range product overflows int64 range packing and the
    planner must take the per-column factorization fallback."""
    rng = np.random.default_rng(seed)
    if wide:
        pool_a = rng.integers(0, 1 << 40, 16)
        pool_b = rng.integers(-(1 << 40), 1 << 40, 8)
        a = lambda n: rng.choice(pool_a, n)
        b = lambda n: rng.choice(pool_b, n)
    else:
        a = lambda n: rng.integers(0, 20, n)
        b = lambda n: rng.integers(-10, 10, n)
    left = Relation({"a": a(n_left).astype(np.int64),
                     "b": b(n_left).astype(np.int64),
                     "w": rng.integers(0, 100, n_left).astype(np.int64)})
    right = Relation({"a": a(n_right).astype(np.int64),
                      "b": b(n_right).astype(np.int64),
                      "v": rng.integers(0, 100, n_right).astype(np.int64)})
    return left, right


def _twokey_reference(left, right):
    matches = {}
    for i, ab in enumerate(zip(right["a"].tolist(), right["b"].tolist())):
        matches.setdefault(ab, []).append(i)
    rows = [(j, i)
            for j, ab in enumerate(zip(left["a"].tolist(),
                                       left["b"].tolist()))
            for i in matches.get(ab, [])]
    return Relation({
        "a": left["a"][[j for j, _ in rows]],
        "b": left["b"][[j for j, _ in rows]],
        "w": left["w"][[j for j, _ in rows]],
        "b_v": right["v"][[i for _, i in rows]],
    }) if rows else None


@pytest.mark.parametrize("policy", ["linear", "tensor"])
@pytest.mark.parametrize("wide", [False, True],
                         ids=["range_packed", "factorized"])
def test_multikey_join_matches_reference(policy, wide):
    left, right = _twokey_tables(13, wide=wide)
    sess = Session(work_mem=1 << 30, policy=policy)
    sess.register("L", left).register("R", right)
    out = (sess.table("L").join(sess.table("R"), on=["a", "b"])
           .sort("a", "b").to_relation())
    want = _twokey_reference(left, right)
    assert want is not None
    assert set(out.names) == {"a", "b", "w", "b_v"}  # no __pack__ leak
    assert out.sort_canonical().equals(want.sort_canonical())


@pytest.mark.parametrize("wide", [False, True],
                         ids=["range_packed", "factorized"])
def test_multikey_packed_column_cached_across_queries(wide):
    """Packed key coordinates (range-compressed AND factorized) are
    content-cached on the base relations: repeated queries reuse the same
    array objects (and so their device uploads)."""
    left, right = _twokey_tables(17, wide=wide)
    sess = Session(work_mem=1 << 30, policy="tensor")
    sess.register("L", left).register("R", right)
    q = (sess.table("L").join(sess.table("R"), on=["a", "b"])
         .group_by("a", {"w": "sum"}))
    first = q.collect()
    second = q.collect()
    assert first.relation.sort_canonical().equals(
        second.relation.sort_canonical())
    assert second.total_h2d_bytes == 0  # everything resident, pack included
    # reference parity
    want = _twokey_reference(left, right)
    ref = {}
    for a, w in zip(want["a"].tolist(), want["w"].tolist()):
        ref[a] = ref.get(a, 0) + w
    got = dict(zip(first.relation["a"].tolist(),
                   first.relation["sum_w"].tolist()))
    assert got == {int(k): float(v) for k, v in ref.items()}


def test_multikey_join_reserved_pack_name_raises():
    """A user column literally named like the synthetic pack coordinate must
    refuse loudly, not be silently overwritten (regression)."""
    from repro.core.planner import PACK_COL

    left, right = _twokey_tables(61)
    tainted = Relation(dict(left.columns, **{PACK_COL: left["w"]}))
    sess = Session(work_mem=1 << 30, policy="linear")
    sess.register("L", tainted).register("R", right)
    with pytest.raises(ValueError, match="reserved"):
        sess.table("L").join(sess.table("R"), on=["a", "b"]).collect()


def test_factorized_pack_cache_is_bounded():
    """One build table factorize-joined against a stream of distinct probe
    relations must not grow its pack cache without bound (regression)."""
    left, _ = _twokey_tables(67, wide=True)
    sess = Session(work_mem=1 << 30, policy="linear")
    sess.register("L", left)
    rng = np.random.default_rng(67)
    for i in range(12):
        probe = Relation({"a": rng.choice(left["a"], 50),
                          "b": rng.choice(left["b"], 50),
                          "v": rng.integers(0, 9, 50).astype(np.int64)})
        (sess.from_relation(probe).join(sess.table("L"), on=["a", "b"])
         .aggregate("v", "count")).collect()
    entries = [k for k in left.__dict__.get("_packed_cols", {})
               if k[0] == "factorized"]
    assert 0 < len(entries) <= 8


# ---------------------------------------------------------------------------
# Rewrites: pushdown and pruning mechanics
# ---------------------------------------------------------------------------

def test_filter_pushdown_splits_conjunctions_across_stages():
    orders, users, parts = _star_tables(n_orders=2000, seed=19)
    sess = _star_session(orders=orders, users=users, parts=parts)
    q = (sess.table("orders")
         .join(sess.table("users"), on="uid")
         .join(sess.table("parts"), on="pid")
         .filter((col("w") > 0) & (col("b_price") > 3))
         .aggregate("w", "count"))
    lines = q.explain().splitlines()
    # w-conjunct sinks to stage 0 (users⋈orders); the b_price conjunct
    # references the TOP join's build side and stays at stage 1
    assert "filter" in lines[0] and "filter" in lines[1]
    res = q.collect()
    ref = Executor(work_mem=1 << 30, policy="linear").execute(
        Aggregate(Filter(Join(Scan(parts),
                              Join(Scan(users), Scan(orders), "uid"), "pid"),
                         lambda r: (r["w"] > 0) & (r["b_price"] > 3)),
                  "w", "count"))
    assert res.scalar == ref.scalar


def test_pushdown_respects_build_side_column_shadowing():
    """A conjunct mixing probe refs with a b_-name served by the TOP join's
    build side must NOT descend into the probe subtree, where the same
    b_-name is a different column (regression: wrong results when the outer
    build shadows an inner join's b_ output)."""
    rng = np.random.default_rng(59)
    n = 2000
    orders = Relation({"uid": rng.integers(0, 50, n).astype(np.int64),
                       "pid": rng.integers(0, 30, n).astype(np.int64),
                       "w": rng.integers(-9, 9, n).astype(np.int64)})
    # BOTH users and parts carry a `region` column: after the second join,
    # b_region means parts.region (build wins), not users.region
    users = Relation({"uid": np.arange(50, dtype=np.int64),
                      "region": rng.integers(0, 3, 50).astype(np.int64)})
    parts = Relation({"pid": np.arange(30, dtype=np.int64),
                      "region": rng.integers(3, 9, 30).astype(np.int64)})
    sess = _star_session(orders=orders, users=users, parts=parts)
    q = (sess.table("orders")
         .join(sess.table("users"), on="uid")
         .join(sess.table("parts"), on="pid")
         .filter((col("w") + col("b_region")) > 6)  # mixed: w + parts.region
         .aggregate("w", "count"))
    ref = Executor(work_mem=1 << 30, policy="linear").execute(
        Aggregate(Filter(Join(Scan(parts),
                              Join(Scan(users), Scan(orders), "uid"), "pid"),
                         lambda r: (r["w"] + r["b_region"]) > 6),
                  "w", "count"))
    assert q.collect().scalar == ref.scalar
    # and a pure-b_ conjunct on the shadowed name stays at the top join too
    q2 = (sess.table("orders")
          .join(sess.table("users"), on="uid")
          .join(sess.table("parts"), on="pid")
          .filter(col("b_region") >= 5)
          .aggregate("w", "count"))
    ref2 = Executor(work_mem=1 << 30, policy="linear").execute(
        Aggregate(Filter(Join(Scan(parts),
                              Join(Scan(users), Scan(orders), "uid"), "pid"),
                         lambda r: r["b_region"] >= 5), "w", "count"))
    assert q2.collect().scalar == ref2.scalar


def test_mixed_predicate_merge_keeps_compile_cache_stable():
    """A fragment whose filters mix an opaque callable with an Expr must not
    re-trace per collect(): the merged predicate's cache key composes the
    per-part keys (regression: fresh closure per plan → one new compiled
    program per query)."""
    from repro.core import pipeline_cache_clear, pipeline_cache_info

    rng = np.random.default_rng(61)
    build = Relation({"k": rng.permutation(512).astype(np.int64),
                      "v": rng.integers(0, 9, 512).astype(np.int64)})
    probe = Relation({"k": rng.integers(0, 512, 512).astype(np.int64),
                      "w": rng.integers(-9, 9, 512).astype(np.int64)})
    sess = Session(work_mem=1 << 30, policy="tensor")
    sess.register("B", build).register("P", probe)
    pipeline_cache_clear()
    results = set()
    for _ in range(3):
        q = (sess.table("P").join(sess.table("B"), on="k")
             .filter(lambda r: r["w"] > 0)      # opaque part
             .filter(col("w") < 5)              # Expr part
             .sort("k")
             .aggregate("w", "sum"))
        results.add(q.collect().scalar)
    info = pipeline_cache_info()
    assert info["misses"] == 1 and info["programs"] == 1, info
    assert len(results) == 1


def test_opaque_callable_filter_stays_put_and_correct():
    orders, users, parts = _star_tables(n_orders=2000, seed=23)
    sess = _star_session(orders=orders, users=users, parts=parts)
    q = (sess.table("orders")
         .join(sess.table("users"), on="uid")
         .filter(lambda r: r["w"] > 0)  # opaque: no pushdown, still correct
         .aggregate("w", "sum"))
    ref = Executor(work_mem=1 << 30, policy="linear").execute(
        Aggregate(Filter(Join(Scan(users), Scan(orders), "uid"),
                         lambda r: r["w"] > 0), "w", "sum"))
    assert q.collect().scalar == ref.scalar


def test_select_prunes_scans_and_projects_output():
    orders, users, _ = _star_tables(n_orders=2000, seed=29)
    sess = _star_session(orders=orders, users=users)
    out = (sess.table("orders")
           .join(sess.table("users"), on="uid")
           .select("uid", "w", "b_region")
           .sort("uid", "w")
           .to_relation())
    assert set(out.names) == {"uid", "w", "b_region"}
    ref = Executor(work_mem=1 << 30, policy="linear").execute(
        Sort(Join(Scan(users), Scan(orders), "uid"), ["uid", "w"]))
    assert out.sort_canonical().equals(
        ref.relation.select(["uid", "w", "b_region"]).sort_canonical())


def test_group_by_then_having_style_filter():
    orders, users, _ = _star_tables(n_orders=2000, seed=31)
    sess = _star_session(orders=orders, users=users)
    out = (sess.table("orders")
           .group_by("uid", {"w": "sum"})
           .filter(col("sum_w") > 100)
           .sort("uid")
           .to_relation())
    lin = Executor(work_mem=1 << 30, policy="linear").execute(
        GroupBy(Scan(orders), "uid", {"w": "sum"}))
    keep = lin.relation["sum_w"] > 100
    want = Relation({k: v[keep] for k, v in lin.relation.columns.items()})
    assert out.sort_canonical().equals(want.sort_canonical())


def test_query_validation_errors_name_the_problem():
    orders, users, _ = _star_tables(n_orders=100, seed=37)
    sess = _star_session(orders=orders, users=users)
    with pytest.raises(KeyError, match="nope"):
        sess.table("orders").filter(col("nope") > 0)
    with pytest.raises(KeyError, match="region"):
        sess.table("orders").sort("region")  # users' column, not orders'
    with pytest.raises(KeyError, match="unknown table"):
        sess.table("missing")
    with pytest.raises(KeyError, match="pid"):
        sess.table("orders").join(sess.table("users"), on="pid")


def test_session_refuses_conflicting_policy_and_shared_selector():
    """A Session given both a non-auto policy and an explicit selector must
    refuse rather than let the Executor mutate selector.force in place,
    silently re-pinning every other Session sharing it (regression)."""
    from repro.core import PathSelector, RuntimeProfile

    sel = PathSelector(1 << 20, profile=RuntimeProfile())
    Session(selector=sel)  # auto: fine, selector untouched
    with pytest.raises(ValueError, match="conflicts"):
        Session(policy="tensor", selector=sel)
    assert sel.force is None  # the shared selector was NOT mutated
    with pytest.raises(ValueError, match="either selector or profile"):
        Session(selector=sel, profile=RuntimeProfile())


def test_plan_program_rewrite_false_matches_rewrite_true():
    orders, users, parts = _star_tables(n_orders=1500, seed=41)
    sess = _star_session(orders=orders, users=users, parts=parts)
    q = _star_query(sess)
    assert q.collect(rewrite=False).scalar == q.collect().scalar
    prog = plan_program(q.logical())
    assert len(prog.stages) == 2 and prog.scalar


def test_auto_selector_handles_device_resident_fragment_inputs():
    """choose_fragment's Expr selectivity sampling must not crash (or pull
    data to the host) when a fragment's Scan holds a DeviceRelation
    (regression: probe.head() on a device relation)."""
    from repro.core import DeviceRelation

    rng = np.random.default_rng(53)
    build = Relation({"k": rng.permutation(512).astype(np.int64),
                      "v": rng.integers(0, 9, 512).astype(np.int64)})
    probe = Relation({"k": rng.integers(0, 512, 512).astype(np.int64),
                      "w": rng.integers(-9, 9, 512).astype(np.int64)})
    plan = lambda b, p: Aggregate(
        Sort(Filter(Join(Scan(b), Scan(p), "k"), col("w") > 0), ["k"]),
        "w", "sum")
    ref = Executor(work_mem=1 << 30, policy="linear").execute(
        plan(build, probe))
    got = Executor(work_mem=1 << 30, policy="auto").execute(
        plan(DeviceRelation.from_host(build),
             DeviceRelation.from_host(probe)))
    assert got.scalar == ref.scalar


# ---------------------------------------------------------------------------
# Relation.select device-cache sharing (satellite)
# ---------------------------------------------------------------------------

def test_select_subrelation_reuses_parent_device_cache():
    from repro.core.table_cache import get_device_columns

    rng = np.random.default_rng(43)
    parent = Relation({"k": rng.permutation(4096).astype(np.int64),
                       "v": rng.integers(0, 9, 4096).astype(np.int64),
                       "fat": rng.integers(0, 9, 4096).astype(np.int64)})
    # warm the parent at the padded bucket
    _, up_parent = get_device_columns(parent, bucket=4096)
    assert up_parent > 0
    # a selected sub-relation reuses the parent's uploads: zero new bytes
    sub = parent.select(["k", "v"])
    _, up_sub = get_device_columns(sub, bucket=4096)
    assert up_sub == 0
    # and uploads THROUGH a sub-relation warm the parent and later siblings
    fresh = Relation({"k": parent["k"], "v": parent["v"],
                      "fat": parent["fat"]})
    _, up1 = get_device_columns(fresh.select(["v"]), bucket=4096)
    assert up1 > 0
    _, up2 = get_device_columns(fresh.select(["v", "k"]), bucket=4096)
    assert up2 == 4096 * 8  # only k is new; v came from the sibling's upload
    # explicit invalidation reaches PRE-EXISTING shared selections, and the
    # shared dicts survive (cleared in place, not replaced): uploads after
    # the invalidation keep warming parent and siblings alike
    pre_sub = fresh.select(["v"])
    fresh.invalidate_device_cache()
    _, up3 = get_device_columns(pre_sub, bucket=4096)
    assert up3 > 0  # the old selection sees the invalidation
    _, up4 = get_device_columns(fresh, bucket=4096)
    assert up4 == 2 * 4096 * 8  # k+fat re-upload; v re-warmed via pre_sub


def test_select_subrelation_query_transfers_zero_when_parent_warm():
    rng = np.random.default_rng(47)
    build = Relation({"k": rng.permutation(2048).astype(np.int64),
                      "v": rng.integers(0, 9, 2048).astype(np.int64)})
    probe = Relation({"k": rng.integers(0, 2048, 2048).astype(np.int64),
                      "w": rng.integers(0, 9, 2048).astype(np.int64)})
    plan = lambda b, p: Aggregate(Sort(Join(Scan(b), Scan(p), "k"), ["k"]),
                                  "w", "sum")
    ex = Executor(work_mem=1 << 30, policy="tensor")
    cold = ex.execute(plan(build, probe))
    assert cold.total_h2d_bytes > 0
    # same columns through select(): fully warm (regression: re-uploaded)
    warm = ex.execute(plan(build.select(["k", "v"]), probe.select(["k", "w"])))
    assert warm.scalar == cold.scalar
    assert warm.total_h2d_bytes == 0


# ---------------------------------------------------------------------------
# Only a host-only predicate moves a fragment off the device
# ---------------------------------------------------------------------------

def _join_sum_session(policy="tensor"):
    rng = np.random.default_rng(3)
    sess = Session(work_mem=1 << 30, policy=policy)
    sess.register("orders", {"uid": rng.integers(0, 64, 2000),
                             "w": rng.integers(-9, 9, 2000)})
    sess.register("users", {"uid": np.arange(64),
                            "region": rng.integers(0, 4, 64)})
    return sess


def test_fused_device_error_propagates(monkeypatch):
    """A compile, lowering or runtime failure of the fused program is not a
    reason to re-run the query on the host: it reaches the caller."""
    import repro.core.fused as fused

    def fail(*_a, **_k):
        raise RuntimeError("device program failed")

    monkeypatch.setattr(fused, "run_fused", fail)
    sess = _join_sum_session()
    q = (sess.table("orders").join("users", on="uid")
         .filter(col("w") > 0).aggregate("w", "sum"))
    with pytest.raises(RuntimeError, match="device program failed"):
        q.collect()


def test_untraceable_predicate_takes_generic_walk():
    """A predicate that needs host numpy cannot trace into the fused
    program; the query still answers, on the generic walk."""
    sess = _join_sum_session()
    host_only = lambda r: np.asarray(r["w"]) > 0  # noqa: E731
    res = (sess.table("orders").join("users", on="uid")
           .filter(host_only).aggregate("w", "sum")).collect()
    ops = [m.op for m in res.metrics]
    assert "fused_pipeline" not in ops
    ref = (_join_sum_session("linear").table("orders").join("users", on="uid")
           .filter(host_only).aggregate("w", "sum")).collect()
    assert res.scalar == ref.scalar
