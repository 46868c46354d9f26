"""Device-resident tensor execution path: fused pipelines, late
materialization, capacity bucketing, and Pallas kernel wiring.

These tests are deliberately hypothesis-free so they always run: they carry
the tensor-vs-linear parity coverage for environments without the optional
property-testing dependency (see requirements.txt).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    Aggregate,
    DeviceRelation,
    Executor,
    Filter,
    GroupBy,
    Join,
    Relation,
    Scan,
    Sort,
    aligned_join_indices,
    capacity_bucket,
    group_aggregate_device,
    group_aggregate_linear,
    hash_join_linear,
    join_capacity,
    match_fragment,
    pipeline_cache_clear,
    pipeline_cache_info,
    sort_linear,
    tensor_join,
    tensor_join_aggregate,
    tensor_join_device,
    tensor_sort_device,
)


def _tables(rng, n_build, n_probe, bkeys=None, domain=None):
    domain = domain or max(1, n_build)
    build = Relation({
        "k": (bkeys if bkeys is not None
              else rng.integers(0, domain, n_build)).astype(np.int64),
        "v": rng.integers(-99, 99, n_build).astype(np.int64),
    })
    probe = Relation({
        "k": rng.integers(0, domain, n_probe).astype(np.int64),
        "w": rng.integers(-99, 99, n_probe).astype(np.int64),
    })
    return build, probe


# ---------------------------------------------------------------------------
# Parity: fused / device-resident tensor path vs linear, nasty key shapes
# ---------------------------------------------------------------------------

PARITY_CASES = {
    "unique_dense": lambda rng: _tables(rng, 3000, 4000,
                                        bkeys=rng.permutation(3000)),
    "duplicate_heavy": lambda rng: _tables(rng, 4000, 4000, domain=17),
    "skewed_90pct_one_key": lambda rng: _tables(
        rng, 3000, 3000,
        bkeys=np.where(rng.random(3000) < 0.9, 7,
                       rng.integers(0, 3000, 3000))),
    "sparse_wide_domain": lambda rng: _tables(
        rng, 2000, 3000, bkeys=rng.permutation(2000) * 10**9,
        domain=2000 * 10**9),
    "empty_probe": lambda rng: _tables(rng, 1024, 0),
    "empty_build": lambda rng: _tables(rng, 0, 1024),
    "single_row": lambda rng: _tables(rng, 1, 10, domain=1),
}


@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_fused_pipeline_parity(case):
    rng = np.random.default_rng(hash(case) % 2**31)
    build, probe = PARITY_CASES[case](rng)
    plans = [
        lambda: Sort(Join(Scan(build), Scan(probe), "k"), ["k", "w"]),
        lambda: Aggregate(Sort(Join(Scan(build), Scan(probe), "k"),
                               ["k"]), "b_v", "sum"),
        lambda: Aggregate(Sort(Filter(Join(Scan(build), Scan(probe), "k"),
                                      lambda r: r["w"] % 2 == 0),
                               ["k", "w"]), "w", "sum"),
        lambda: Aggregate(Join(Scan(build), Scan(probe), "k"), "b_v", "count"),
    ]
    if len(build) == 0:
        plans = plans[:1]  # aggregates over an empty schema column set differ
    for mk in plans:
        q_lin = Executor(work_mem=1 << 30, policy="linear").execute(mk())
        q_ten = Executor(work_mem=1 << 30, policy="tensor").execute(mk())
        if q_lin.relation is not None:
            assert q_lin.relation.sort_canonical().equals(
                q_ten.relation.sort_canonical()), case
        else:
            assert q_lin.scalar == q_ten.scalar, case


@pytest.mark.parametrize("work_mem", [1 << 30, 64 * 1024])
def test_device_chain_groupby_parity(work_mem):
    """Join→Filter→GroupBy chains on the generic device-resident walk (not
    the fused matcher) agree with the linear path and materialize once."""
    rng = np.random.default_rng(5)
    build, probe = _tables(rng, 3000, 3000, domain=64)
    plan = lambda: GroupBy(
        Filter(Join(Scan(build), Scan(probe), "k"), lambda r: r["w"] > 0),
        "k", {"w": "sum", "b_v": "min"})
    q_lin = Executor(work_mem=work_mem, policy="linear").execute(plan())
    q_ten = Executor(work_mem=work_mem, policy="tensor").execute(plan())
    lin, ten = q_lin.relation, q_ten.relation
    assert set(lin.names) == set(ten.names)
    ol, ot = np.argsort(lin["k"]), np.argsort(ten["k"])
    for name in lin.names:
        np.testing.assert_allclose(lin[name][ol], ten[name][ot],
                                   rtol=1e-9, atol=1e-9, err_msg=name)
    # device-resident chain: the join's scalar capacity sync + root
    # materialization are the ONLY device→host events
    assert q_ten.total_host_syncs <= 2
    ops = [m.op for m in q_ten.metrics]
    assert ops[-1] == "materialize"


def test_fused_single_host_sync_and_metrics():
    rng = np.random.default_rng(7)
    build, probe = _tables(rng, 2048, 2048, bkeys=rng.permutation(2048))
    plan = Aggregate(Sort(Join(Scan(build), Scan(probe), "k"), ["k"]),
                     "b_v", "sum")
    q = Executor(work_mem=1 << 30, policy="tensor").execute(plan)
    assert [m.op for m in q.metrics] == ["fused_pipeline"]
    assert q.total_host_syncs == 1
    assert q.metrics[0].spill.temp_bytes == 0


# ---------------------------------------------------------------------------
# Capacity: device-computed, bucketed, overflow-detecting
# ---------------------------------------------------------------------------

def test_join_capacity_matches_exact_count():
    rng = np.random.default_rng(11)
    bk = rng.integers(0, 37, 5000).astype(np.int64)
    pk = rng.integers(0, 37, 3000).astype(np.int64)
    sk = np.sort(bk)
    exact = int((np.searchsorted(sk, pk, "right")
                 - np.searchsorted(sk, pk, "left")).sum())
    assert join_capacity(bk, pk) == exact
    assert join_capacity(bk[:0], pk) == 0


def test_aligned_join_indices_capacity_overflow():
    """total > capacity is detectable from the returned count; the valid mask
    covers every slot and the clipped gather indices stay in range."""
    bk = jnp.asarray(np.zeros(64, np.int64))  # every probe matches all 64
    pk = jnp.asarray(np.zeros(8, np.int64))
    capacity = 16  # exact need: 512
    b_idx, p_idx, valid, total = aligned_join_indices(bk, pk, capacity)
    assert int(total) == 512
    assert int(total) > capacity
    assert bool(valid.all())
    assert int(b_idx.max()) < 64 and int(p_idx.max()) < 8
    # the host wrapper refuses an insufficient explicit capacity
    build = Relation({"k": np.zeros(64, np.int64), "v": np.arange(64)})
    probe = Relation({"k": np.zeros(8, np.int64), "w": np.arange(8)})
    with pytest.raises(ValueError, match="capacity"):
        tensor_join(build, probe, "k", capacity=capacity)


def test_fused_capacity_overflow_recovers():
    """The optimistic capacity bucket (sample-based) can underestimate under
    skew the sample misses; the driver must re-run at the exact bucket and
    still return the right answer."""
    rng = np.random.default_rng(13)
    # first 65536-row sample looks unique; the tail repeats one key 200x
    n = 70000
    bk = np.arange(n, dtype=np.int64)
    bk[65536:65736] = 1  # duplicates hidden from the sample
    build = Relation({"k": bk, "v": rng.integers(0, 9, n).astype(np.int64)})
    probe = Relation({"k": np.ones(4096, np.int64),
                      "w": rng.integers(0, 9, 4096).astype(np.int64)})
    plan = lambda: Aggregate(Sort(Join(Scan(build), Scan(probe), "k"), ["k"]),
                             "b_v", "sum")
    q_lin = Executor(work_mem=1 << 30, policy="linear").execute(plan())
    q_ten = Executor(work_mem=1 << 30, policy="tensor").execute(plan())
    assert q_lin.scalar == q_ten.scalar


def test_pipeline_compile_cache_bucketing():
    """Shape bucketing prevents recompile churn: queries with drifting row
    counts inside one power-of-two bucket reuse the SAME compiled program."""
    pipeline_cache_clear()
    rng = np.random.default_rng(17)
    for n in (900, 1000, 1024, 770):  # all bucket to 1024
        assert capacity_bucket(n) == 1024
        build, probe = _tables(rng, n, n, bkeys=rng.permutation(n))
        plan = Aggregate(Sort(Join(Scan(build), Scan(probe), "k"), ["k"]),
                         "b_v", "sum")
        Executor(work_mem=1 << 30, policy="tensor").execute(plan)
    info = pipeline_cache_info()
    assert info["misses"] == 1, info  # ONE compile for the whole bucket
    assert info["hits"] == 3, info


# ---------------------------------------------------------------------------
# Device-resident relation mechanics
# ---------------------------------------------------------------------------

def test_device_relation_lazy_gather_and_single_fetch():
    rng = np.random.default_rng(19)
    rel = Relation({"a": rng.integers(0, 9, 100).astype(np.int64),
                    "b": rng.integers(0, 9, 100).astype(np.int64)})
    dev = DeviceRelation.from_host(rel)
    idx = jnp.asarray(np.arange(99, -1, -1))
    lazy = dev.take_lazy(idx).take_lazy(idx)  # double reversal == identity
    assert lazy.columns["a"].gather is not None  # still pending
    assert lazy.to_host().equals(rel)


def test_device_join_sort_matches_host_ops():
    rng = np.random.default_rng(23)
    build, probe = _tables(rng, 1500, 2000, domain=40)
    d_out, m = tensor_join_device(DeviceRelation.from_host(build),
                                  DeviceRelation.from_host(probe), "k")
    assert m.host_syncs == 1  # the scalar capacity sync only
    d_sorted, ms = tensor_sort_device(d_out, ["k", "w"])
    assert ms.host_syncs == 0
    got = d_sorted.to_host()
    want, _ = hash_join_linear(build, probe, "k", 1 << 30)
    assert got.sort_canonical().equals(want.sort_canonical())
    want_sorted, _ = sort_linear(want, ["k", "w"], 1 << 30)
    for c in ("k", "w"):  # identical sort order on key columns
        np.testing.assert_array_equal(got[c], want_sorted[c])


def test_group_aggregate_device_masked_rows_at_dtype_max():
    """A valid row keyed at int64 max must keep its own group even when
    masked rows exist (regression: sentinel remap used to merge them)."""
    kmax = np.iinfo(np.int64).max
    rel = Relation({"k": np.array([5, 7, kmax], np.int64),
                    "v": np.array([1, 999, 100], np.int64)})
    dev = DeviceRelation.from_host(rel).mask_and(
        jnp.asarray([True, False, True]))
    out, _ = group_aggregate_device(dev, "k", {"v": "sum"})
    host = out.to_host()
    assert sorted(host["k"].tolist()) == [5, kmax]
    got = dict(zip(host["k"].tolist(), host["sum_v"].tolist()))
    assert got[5] == 1.0 and got[kmax] == 100.0


def test_device_join_explicit_capacity_overflow_raises():
    """tensor_join_device must refuse an insufficient explicit capacity
    rather than silently truncate (regression)."""
    build = DeviceRelation.from_host(
        Relation({"k": np.zeros(64, np.int64), "v": np.arange(64)}))
    probe = DeviceRelation.from_host(
        Relation({"k": np.zeros(8, np.int64), "w": np.arange(8)}))
    with pytest.raises(ValueError, match="capacity"):
        tensor_join_device(build, probe, "k", capacity=16)


def test_pipeline_cache_hits_across_recreated_predicates():
    """Identical filter lambdas rebuilt per query (the normal plan-building
    pattern) must hit the compile cache, not grow it (regression: keyed on
    id(fn))."""
    pipeline_cache_clear()
    rng = np.random.default_rng(53)
    build, probe = _tables(rng, 512, 512, bkeys=rng.permutation(512))
    for _ in range(3):
        plan = Aggregate(Sort(Filter(Join(Scan(build), Scan(probe), "k"),
                                     lambda r: r["w"] > 0), ["k"]),
                         "b_v", "sum")
        Executor(work_mem=1 << 30, policy="tensor").execute(plan)
    info = pipeline_cache_info()
    assert info["misses"] == 1 and info["hits"] == 2, info
    # distinct captured values are distinct predicates — no stale reuse
    results = []
    for cut in (10, 80):
        plan = Aggregate(Sort(Filter(Join(Scan(build), Scan(probe), "k"),
                                     lambda r: r["w"] > cut), ["k"]),
                         "b_v", "count")
        results.append(
            Executor(work_mem=1 << 30, policy="tensor").execute(plan).scalar)
    assert results[0] > results[1]  # looser cut keeps more rows


def test_group_aggregate_device_masked_rows():
    rng = np.random.default_rng(29)
    rel = Relation({"k": rng.integers(0, 8, 500).astype(np.int64),
                    "v": rng.integers(-50, 50, 500).astype(np.int64)})
    keep = rng.random(500) < 0.5
    dev = DeviceRelation.from_host(rel).mask_and(jnp.asarray(keep))
    out, m = group_aggregate_device(dev, "k", {"v": "sum"})
    assert m.host_syncs == 0
    host = out.to_host()
    want, _ = group_aggregate_linear(
        Relation({k: v[keep] for k, v in rel.columns.items()}),
        "k", {"v": "sum"}, 1 << 30)
    assert host.sort_canonical().equals(want.sort_canonical())


# ---------------------------------------------------------------------------
# Pallas kernels wired into the engine (interpret fallback on CPU)
# ---------------------------------------------------------------------------

def test_pallas_segment_sum_padded_arbitrary_n():
    from repro.kernels.segment_join.ops import segment_sum
    rng = np.random.default_rng(31)
    for n in (100, 1000, 2048, 3000):  # incl. non-multiples of the tile
        seg = jnp.asarray(rng.integers(0, 32, n), jnp.int32)
        val = jnp.asarray(rng.normal(size=n), jnp.float32)
        got = segment_sum(seg, val, 32, interpret=True)
        want = np.zeros(32, np.float32)
        np.add.at(want, np.asarray(seg), np.asarray(val))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_pallas_multikey_sort_padded_matches_lexsort():
    from repro.kernels.multikey_sort.ops import multikey_sort_lsd_padded
    rng = np.random.default_rng(37)
    for n in (1000, 1024, 2500):
        cols = tuple(jnp.asarray(rng.integers(0, 9, n), jnp.int32)
                     for _ in range(2))
        perm = np.asarray(multikey_sort_lsd_padded(cols, tile=256,
                                                   interpret=True))
        ref = np.lexsort([np.asarray(c) for c in cols[::-1]])
        np.testing.assert_array_equal(perm, ref)


def test_engine_parity_with_pallas_forced(monkeypatch):
    """REPRO_PALLAS=1 routes the engine's segment sums through the Pallas
    kernel (interpret mode on CPU) where its exactness rule admits them,
    with identical results; sorts stay on the jnp LSD passes."""
    monkeypatch.setenv("REPRO_PALLAS", "1")
    rng = np.random.default_rng(41)
    rel = Relation({"k": rng.integers(0, 16, 512).astype(np.int64),
                    "v": rng.integers(-9, 9, 512).astype(np.int64)})
    from repro.core import group_aggregate_tensor
    ten, _ = group_aggregate_tensor(rel, "k", {"v": "sum"})
    lin, _ = group_aggregate_linear(rel, "k", {"v": "sum"}, 1 << 30)
    assert ten.sort_canonical().equals(lin.sort_canonical())
    from repro.core.tensor_engine import kernels_traced, sort_perm_device
    assert kernels_traced().get("segment_sum", 0) > 0
    keys = (jnp.asarray(rng.integers(0, 7, 300), jnp.int32),)
    perm = np.asarray(sort_perm_device(keys))
    np.testing.assert_array_equal(np.asarray(keys[0])[perm],
                                  np.sort(np.asarray(keys[0])))


# ---------------------------------------------------------------------------
# Fused join-aggregate dtype contract (satellite: no mixed f64/f32 sides)
# ---------------------------------------------------------------------------

def test_join_aggregate_dtype_precision():
    """Σ(b·p) must not truncate either side to float32: values near 2^25
    would lose low bits.  Both sides now contract at one explicit dtype."""
    n, dom = 256, 16
    rng = np.random.default_rng(43)
    base = 1 << 25
    bv = (base + rng.integers(0, 7, n)).astype(np.float64)
    pv = (base + rng.integers(0, 7, n)).astype(np.float64)
    bk = rng.integers(0, dom, n).astype(np.int64)
    pk = rng.integers(0, dom, n).astype(np.int64)
    build = Relation({"k": bk, "v": bv})
    probe = Relation({"k": pk, "w": pv})
    out, _ = tensor_join_aggregate(build, probe, "k", "v", "w", key_domain=dom)
    # exact reference in python ints over the explicit join
    want_prod = want_add = want_cnt = 0
    for d in range(dom):
        bs = bv[bk == d]
        ps = pv[pk == d]
        want_cnt += len(bs) * len(ps)
        want_add += int(bs.sum()) * len(ps) + int(ps.sum()) * len(bs)
        want_prod += int(bs.sum()) * int(ps.sum())
    assert out["count"] == want_cnt
    np.testing.assert_allclose(out["sum_add"], want_add, rtol=1e-12)
    np.testing.assert_allclose(out["sum_prod"], want_prod, rtol=1e-12)
    # float32 truncation of either side would already be visible here:
    f32_loss = abs(float(np.float32(base + 3)) * n * n - want_prod)
    assert f32_loss > 0  # the test data genuinely exercises the lost bits


# ---------------------------------------------------------------------------
# Error/edge semantics parity (regression coverage from review)
# ---------------------------------------------------------------------------

def test_min_over_zero_match_join_raises_like_linear():
    """min/max over a zero-match (non-empty inputs) join must error on the
    tensor paths too, never return the sentinel fill value."""
    build = Relation({"k": np.arange(100, 200, dtype=np.int64),
                      "v": np.arange(100, dtype=np.int64)})
    probe = Relation({"k": np.arange(0, 50, dtype=np.int64),
                      "w": np.arange(50, dtype=np.int64)})
    for mk in [lambda: Aggregate(Join(Scan(build), Scan(probe), "k"),
                                 "b_v", "min"),
               lambda: Aggregate(Sort(Join(Scan(build), Scan(probe), "k"),
                                      ["k"]), "w", "max")]:
        with pytest.raises(ValueError):
            Executor(work_mem=1 << 30, policy="linear").execute(mk())
        with pytest.raises(ValueError):
            Executor(work_mem=1 << 30, policy="tensor").execute(mk())
        # sum/count stay well-defined (0) on both paths
    q = Executor(work_mem=1 << 30, policy="tensor").execute(
        Aggregate(Join(Scan(build), Scan(probe), "k"), "b_v", "sum"))
    assert q.scalar == 0.0


_GLOBAL_CUT = 3


def test_predicate_cache_tracks_global_captures():
    """Changing a module global referenced by the predicate must NOT reuse
    the stale compiled filter program (regression: globals missing from the
    cache key)."""
    global _GLOBAL_CUT
    rng = np.random.default_rng(59)
    build, probe = _tables(rng, 256, 256, bkeys=rng.permutation(256))
    def run():
        plan = Aggregate(Sort(Filter(Join(Scan(build), Scan(probe), "k"),
                                     lambda r: r["w"] > _GLOBAL_CUT), ["k"]),
                         "b_v", "count")
        return Executor(work_mem=1 << 30, policy="tensor").execute(plan).scalar
    _GLOBAL_CUT = -1000
    loose = run()
    _GLOBAL_CUT = 1000
    tight = run()
    assert loose > 0 and tight == 0.0, (loose, tight)


def test_fused_preserves_key_column_dtype_and_values():
    """Fused results must serve the ORIGINAL key column — same dtype (int32
    stays int32) and same values (float keys not truncated) as the unfused
    paths (regression: coerced int64 upload leaked into the output)."""
    rng = np.random.default_rng(67)
    build = Relation({"k": np.arange(64, dtype=np.int32),
                      "v": rng.integers(0, 9, 64).astype(np.int64)})
    probe = Relation({"k": rng.integers(0, 64, 100).astype(np.int32),
                      "w": rng.integers(0, 9, 100).astype(np.int64)})
    plan = lambda: Sort(Join(Scan(build), Scan(probe), "k"), ["k", "w"])
    fused = Executor(work_mem=1 << 30, policy="tensor").execute(plan())
    unfused = Executor(work_mem=1 << 30, policy="tensor",
                       fuse=False).execute(plan())
    assert fused.relation["k"].dtype == unfused.relation["k"].dtype
    assert fused.relation.sort_canonical().equals(
        unfused.relation.sort_canonical())
    # float keys: join coerces coordinates, output keeps the float values
    buildf = Relation({"k": np.array([0.5, 2.5]),
                       "v": np.array([1, 2], np.int64)})
    probef = Relation({"k": np.array([0.25, 2.75]),
                       "w": np.array([3, 4], np.int64)})
    planf = lambda: Sort(Join(Scan(buildf), Scan(probef), "k"), ["k"])
    ff = Executor(work_mem=1 << 30, policy="tensor").execute(planf())
    uf = Executor(work_mem=1 << 30, policy="tensor", fuse=False).execute(planf())
    assert ff.relation.sort_canonical().equals(uf.relation.sort_canonical())
    assert set(np.asarray(ff.relation["k"]).tolist()) <= {0.25, 2.75}


def test_predicate_cache_identity_fallback_for_mutable_captures():
    """A predicate reading through a mutable captured object must not hit a
    stale compiled program when the plan is rebuilt (regression: identity-
    hashed captures were value-cached)."""
    class Cfg:
        thr = 0
    cfg = Cfg()
    rng = np.random.default_rng(71)
    build, probe = _tables(rng, 256, 256, bkeys=rng.permutation(256))
    def run():
        plan = Aggregate(Sort(Filter(Join(Scan(build), Scan(probe), "k"),
                                     lambda r: r["w"] > cfg.thr), ["k"]),
                         "b_v", "count")
        return Executor(work_mem=1 << 30, policy="tensor").execute(plan).scalar
    cfg.thr = -1000
    loose = run()
    cfg.thr = 1000
    tight = run()
    assert loose > 0 and tight == 0.0, (loose, tight)


def test_predicate_cache_rebound_cell_is_new_entry():
    """Rebinding a closure cell between queries (same lambda OBJECT) must
    produce a different pipeline-cache entry — the captured value is traced
    into the compiled program, so reusing the old entry would silently
    filter with the stale constant (regression)."""
    pipeline_cache_clear()
    rng = np.random.default_rng(73)
    build, probe = _tables(rng, 256, 256, bkeys=rng.permutation(256))

    cut = 2.0
    pred = lambda r: r["w"] > cut  # ONE lambda, cell rebound between runs

    def run():
        plan = Aggregate(Sort(Filter(Join(Scan(build), Scan(probe), "k"),
                                     pred), ["k"]), "b_v", "count")
        return Executor(work_mem=1 << 30, policy="tensor").execute(plan).scalar

    loose = run()
    assert pipeline_cache_info()["misses"] == 1
    cut = 80.0
    tight = run()
    assert pipeline_cache_info()["misses"] == 2  # rebound float → new entry
    assert loose > tight, (loose, tight)
    cut = 2.0
    again = run()  # rebinding BACK hits the first entry with the right value
    assert pipeline_cache_info()["misses"] == 2
    assert again == loose


def test_predicate_cache_type_tags_captured_values():
    """``1 == 1.0 == True`` as dict keys: a captured value rebound across
    equal-comparing types must be a distinct cache entry, not a collision
    resurrecting the program traced with the other dtype (regression)."""
    pipeline_cache_clear()
    rng = np.random.default_rng(79)
    build, probe = _tables(rng, 256, 256, bkeys=rng.permutation(256))

    cut = 1
    pred = lambda r: r["w"] > cut

    def run():
        plan = Aggregate(Sort(Filter(Join(Scan(build), Scan(probe), "k"),
                                     pred), ["k"]), "b_v", "count")
        return Executor(work_mem=1 << 30, policy="tensor").execute(plan).scalar

    r_int = run()
    cut = 1.0
    r_float = run()
    cut = True
    r_bool = run()
    assert pipeline_cache_info()["misses"] == 3  # int / float / bool distinct
    assert r_int == r_float == r_bool  # same comparison semantics, though


def test_ir_predicates_skip_bytecode_keying():
    """Expr-built filters cache by their canonical token: two structurally
    equal expressions built at different source locations share ONE compiled
    program (bytecode keying could never see through source location)."""
    from repro.core import col

    pipeline_cache_clear()
    rng = np.random.default_rng(83)
    build, probe = _tables(rng, 256, 256, bkeys=rng.permutation(256))

    def make_a():
        return (col("w") > 0) & col("k").isin([1, 2, 3])

    def make_b():  # different lines, same meaning
        lhs = col("w") > 0
        rhs = col("k").isin([1, 2, 3])
        return lhs & rhs

    results = []
    for mk in (make_a, make_b):
        plan = Aggregate(Sort(Filter(Join(Scan(build), Scan(probe), "k"),
                                     mk()), ["k"]), "b_v", "count")
        results.append(
            Executor(work_mem=1 << 30, policy="tensor").execute(plan).scalar)
    info = pipeline_cache_info()
    assert info["misses"] == 1 and info["hits"] == 1, info
    assert results[0] == results[1]


def test_filter_only_join_fragment_fuses():
    """Filter(Join(Scan, Scan)) — the shape pushed-down filters produce in
    multi-join chains — runs as ONE fused program with a single sync."""
    rng = np.random.default_rng(89)
    build, probe = _tables(rng, 512, 512, bkeys=rng.permutation(512))
    plan = lambda: Filter(Join(Scan(build), Scan(probe), "k"),
                          lambda r: r["w"] > 0)
    q = Executor(work_mem=1 << 30, policy="tensor").execute(plan())
    assert [m.op for m in q.metrics] == ["fused_pipeline"]
    assert q.total_host_syncs == 1
    ref = Executor(work_mem=1 << 30, policy="linear").execute(plan())
    assert q.relation.sort_canonical().equals(ref.relation.sort_canonical())


def test_projected_fragment_gathers_subset():
    """Project(Sort(Join)) fuses with the projection folded into the spec:
    only the projected columns cross the device→host boundary."""
    from repro.core import Project, match_fragment

    rng = np.random.default_rng(97)
    build, probe = _tables(rng, 512, 512, domain=32)
    plan = lambda: Project(Sort(Join(Scan(build), Scan(probe), "k"),
                                ["k", "w"]), ["k", "w"])
    frag = match_fragment(plan())
    assert frag is not None and frag[0].project == ("k", "w")
    q = Executor(work_mem=1 << 30, policy="tensor").execute(plan())
    assert [m.op for m in q.metrics] == ["fused_pipeline"]
    assert set(q.relation.names) == {"k", "w"}
    ref = Executor(work_mem=1 << 30, policy="linear").execute(plan())
    assert q.relation.sort_canonical().equals(ref.relation.sort_canonical())


def test_pallas_sort_empty_relation(monkeypatch):
    """REPRO_PALLAS=1 sort of a 0-row relation must return empty, not crash
    in the tile-size arithmetic (regression)."""
    monkeypatch.setenv("REPRO_PALLAS", "1")
    from repro.core import tensor_sort
    rel = Relation({"k": np.zeros(0, np.int32), "p": np.zeros(0, np.int64)})
    out, _ = tensor_sort(rel, ["k"])
    assert len(out) == 0


def test_pallas_segment_sum_empty_input(monkeypatch):
    """REPRO_PALLAS=1 join-aggregate over empty relations must return zeros,
    not divide by a zero tile size (regression)."""
    monkeypatch.setenv("REPRO_PALLAS", "1")
    from repro.kernels.segment_join.ops import segment_sum
    got = segment_sum(jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.float32), 8,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(8, np.float32))
    build = Relation({"k": np.zeros(0, np.int64), "v": np.zeros(0)})
    probe = Relation({"k": np.zeros(0, np.int64), "w": np.zeros(0)})
    out, _ = tensor_join_aggregate(build, probe, "k", "v", "w", key_domain=8)
    assert out["count"] == 0.0 and out["sum_prod"] == 0.0


def test_pallas_sort_gate_rejects_uint32():
    from repro.kernels.multikey_sort.ops import keys_fit_int32
    assert keys_fit_int32((jnp.zeros(4, jnp.int32),))
    assert keys_fit_int32((jnp.zeros(4, jnp.int16),))
    assert not keys_fit_int32((jnp.zeros(4, jnp.uint32),))  # would wrap
    assert not keys_fit_int32((jnp.zeros(4, jnp.int64),))
    assert not keys_fit_int32((jnp.zeros(4, jnp.float32),))


def test_group_aggregate_tensor_float_keys():
    """Seed accepted float group keys by truncating to int64; keep that."""
    from repro.core import group_aggregate_tensor
    rel = Relation({"k": np.array([1.0, 2.0, 1.0, 2.0]),
                    "v": np.array([10, 20, 30, 40], np.int64)})
    ten, _ = group_aggregate_tensor(rel, "k", {"v": "sum"})
    got = dict(zip(ten["k"].tolist(), ten["sum_v"].tolist()))
    assert got == {1: 40.0, 2: 60.0}


def test_untraceable_predicate_fallback_counts_sync():
    """A predicate that cannot trace forces a host materialization mid-
    pipeline; that regime crossing must appear in host_syncs."""
    rng = np.random.default_rng(61)
    build, probe = _tables(rng, 512, 512, domain=32)

    def hostile(r):  # touches a numpy-only attribute: device arrays raise
        _ = r["w"].flags
        return r["w"] % 2 == 0

    plan = lambda: GroupBy(Filter(Join(Scan(build), Scan(probe), "k"),
                                  hostile), "k", {"w": "sum"})
    q_ten = Executor(work_mem=1 << 30, policy="tensor").execute(plan())
    q_lin = Executor(work_mem=1 << 30, policy="linear").execute(plan())
    assert q_ten.relation.sort_canonical().equals(
        q_lin.relation.sort_canonical())
    assert any(m.op == "filter_materialize" and m.host_syncs == 1
               for m in q_ten.metrics)


# ---------------------------------------------------------------------------
# Fragment matcher
# ---------------------------------------------------------------------------

def test_match_fragment_shapes():
    rng = np.random.default_rng(47)
    build, probe = _tables(rng, 100, 100)
    j = Join(Scan(build), Scan(probe), "k")
    assert match_fragment(Sort(j, ["k"])) is not None
    assert match_fragment(Aggregate(Sort(j, ["k"]), "w", "sum")) is not None
    spec, _, _ = match_fragment(
        Aggregate(Sort(Filter(j, lambda r: r["w"] > 0), ["k"]), "w", "sum"))
    assert spec.filter_fn is not None and spec.sort_keys == ("k",)
    # a bare join gains nothing from fusion; deeper trees don't match
    assert match_fragment(j) is None
    assert match_fragment(Sort(Join(Sort(Scan(build), ["k"]), Scan(probe),
                                    "k"), ["k"])) is None
