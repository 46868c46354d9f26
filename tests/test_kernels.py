"""Pallas kernel validation: interpret=True vs pure-jnp oracles, with
shape/dtype sweeps (assignment requirement: per kernel, sweep shapes/dtypes
and assert_allclose against the ref.py oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_dispatch.ops import combine, dispatch, moe_dispatch_pallas
from repro.kernels.moe_dispatch.ref import combine_ref, dispatch_ref
from repro.kernels.multikey_sort.ops import multikey_sort_lsd, tile_sort
from repro.kernels.multikey_sort.ref import tile_sort_ref
from repro.kernels.segment_join import kernel as segment_join_kernel
from repro.kernels.segment_join.ops import (join_aggregate_kernel,
                                            radix_hash_probe, radix_partition,
                                            segment_sum)
from repro.kernels.segment_join.ref import (radix_hash_probe_ref,
                                            radix_partition_ref,
                                            segment_sum_ref)


# ---------------------------------------------------------------------------
# moe_dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,d,E,C", [
    (256, 128, 4, 64),
    (512, 256, 8, 128),
    (1024, 128, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_dispatch_sweep(T, d, E, C, dtype):
    rng = np.random.default_rng(T + E)
    x = jnp.asarray(rng.normal(size=(T, d)), dtype)
    eidx = jnp.asarray(rng.integers(0, E, T), jnp.int32)
    slot = jnp.asarray(rng.integers(0, C + C // 4, T), jnp.int32)  # overflow mix
    w = jnp.asarray(rng.random(T), jnp.float32)
    buf = dispatch(x, eidx, slot, E, C, interpret=True)
    buf_r = dispatch_ref(x, eidx, slot, E, C)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(buf, np.float32),
                               np.asarray(buf_r, np.float32), rtol=tol, atol=tol)
    y = combine(buf_r, eidx, slot, w, interpret=True)
    y_r = combine_ref(buf_r, eidx, slot, w)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_r, np.float32), rtol=tol, atol=tol)


def test_moe_dispatch_matches_model_einsum_path():
    """The kernel path reproduces the model's einsum dispatch end to end."""
    from repro.configs import get_smoke_config
    from repro.models.moe import (_dispatch_einsum, _expert_ffn, _route,
                                  capacity_per_expert, init_moe)
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b")
    params = init_moe(jax.random.PRNGKey(0), cfg)
    T = 128
    x = jax.random.normal(jax.random.PRNGKey(1), (T, cfg.d_model), jnp.float32)
    topk_idx, topk_w, _ = _route(params, x, cfg)
    cap = capacity_per_expert(T, cfg.num_experts, cfg.experts_per_token,
                              cfg.capacity_factor)
    y_einsum = _dispatch_einsum(params, x, topk_idx, topk_w, cfg, cap)
    y_kernel = moe_dispatch_pallas(params, x, topk_idx, topk_w, cfg, cap,
                                   _expert_ffn, interpret=True)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_einsum),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# multikey_sort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,tile", [(256, 64), (1024, 256), (2048, 2048)])
@pytest.mark.parametrize("domain", [8, 1 << 20])
def test_bitonic_tile_sort_sweep(n, tile, domain):
    rng = np.random.default_rng(n + domain)
    keys = jnp.asarray(rng.integers(0, domain, n), jnp.int32)
    vals = jnp.asarray(rng.permutation(n), jnp.int32)
    ks, vs = tile_sort(keys, vals, tile=tile, interpret=True)
    kr, vr = tile_sort_ref(keys, vals, tile)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vs), np.asarray(vr))


def test_bitonic_stability_via_index_payload():
    n = 512
    keys = jnp.zeros(n, jnp.int32)  # all equal keys
    vals = jnp.arange(n, dtype=jnp.int32)
    ks, vs = tile_sort(keys, vals, tile=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(vs), np.arange(n))


@pytest.mark.parametrize("nkeys", [1, 2, 3])
def test_multikey_sort_lsd_matches_lexsort(nkeys):
    rng = np.random.default_rng(nkeys)
    n = 1024
    cols = tuple(jnp.asarray(rng.integers(0, 16, n), jnp.int32)
                 for _ in range(nkeys))
    perm = multikey_sort_lsd(cols, tile=256, interpret=True)
    ref = np.lexsort([np.asarray(c) for c in cols[::-1]])
    got = np.stack([np.asarray(c)[np.asarray(perm)] for c in cols])
    want = np.stack([np.asarray(c)[ref] for c in cols])
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# segment_join
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,S,tblk", [(2048, 64, 512), (4096, 256, 1024),
                                      (1024, 1024, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_segment_sum_sweep(n, S, tblk, dtype):
    rng = np.random.default_rng(n + S)
    seg = jnp.asarray(rng.integers(0, S, n), jnp.int32)
    val = jnp.asarray(rng.normal(size=n), dtype)
    got = segment_sum(seg, val, S, tblk=tblk, interpret=True)
    want = segment_sum_ref(seg, val, S)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,nbuckets,tblk", [
    (1000, 8, 256),        # non-pow2 n: padded tail rows must stay uncounted
    (2048, 64, 512),
    (4096, 1, 1024),       # single bucket: pure stable identity ordering
    (513, 16, 256),
])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.int64, jnp.int8])
def test_radix_partition_parity(n, nbuckets, tblk, dtype):
    rng = np.random.default_rng(n + nbuckets)
    hi = min(nbuckets, np.iinfo(np.dtype(dtype)).max + 1)
    ids = jnp.asarray(rng.integers(0, hi, n), dtype)
    dest, counts = radix_partition(ids, nbuckets, tblk=tblk, interpret=True)
    dest_r, counts_r = radix_partition_ref(ids, nbuckets)
    np.testing.assert_array_equal(np.asarray(dest), np.asarray(dest_r))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_r))


def test_radix_partition_empty():
    dest, counts = radix_partition(jnp.zeros((0,), jnp.int32), 8,
                                   interpret=True)
    assert dest.shape == (0,)
    np.testing.assert_array_equal(np.asarray(counts), np.zeros(8, np.int32))


def _probe_case(nb, npr, domain, seed, dup=False, dead=False):
    """Codes in [0, domain]; slot ``domain`` is the dead/padding slot."""
    rng = np.random.default_rng(seed)
    hi = domain if not dead else domain + 1
    bk = rng.integers(0, domain, nb) if not dup else \
        rng.integers(0, max(1, domain // 4), nb)
    if not dup and nb <= domain:
        bk = rng.permutation(domain)[:nb]  # unique live build keys
    pk = rng.integers(0, hi, npr)
    if dead:
        bk[rng.random(nb) < 0.1] = domain
    return jnp.asarray(bk, jnp.int32), jnp.asarray(pk, jnp.int32)


@pytest.mark.parametrize("nb,npr,domain", [
    (256, 1024, 512),
    (1000, 3000, 1024),     # non-pow2 sizes
    (2048, 2048, 4096),     # max dense width the dispatcher allows
    (64, 128, 16),          # domain smaller than dblk
])
@pytest.mark.parametrize("dup", [False, True])
@pytest.mark.parametrize("dead", [False, True])
def test_radix_hash_probe_parity(nb, npr, domain, dup, dead):
    bk, pk = _probe_case(nb, npr, domain, nb + npr + domain, dup, dead)
    cnt, row, has_dup = radix_hash_probe(bk, pk, domain, interpret=True)
    cnt_r, row_r, has_dup_r = radix_hash_probe_ref(bk, pk, domain)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))
    np.testing.assert_array_equal(np.asarray(row), np.asarray(row_r))
    assert bool(has_dup) == bool(has_dup_r)


@pytest.mark.parametrize("nb,npr", [(0, 256), (256, 0), (0, 0)])
def test_radix_hash_probe_empty_sides(nb, npr):
    rng = np.random.default_rng(7)
    bk = jnp.asarray(rng.integers(0, 64, nb), jnp.int32)
    pk = jnp.asarray(rng.integers(0, 64, npr), jnp.int32)
    cnt, row, has_dup = radix_hash_probe(bk, pk, 64, interpret=True)
    cnt_r, row_r, has_dup_r = radix_hash_probe_ref(bk, pk, 64)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))
    np.testing.assert_array_equal(np.asarray(row), np.asarray(row_r))
    assert bool(has_dup) == bool(has_dup_r) == False  # noqa: E712


def test_radix_hash_probe_all_dead_and_max_width():
    """Every build row dead (slot == domain) and probes at the dead slot:
    matches at the dead slot are the CALLER's masking problem — the kernel
    must still agree with the oracle bit for bit."""
    domain = 4096
    bk = jnp.full((512,), domain, jnp.int32)
    pk = jnp.concatenate([jnp.full((100,), domain, jnp.int32),
                          jnp.arange(100, dtype=jnp.int32)])
    cnt, row, has_dup = radix_hash_probe(bk, pk, domain, interpret=True)
    cnt_r, row_r, has_dup_r = radix_hash_probe_ref(bk, pk, domain)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))
    np.testing.assert_array_equal(np.asarray(row), np.asarray(row_r))
    # dead-slot pile-ups are NOT live duplicates (has_dup scans [0, domain))
    assert bool(has_dup) == bool(has_dup_r) == False  # noqa: E712


def _supplier_join_case(n_probe, live_probe, seed):
    """The SSB supplier join's codes: 2,000 live build codes in a 2,048
    domain, padded with dead build rows to a 2,048-row bucket; probes draw
    a live code each, and the padding probes sit in the dead slot."""
    domain, live_build = 2048, 2000
    bk = np.full(2048, domain, np.int32)
    bk[:live_build] = np.arange(live_build)
    pk = np.full(n_probe, domain, np.int32)
    pk[:live_probe] = np.random.default_rng(seed).integers(0, live_build,
                                                           live_probe)
    # the build row of every code: live codes are their own row, the dead
    # slot holds the largest dead row
    row_of = np.append(np.arange(live_build), 2047)
    want = np.where(pk == domain, 2047, row_of[np.minimum(pk, live_build)])
    return bk, pk, domain, want


def test_radix_hash_probe_build_rows_where_every_probe_matches():
    """Per-row build-row ids, not counts: every live probe matches exactly
    one build row, so a probe that read the wrong table slot still counts
    once.  Three domain blocks (2,048 codes and the dead slot), dead build
    rows in the dead slot, and a probe side over 48 row tiles."""
    bk, pk, domain, want = _supplier_join_case(48 * 1024, 45_000, 15)
    cnt, row, has_dup = radix_hash_probe(jnp.asarray(bk), jnp.asarray(pk),
                                         domain, interpret=True)
    np.testing.assert_array_equal(np.asarray(row), want)
    np.testing.assert_array_equal(np.asarray(cnt)[:45_000], 1)
    assert not bool(has_dup)


def test_radix_hash_probe_build_rows_on_the_chip():
    """The same at the SSB SF1 cell's shapes, compiled for the chip:
    6,001,215 live probes in a 2^23 bucket."""
    if jax.default_backend() != "tpu":
        pytest.skip("compiled Mosaic kernels need a TPU")
    bk, pk, domain, want = _supplier_join_case(1 << 23, 6_001_215, 15)
    cnt, row, has_dup = radix_hash_probe(jnp.asarray(bk), jnp.asarray(pk),
                                         domain)
    np.testing.assert_array_equal(np.asarray(row), want)
    np.testing.assert_array_equal(np.asarray(cnt)[:6_001_215], 1)
    assert not bool(has_dup)


def _output_block_walks(fn, *args):
    """For each Pallas call that ``fn`` makes, each output's block index
    at every grid step, in the order the TPU runs the grid (row-major)."""
    import itertools

    walks = []

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                outs = gm.block_mappings[gm.num_inputs:]
                steps = list(itertools.product(*map(range, gm.grid)))
                for bm in outs:
                    walks.append([tuple(int(x) for x in jax.core.eval_jaxpr(
                        bm.index_map_jaxpr.jaxpr, bm.index_map_jaxpr.consts,
                        *map(np.int32, step))) for step in steps])
                continue
            for v in eqn.params.values():      # jitted inner calls
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        visit(sub)

    visit(jax.make_jaxpr(fn)(*args).jaxpr)
    return walks


@pytest.mark.parametrize("name,fn,shapes", [
    ("segment_sum", lambda s, v: segment_join_kernel.segment_sum_pallas(
        s, v, 256, tblk=1024, interpret=True),
     [((4096,), jnp.int32), ((4096,), jnp.float32)]),
    ("radix_rank", lambda b: segment_join_kernel.radix_rank_pallas(
        b, 3, interpret=True), [((4096,), jnp.int32)]),
    ("table_build", lambda b, r: segment_join_kernel.join_table_build_pallas(
        b, r, 3072, interpret=True),
     [((2048,), jnp.int32), ((2048,), jnp.int32)]),
    ("table_probe", lambda p, c, i: segment_join_kernel.join_table_probe_pallas(
        p, c, i, interpret=True),
     [((4096,), jnp.int32), ((3072,), jnp.int32), ((3072,), jnp.int32)]),
])
def test_segment_join_output_blocks_are_revisited_on_consecutive_steps(
        name, fn, shapes):
    """The compiled kernel writes an output block back when the next grid
    step moves to another block and never reads it back, so an
    accumulating block must not be left and revisited later (the fault
    that lost the hash table's first domain block on the chip)."""
    args = [jnp.zeros(s, dt) for s, dt in shapes]
    walks = _output_block_walks(fn, *args)
    assert walks, name
    for walk in walks:
        left = set()
        for prev, cur in zip(walk, walk[1:]):
            if cur != prev:
                left.add(prev)
                assert cur not in left, (name, walk)


def test_join_aggregate_kernel_matches_core():
    """Kernel-path fused aggregate join == relational-core tensor path."""
    from repro.core import Relation, tensor_join_aggregate
    rng = np.random.default_rng(9)
    nb, npr, dom = 2048, 4096, 128
    bk = rng.integers(0, dom, nb)
    pk = rng.integers(0, dom, npr)
    bv = rng.integers(0, 50, nb).astype(np.float64)
    pv = rng.integers(0, 50, npr).astype(np.float64)
    agg = join_aggregate_kernel(
        jnp.asarray(bk, jnp.int32), jnp.asarray(bv, jnp.float32),
        jnp.asarray(pk, jnp.int32), jnp.asarray(pv, jnp.float32),
        dom, interpret=True)
    core, _ = tensor_join_aggregate(
        Relation({"k": bk.astype(np.int64), "v": bv}),
        Relation({"k": pk.astype(np.int64), "w": pv}),
        "k", "v", "w", key_domain=dom)
    np.testing.assert_allclose(float(agg["count"]), core["count"], rtol=1e-6)
    np.testing.assert_allclose(float(agg["sum_prod"]), core["sum_prod"], rtol=1e-5)
    np.testing.assert_allclose(float(agg["sum_add"]), core["sum_add"], rtol=1e-5)
