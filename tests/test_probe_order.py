"""Probe-order join cores against the expanding cores they stand in for.

When the build key is believed unique, each probe row matches at most one
build row, and the fused join cores keep their output in probe order at
the probe bucket instead of expanding matches into a padded capacity.
``run_fused`` must then give the same ``Relation`` or scalar, bit for
bit, as the expanding core on the same fragment: for the sorted, dense,
Pallas (interpret mode) and dictionary-key cores, with and without a
filter and a sort stage, for aggregate and relation roots.  A duplicate
build key the sample missed costs one retry on the expanding core, never
a wrong answer.
"""
import itertools

import numpy as np
import pytest

from repro.core import Relation, col, fused, tracing
from repro.core import table_cache

I64_MAX = np.iinfo(np.int64).max
N_PROBE = 3000  # bucket 4096: the tail of the probe bucket is padding

CORES = ("sorted", "dense", "pallas", "dict")


def _tables(core, rng):
    """(build, probe) whose key takes ``core``'s join path.  Probes mix
    matches, misses and keys equal to the int64 sentinel."""
    if core == "sorted":      # sparse keys: the sorted core
        bkeys = pool = rng.choice(1 << 40, 700, replace=False) * 3 + 1
        misses = rng.choice(1 << 40, 64) * 3 + 2
    elif core == "dict":      # wide keys, many repeats: dictionary codes
        head = rng.choice(1 << 20, 256, replace=False) << 30
        # the repeats lie past the (shortened) key sample, which then
        # believes the key unique; no probe hits the repeated key
        bkeys = np.concatenate([head, np.full(768, head[0])])
        pool = head[1:]
        misses = (rng.choice(1 << 20, 64) << 30) + 1
    else:                     # a dense domain: the coordinate core
        bkeys = pool = rng.permutation(1000)[:700] + 5000
        misses = np.concatenate([rng.permutation(1000)[700:764] + 5000,
                                 rng.integers(-50, 4000, 32)])
    pk = rng.choice(pool, N_PROBE)
    miss = rng.random(N_PROBE) < 0.3
    pk[miss] = rng.choice(misses, int(miss.sum()))
    pk[::97] = I64_MAX
    build = Relation({"k": bkeys.astype(np.int64),
                      "v": rng.integers(0, 1000, len(bkeys)),
                      "c": rng.random(len(bkeys))})
    probe = Relation({"k": pk.astype(np.int64),
                      "w": rng.integers(0, 50, N_PROBE),
                      "x": rng.random(N_PROBE)})
    return build, probe


def _run(spec, build, probe):
    with tracing.query() as trace:
        out, _ = fused.run_fused(spec, build, probe)
    return out, trace


def _expanding(monkeypatch):
    """Make ``run_fused`` plan the expanding cores whatever the keys."""
    plan = fused._host_plan
    monkeypatch.setattr(fused, "_host_plan",
                        lambda *a: plan(*a)[:3] + (False,))


def _assert_same(got, want):
    if isinstance(want, Relation):
        assert got.names == want.names
        for name in want.names:
            assert got[name].dtype == want[name].dtype, name
            np.testing.assert_array_equal(got[name], want[name])
        assert len(want) > 0
    else:
        assert type(got) is type(want)
        assert got == want


@pytest.mark.parametrize("core,filtered,sorted_,root", list(
    itertools.product(CORES, (False, True), (False, True),
                      ("aggregate", "relation"))))
def test_probe_order_matches_the_expanding_core(core, filtered, sorted_,
                                                root, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "1" if core == "pallas" else "0")
    if core == "dict":
        monkeypatch.setattr(table_cache, "SAMPLE_ROWS", 256)
    build, probe = _tables(core, np.random.default_rng(CORES.index(core)))
    spec = fused.FusedSpec(
        "k", (col("w") % 3 != 0) | (col("b_v") > 600) if filtered else None,
        ("b_v", "k") if sorted_ else (),
        ("b_v", "sum") if root == "aggregate" else None,
        None if root == "aggregate" or not filtered else ("k", "b_c", "x"))
    got, trace = _run(spec, build, probe)
    # the dictionary core's key sample misses the repeats: one retry
    retries = int(core == "dict")
    assert (trace.probe_order_joins, trace.retries,
            trace.dispatches) == (1, retries, 1 + retries)
    _expanding(monkeypatch)
    want, ref = _run(spec, build, probe)
    assert (ref.probe_order_joins, ref.retries) == (0, retries)
    _assert_same(got, want)


@pytest.mark.parametrize("core,hit", list(
    itertools.product(("sorted", "dense"), (True, False))))
def test_duplicate_past_the_key_sample(core, hit, monkeypatch):
    """The first 65,536 build rows hold unique keys, a later row repeats
    one.  Probes hitting the repeat make the probe-order core retry once on
    the expanding core; a repeat no probe hits leaves the sorted core's
    answer exact with no retry (the dense core checks the build itself and
    retries either way)."""
    monkeypatch.setenv("REPRO_PALLAS", "0")
    rng = np.random.default_rng(17)
    n = 70000
    keys = np.arange(n, dtype=np.int64) * (1000 if core == "sorted" else 1)
    assert table_cache.SAMPLE_ROWS < 69000
    keys[69000] = keys[10]
    build = Relation({"k": keys, "v": rng.integers(0, 1000, n)})
    pk = rng.choice(keys[20:60000], N_PROBE)
    if hit:
        pk[::100] = keys[10]
    probe = Relation({"k": pk, "w": rng.integers(0, 50, N_PROBE)})
    spec = fused.FusedSpec("k", None, (), ("b_v", "sum"))
    got, trace = _run(spec, build, probe)
    retries = int(hit or core == "dense")
    assert (trace.probe_order_joins, trace.retries) == (1, retries)
    sums = {}
    for k, v in zip(keys.tolist(), build["v"].tolist()):
        sums[k] = sums.get(k, 0) + v
    assert got == float(sum(sums.get(k, 0) for k in pk.tolist()))
    _expanding(monkeypatch)
    want, _ = _run(spec, build, probe)
    _assert_same(got, want)
