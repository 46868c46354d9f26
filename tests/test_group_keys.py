"""Composite group keys and computed measures against numpy.

A group-by on several keys groups by the keys' lexicographic order, over
the joined rows.  A measure is an ``Expr`` over the rows, evaluated before
the reduction.  Every case runs under the
``linear``, ``tensor`` and ``auto`` policies, on the fused and the
per-operator paths, and is compared with a plain numpy reference over
seeded random tables.
"""
import numpy as np
import pytest

from repro.core import Relation, Session, col
from repro.core.executor import GroupBy
from repro.core.planner import plan_program

POLICIES = ["linear", "tensor", "auto"]
N_FACT = 6_000


def _tables(seed, wide=False):
    """A fact table joined to two dimensions by ``k1`` and ``k2``.  With
    ``wide`` the key columns spread over +-2**40, so that the product of
    two keys' spans does not fit an int64."""
    rng = np.random.default_rng(seed)
    spread = 1 << 40 if wide else 1

    def codes(card, n):
        return rng.integers(0, card, n).astype(np.int64) * spread

    fact = {"k1": rng.integers(1, 301, N_FACT), "k2": rng.integers(1, 41,
                                                                   N_FACT),
            "z": codes(4, N_FACT), "rev": rng.integers(0, 10**7, N_FACT),
            "cost": rng.integers(0, 10**6, N_FACT),
            "disc": rng.integers(0, 11, N_FACT)}
    d1 = {"k1": np.arange(1, 301), "x": codes(25, 300),
          "r": rng.integers(0, 5, 300)}
    d2 = {"k2": np.arange(1, 41), "y": codes(7, 40) - 3 * spread}
    return {"fact": {k: np.asarray(v, np.int64) for k, v in fact.items()},
            "d1": {k: np.asarray(v, np.int64) for k, v in d1.items()},
            "d2": {k: np.asarray(v, np.int64) for k, v in d2.items()}}


def _joined(t):
    """The fact rows with their dimensions' columns, as the engine names
    them, filtered on ``b_r < 4``."""
    f = t["fact"]
    out = dict(f)
    out["b_x"] = t["d1"]["x"][f["k1"] - 1]
    out["b_r"] = t["d1"]["r"][f["k1"] - 1]
    out["b_y"] = t["d2"]["y"][f["k2"] - 1]
    keep = out["b_r"] < 4
    return {k: v[keep] for k, v in out.items()}


def _want(rows, keys, values):
    """``{(key, ...): sum}`` of ``values`` over the distinct key tuples."""
    tuples = np.stack([rows[k] for k in keys], axis=1)
    uniq, gid = np.unique(tuples, axis=0, return_inverse=True)
    sums = np.zeros(len(uniq), np.int64)
    np.add.at(sums, gid.reshape(-1), values)
    return {tuple(int(x) for x in u): int(s) for u, s in zip(uniq, sums)}


def _got(rel, keys, value):
    cols = [rel[k].tolist() for k in keys]
    return {tuple(ks): v for ks, v in zip(zip(*cols), rel[value].tolist())}


def _session(t, policy, fuse=True):
    s = Session(work_mem=1 << 20, policy=policy, fuse=fuse)
    for name, cols in t.items():
        s.register(name, cols)
    return s


def _chain(s):
    """fact ⋈ d1 ⋈ d2, filtered on a build-side column: two fragments."""
    return (s.table("fact").join("d1", on="k1").filter(col("b_r") < 4)
            .join("d2", on="k2"))


MEASURES = {
    "product": (col("rev") * col("disc"), lambda r: r["rev"] * r["disc"]),
    "difference": (col("rev") - col("cost"),
                   lambda r: r["rev"] - r["cost"]),
}
KEYS = {
    # two build sides
    "two_builds": ("b_x", "b_y"),
    # two build sides and the probe side, in an order of the query's own
    "three": ("b_y", "z", "b_x"),
    # the probe side and one build side
    "probe_and_build": ("z", "b_x"),
}


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("keys", sorted(KEYS))
@pytest.mark.parametrize("measure", sorted(MEASURES))
def test_composite_group_by_with_measure_equals_numpy(policy, keys, measure):
    t = _tables(11)
    expr, ref = MEASURES[measure]
    ks = KEYS[keys]
    q = _chain(_session(t, policy)).group_by(ks, {(measure, expr): "sum",
                                                  "cost": "sum"})
    rel = q.collect().relation
    assert rel.names == ks + (f"sum_{measure}", "sum_cost")
    rows = _joined(t)
    assert _got(rel, ks, f"sum_{measure}") == _want(rows, ks, ref(rows))
    assert _got(rel, ks, "sum_cost") == _want(rows, ks, rows["cost"])
    for k in ks:
        assert rel[k].dtype == np.int64


def _group_nodes(q):
    """The physical GroupBy nodes the planner builds for ``q``; each stage
    runs so that the next one is built over its output."""
    outputs, nodes = [], []
    for stage in plan_program(q.logical()).stages:
        node = stage.build_physical(outputs)
        outputs.append(q._session.executor.execute(node).relation)
        while node is not None:
            if isinstance(node, GroupBy):
                nodes.append(node)
            node = getattr(node, "child", None)
    return nodes


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("wide", [False, True])
def test_group_keys_of_any_span_give_numpys_groups(policy, wide):
    """Several keys reach one GroupBy node of the stage that joins them,
    and group by their lexicographic order whatever their spans: keys
    spread over +-2**40, whose spans' product no int64 holds, give
    numpy's answer too."""
    t = _tables(12, wide=wide)
    s = _session(t, policy)
    ks = ("b_x", "b_y", "z")
    q = _chain(s).group_by(ks, {("profit", col("rev") - col("cost")): "sum"})
    (node,) = _group_nodes(q)
    assert node.keys == ks
    rows = _joined(t)
    rel = q.collect().relation
    assert _got(rel, ks, "sum_profit") == _want(rows, ks,
                                                rows["rev"] - rows["cost"])


@pytest.mark.parametrize("policy", POLICIES)
def test_one_key_and_a_stored_measure_are_unchanged(policy):
    t = _tables(13)
    s = _session(t, policy)
    rel = _chain(s).group_by("b_x", {"rev": "sum", "cost": "max"}) \
        .collect().relation
    assert rel.names == ("b_x", "sum_rev", "max_cost")
    rows = _joined(t)
    want = _want(rows, ("b_x",), rows["rev"])
    assert {(int(k),): v for k, v in zip(rel["b_x"], rel["sum_rev"])} == want
    assert rel["sum_rev"].dtype == np.float64
    for k, v in zip(rel["b_x"], rel["max_cost"]):
        assert v == rows["cost"][rows["b_x"] == k].max()


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("fuse", [True, False])
@pytest.mark.parametrize("measure", sorted(MEASURES))
def test_scalar_aggregate_of_a_measure_equals_numpy(policy, fuse, measure):
    """A scalar root over a measure: the fused program under ``tensor``
    (one fragment), the per-operator walk without fusion, the host under
    ``linear``."""
    t = _tables(14)
    expr, ref = MEASURES[measure]
    s = _session(t, policy, fuse=fuse)
    res = (s.table("fact").join("d1", on="k1").filter(col("b_r") < 4)
           .aggregate((measure, expr), "sum").collect())
    rows = _joined(t)
    assert res.scalar == float(ref(rows).sum())
    ops = [m.op for m in res.metrics]
    if policy == "tensor":
        assert ("fused_pipeline" in ops) is fuse


def test_measure_api_rejects_bad_specs():
    s = _session(_tables(15), "tensor")
    fact = s.table("fact")
    with pytest.raises(KeyError, match="nope"):
        fact.aggregate(("m", col("nope") * 2))
    with pytest.raises(ValueError, match="already a column"):
        fact.group_by("z", {("rev", col("cost") * 2): "sum"})
    with pytest.raises(TypeError, match="named measure"):
        fact.aggregate(("m", 3))
    with pytest.raises(KeyError, match="nope"):
        fact.group_by(("z", "nope"), {"rev": "sum"})
    q = fact.group_by(("z", "k1"), {("m", col("rev") + 1): "count"})
    assert q.schema() == ("z", "k1", "count_m")
    assert "group_by[z,k1]{'m'=(col('rev') + 1): 'count'}" in q.explain()


def test_sharded_scalar_measure_matches_single_device(eight_device_mesh):
    from repro.core.fused import FusedSpec, run_fused, sharded_supported

    t = _tables(16)
    build, probe = Relation(dict(t["d1"])), Relation(dict(t["fact"]))
    spec = FusedSpec("k1", None, (), ("m", "sum"),
                     measure=col("rev") * col("disc") - col("b_r"))
    assert sharded_supported(spec, build, probe)
    single, _ = run_fused(spec, build, probe)
    sharded, m8 = run_fused(spec, build, probe, shards=8)
    f = t["fact"]
    want = (f["rev"] * f["disc"] - t["d1"]["r"][f["k1"] - 1]).sum()
    assert m8.devices == 8
    assert sharded == single == float(want)
    # a measure with a true division sums floats: not sharded
    frac = FusedSpec("k1", None, (), ("m", "sum"),
                     measure=col("rev") / col("disc"))
    assert not sharded_supported(frac, build, probe)
