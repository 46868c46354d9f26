"""Host spans, device scopes and per-query counters (``repro.core.tracing``).

A TPC-H-shaped fused query (lineitem ⋈ partsupp on a packed composite key,
summing the supply cost) at toy scale is served under the profiler; its
``rel.*`` spans must land on the query's thread, nested in ``rel.query``.
The fused and per-operator programs' lowered HLO must carry the scope
names, and ``QueryResult.trace`` must count fresh programs and retries.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Aggregate, Executor, Join, QueryResult, QueryServer,
                        QueryTrace, Relation, Scan, Session, Sort, col,
                        tracing)
from repro.core import aggregate as aggregate_mod
from repro.core import fused
from repro.core import tensor_engine as te

HOST_SPANS = ("rel.plan", "rel.host_prep", "rel.dispatch", "rel.fetch",
              "rel.assemble")


def _tpch_tables(seed: int, n_parts: int = 300, n_lines: int = 6000,
                 prefix: str = ""):
    """lineitem and partsupp with TPC-H's key shape: four suppliers per
    part, the composite (partkey, suppkey) packed into one int64 column."""
    rng = np.random.default_rng(seed)
    ps_part = np.repeat(np.arange(1, n_parts + 1, dtype=np.int64), 4)
    ps_supp = (ps_part + np.tile(np.arange(4), n_parts) * 25) % 100 + 1
    partkey = rng.integers(1, n_parts + 1, n_lines)
    suppkey = (partkey + rng.integers(0, 4, n_lines) * 25) % 100 + 1
    key = f"{prefix}pskey"
    partsupp = {key: (ps_part << 32) | ps_supp,
                "ps_supplycost": rng.integers(100, 100_001, len(ps_part))}
    lineitem = {key: (partkey << 32) | suppkey,
                "l_quantity": rng.integers(1, 51, n_lines)}
    return ({"lineitem": Relation(lineitem), "partsupp": Relation(partsupp)},
            key)


def _q9(session, key):
    return (session.table("lineitem").join("partsupp", on=key)
            .aggregate("b_ps_supplycost", "sum"))


def _events(logdir):
    """``(line, name, start, end)`` of every ``rel.`` event on the host."""
    from jax.profiler import ProfileData

    import glob
    import os

    path = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("rel."):
                    out.append((i, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def test_spans_of_a_served_fused_query_nest_in_rel_query(tmp_path):
    tables, key = _tpch_tables(5, prefix="span_")
    server = QueryServer(tables, total_mem=8 << 20, work_mem=4 << 20,
                         policy="tensor")
    q = _q9(server.session, key)
    expected = server.submit(q).scalar   # compiles: the traced run is warm
    answers = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        worker = threading.Thread(
            target=lambda: answers.append(server.submit(q)))
        worker.start()
        worker.join()
    finally:
        jax.profiler.stop_trace()
    assert answers[0].scalar == expected
    events = _events(tmp_path)
    queries = [e for e in events if e[1] == "rel.query"]
    assert len(queries) == 1
    line, _, q_start, q_end = queries[0]
    inside = {name for ln, name, s, e in events
              if ln == line and q_start <= s and e <= q_end}
    assert set(HOST_SPANS) <= inside, inside
    # every span of the query is on the query's thread
    assert {ln for ln, *_ in events} == {line}
    # the same spans are in the query's own record, no profiler needed
    assert set(HOST_SPANS) <= set(answers[0].trace.seconds)


def test_fresh_programs_counted_first_run_then_warm():
    tables, key = _tpch_tables(7, prefix="fresh_")
    sess = Session(work_mem=4 << 20, policy="tensor")
    for name, rel in tables.items():
        sess.register(name, rel)
    first = _q9(sess, key).collect()
    second = _q9(sess, key).collect()
    assert first.scalar == second.scalar
    assert (first.trace.fresh_programs, first.trace.dispatches) == (1, 1)
    assert "rel.compile" in first.trace.seconds
    assert "rel.h2d" in first.trace.seconds      # the tables were cold
    assert (second.trace.fresh_programs, second.trace.dispatches) == (0, 1)
    assert "rel.compile" not in second.trace.seconds
    assert "rel.h2d" not in second.trace.seconds  # resident: nothing moved
    assert second.trace.retries == 0
    assert second.trace.ms("rel.plan", "rel.select") > 0


def _dup_tables(seed: int, prefix: str):
    """The TPC-H tables with every partsupp row listed twice."""
    tables, key = _tpch_tables(seed, prefix=prefix)
    ps = tables["partsupp"]
    tables["partsupp"] = Relation({n: np.concatenate([ps[n], ps[n]])
                                   for n in ps.names})
    return tables, key


@pytest.mark.parametrize("tables,joins", [(_tpch_tables, 1),
                                          (_dup_tables, 0)])
def test_probe_order_joins_count_unique_builds(tables, joins):
    """A build key the sample finds unique (partsupp's packed primary key)
    runs the probe-order core once; a duplicated build runs the expanding
    core and counts nothing."""
    tables, key = tables(11, prefix=f"po{joins}_")
    sess = Session(work_mem=4 << 20, policy="tensor")
    for name, rel in tables.items():
        sess.register(name, rel)
    res = _q9(sess, key).collect()
    assert (res.trace.probe_order_joins, res.trace.dispatches,
            res.trace.retries) == (joins, 1, 0)


def test_capacity_overflow_counts_one_retry():
    """The key sample sees unique keys, the tail repeats one key: the
    optimistic capacity overflows and the fragment re-runs once.  The keys
    are sparse, so the sorted core runs from the start."""
    rng = np.random.default_rng(13)
    n = 70000
    bk = np.arange(n, dtype=np.int64) * 1000
    bk[65536:65736] = 1000
    build = Relation({"k": bk, "v": rng.integers(0, 9, n)})
    probe = Relation({"k": np.full(4096, 1000, np.int64),
                      "w": rng.integers(0, 9, 4096)})
    plan = Aggregate(Sort(Join(Scan(build), Scan(probe), "k"), ["k"]),
                     "b_v", "sum")
    # without guards: a guard could hand the overflow to the generic walk
    res = Executor(work_mem=1 << 30, policy="tensor",
                   guards=False).execute(plan)
    assert [m.op for m in res.metrics] == ["fused_pipeline"]
    assert res.trace.retries == 1
    assert res.trace.dispatches == 2
    assert res.metrics[0].host_syncs == 2


def test_generic_walk_names_each_operator():
    rng = np.random.default_rng(3)
    sess = Session(work_mem=1 << 20, policy="tensor")
    sess.register("l", {"k": rng.integers(0, 50, 400),
                        "q": rng.integers(0, 9, 400)})
    sess.register("p", {"k": np.arange(50), "c": rng.integers(0, 9, 50)})
    res = (sess.table("l").join("p", on="k").sort("k")
           .group_by("k", {"q": "sum"}).collect())
    names = set(res.trace.seconds)
    assert {"rel.op.join", "rel.op.sort", "rel.op.group_by",
            "rel.op.materialize"} <= names, names
    assert res.trace.dispatches == len(res.metrics)
    # operators nest: the group-by's span holds its input's sort
    assert (res.trace.seconds["rel.op.group_by"]
            >= res.trace.seconds["rel.op.sort"])


def test_query_records_nest_and_close():
    assert tracing.current() is None
    with tracing.query() as outer:
        with tracing.span("rel.plan"):
            pass
        with tracing.query() as inner:
            assert inner is outer
            tracing.count(retries=2, dispatches=1)
        assert tracing.current() is outer
    assert tracing.current() is None
    assert outer.retries == 2 and outer.dispatches == 1
    assert set(outer.seconds) == {"rel.plan", "rel.query"}
    assert outer.seconds["rel.query"] >= outer.seconds["rel.plan"]
    # outside a query a span still annotates, and counts go nowhere
    with tracing.span("rel.plan"):
        tracing.count(dispatches=1)
    with pytest.raises(RuntimeError):
        with tracing.query():
            raise RuntimeError("boom")
    assert tracing.current() is None


def test_result_built_outside_a_query_has_an_empty_trace():
    res = QueryResult(None, 1.0, [], [])
    assert res.trace == QueryTrace()
    assert res.trace.ms("rel.query") == 0.0


def _lowered(dense_domain=None, use_kernel=False, probe_order=False,
             dialect=None):
    spec = fused.FusedSpec("k", None, (), ("b_v", "sum"))
    prog = fused._build_program(spec, "k", 64, dense_domain=dense_domain,
                                use_kernel=use_kernel,
                                probe_order=probe_order)
    keys = jnp.arange(32, dtype=jnp.int64)
    bcols = {"k": keys, "v": keys}
    pcols = {"k": jnp.concatenate([keys, keys]), "w": jnp.zeros(64, int)}
    return prog.lower(bcols, pcols, {}, {}, {}, {}, 32, 64,
                      0).as_text(dialect=dialect, debug_info=True)


@pytest.mark.parametrize("core,scopes", [
    ("sorted", ("join.sorted.sort", "join.sorted.search", "join.prefix_sum",
                "join.expand", "aggregate")),
    ("dense", ("join.dense.build", "join.dense.probe", "join.prefix_sum",
               "join.expand")),
    ("pallas", ("join.dense.pallas", "join.prefix_sum", "join.expand")),
])
def test_fused_program_carries_scope_names(core, scopes):
    text = _lowered(dense_domain=None if core == "sorted" else 32,
                    use_kernel=core == "pallas")
    for scope in scopes:
        assert f"jit(program)/{scope}/" in text, scope
    if core == "sorted":
        assert "join.dense" not in text


@pytest.mark.parametrize("core", ["sorted", "dense", "pallas"])
@pytest.mark.parametrize("probe_order", [False, True])
def test_probe_order_core_scatters_nothing_in_its_expansion(core,
                                                             probe_order):
    """The probe-order form of each join core keeps its one lookup per
    probe row under ``join.expand`` and scatters nothing there; the
    expanding form scatters each matched probe row to its first slot."""
    text = _lowered(dense_domain=None if core == "sorted" else 32,
                    use_kernel=core == "pallas", probe_order=probe_order,
                    dialect="hlo")
    expand = [ln for ln in text.splitlines()
              if 'op_name="jit(program)/join.expand/' in ln]
    assert expand
    assert any(" scatter(" in ln for ln in expand) is not probe_order


def test_pallas_probe_carries_its_stage_scopes():
    """Inside ``join.dense.pallas`` the radix probe names its stages: the
    radix partition of each side, the table build, the table probe and
    the gather back to row order."""
    text = _lowered(dense_domain=32, use_kernel=True)
    for scope in ("pallas.partition.build", "pallas.partition.probe",
                  "pallas.table.build", "pallas.table.probe",
                  "pallas.gather"):
        assert f'"{scope}/' in text, scope
    assert "jit(program)/join.dense.pallas/jit(radix_hash_probe)" in text


def test_per_operator_programs_carry_scope_names():
    keys = jnp.arange(16, dtype=jnp.int64)
    assert "op.join/" in te._join_plan.lower(keys, keys).as_text(
        debug_info=True)
    assert "op.sort/" in te._multikey_perm.lower(
        (keys,), None, num_keys=1).as_text(debug_info=True)
    group = aggregate_mod._group_program_jit()
    text = group.lower((keys,), None, {"k": keys}, (("k", "sum", None),),
                       16, (False,)).as_text(debug_info=True)
    assert "op.group_by/" in text
    # several keys and a computed measure
    measure = aggregate_mod.Measure("m", col("k") * 2)
    text = group.lower((keys, keys), None, {"k": keys},
                       (("m", "sum", measure),), 16,
                       (False,)).as_text(debug_info=True)
    assert "op.group_by/measure/" in text


def _instructions(text):
    """Compiled HLO without metadata and instruction numbers."""
    import re

    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    lines = [ln for ln in text.splitlines()
             if ln.lstrip().startswith(("%", "ROOT", "ENTRY", "}"))]
    return sorted(re.sub(r"([%\w-]+)\.\d+\b", r"\1", ln) for ln in lines)


@pytest.mark.parametrize("reducer,reference", [
    (jax.lax.add, jnp.cumsum), (jax.lax.max, jax.lax.cummax)])
def test_prefix_equals_the_cumulative_primitive_and_keeps_the_scope(
        reducer, reference):
    x = jnp.asarray(np.random.default_rng(0).integers(-50, 50, 4096))
    assert (fused._prefix(x, reducer) == reference(x)).all()

    def scoped(fn):
        def body(v):
            with jax.named_scope("join.prefix_sum"):
                return fn(v)
        return jax.jit(body).lower(x).compile().as_text()

    ours = scoped(lambda v: fused._prefix(v, reducer))
    theirs = scoped(reference)
    assert "join.prefix_sum/reduce_window" in ours
    assert "join.prefix_sum/reduce_window" not in theirs
    assert _instructions(ours) == _instructions(theirs)
