"""Device time by scope, idle gaps by the engine's innermost span, and the
readers of the engine's spans and counters: on synthetic traces and on a
TPC-H-shaped query traced on the CPU."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
import scopes  # noqa: E402
import trace_reduce as tr  # noqa: E402

S = 1e9   # nanoseconds per second

NEW_READERS = ("planner.plan_ms", "fused.host_prep_ms",
               "fused.join_core_ms_per_query",
               "serving.fresh_programs_per_query")


def synthetic():
    """One query on thread 0 over 10 s: a search loop and a body fusion
    that overlaps it, a prefix sum, an op no scope names; the host waits
    in rel.fetch, with a shorter rel.lease_wait inside it."""
    ops = [("join.sorted.search", 1 * S, 5 * S, 0),       # the while
           ("join.sorted.search", 2 * S, 4 * S, 0),       # its body fusion
           ("join.prefix_sum", 6 * S, 7 * S, 0),
           ("unscoped:reduce_window_sum", 7 * S, 8 * S, 0)]
    spans = [("query:Q9.ps_join", 0, 10 * S, 0),
             ("rel.query", 0, 10 * S, 0),
             ("rel.fetch", 0.5 * S, 9.5 * S, 0),
             ("rel.lease_wait", 5 * S, 5.8 * S, 0),
             ("rel.query", 0, 10 * S, 1)]                 # another thread
    return scopes.ScopedTrace(ops=ops, spans=spans)


def test_scope_seconds_take_the_union_of_nested_ops():
    s = scopes.summarize(synthetic())
    # the while [1, 5] and its body [2, 4] count once: 4 s, not 6 s
    assert s["scope_seconds"]["join.sorted.search"] == pytest.approx(4.0)
    assert s["scope_seconds"]["join.prefix_sum"] == pytest.approx(1.0)
    assert s["busy_s"] == pytest.approx(6.0)
    assert s["unscoped_share"] == pytest.approx(1.0 / 6.0)
    assert s["device_scopes"][0] == ["join.sorted.search",
                                     pytest.approx(4.0)]
    assert s["queries"] == 1
    # the same window and busy time as the trace reduction the accepted
    # metrics read
    plain = tr.summarize(tr.Trace(ops=synthetic().ops,
                                  spans=[sp[:3] for sp in synthetic().spans
                                         if sp[0].startswith("query:")]))
    assert (plain.window_s, plain.busy_s) == (pytest.approx(s["window_s"]),
                                              pytest.approx(s["busy_s"]))


def test_gaps_are_named_by_the_innermost_engine_span():
    s = scopes.summarize(synthetic())
    gaps = dict((k, v) for k, v in s["idle_gaps"])
    # [5, 6] lies in rel.lease_wait (inside rel.fetch): the shorter wins;
    # thread 1's rel.query covers it too
    assert gaps["no query/rel.query+query:Q9.ps_join/rel.lease_wait"] == \
        pytest.approx(1.0)
    # [8, 10] has midpoint 9: rel.fetch on thread 0
    assert "no query/rel.query+query:Q9.ps_join/rel.fetch" in gaps
    # a gap no engine span covers keeps the query spans' label
    assert scopes.label((0, 0.2 * S), [("query:Q1.1", 0, S, 0)]) == \
        "query:Q1.1"
    assert scopes.label((0, 0.2 * S), []) == "no query"


def test_scope_of_an_op_path():
    path = ("jit(program)/join.sorted.search/jit(searchsorted)/vmap()/"
            "while/body/closed_call/gather")
    assert scopes.scope_of(path) == "join.sorted.search"
    # the last component is the primitive: `sort` there is not the stage
    assert scopes.scope_of("jit(program)/join.sorted.sort/sort") == \
        "join.sorted.sort"
    assert scopes.scope_of("jit(program)/sort/jit(_take)/gather") == "sort"
    assert scopes.scope_of("jit(program)/aggregate/gather/add") == "gather"
    assert scopes.scope_of("reduce_window_sum") == \
        "unscoped:reduce_window_sum"
    assert scopes.scope_of("jit(program)/while:while") == "unscoped:while"
    assert scopes.scope_of("", fallback="fusion") == "unscoped:fusion"


def test_op_path_of_an_event_from_its_program():
    program = {"while.8": "jit(program)/join.sorted.search/while",
               "copy.3": ""}
    tpu = ('%while.8 = (u32[], s32[8388608]{0}) while(%tuple.2), '
           'condition=%region_4')
    assert scopes.op_path(tpu, program) == \
        "jit(program)/join.sorted.search/while"
    assert scopes.op_path("while.8", program).endswith("/while")   # CPU
    assert scopes.op_path("copy.3", program) == ""
    assert scopes.op_path("fusion.9", program) == ""
    assert scopes.opcode(tpu) == "while"
    assert scopes.opcode("wrapped_reduce-window.1") == "wrapped_reduce-window"


class _Event:
    def __init__(self, start, stats=()):
        self.start_ns = start
        self.stats = list(stats)


def test_program_of_an_event():
    programs = {"jit_program(7)": {"a": "x"}, "jit_program(9)": {"a": "y"}}
    runs = [(0, 10, "jit_program(7)"), (20, 30, "jit_program(9)")]
    # on the TPU the module run that covers the op names its program
    assert scopes._program_of(_Event(25), runs, programs) == {"a": "y"}
    # on the CPU the op's own stats do
    ev = _Event(15, [("hlo_module", "jit_program"), ("program_id", 7)])
    assert scopes._program_of(ev, [], programs) == {"a": "x"}
    assert scopes._program_of(_Event(15), runs, programs) == {}


def _field(number, payload):
    """One length-delimited protobuf field."""
    out, size = bytearray([number << 3 | 2]), len(payload)
    while True:
        out.append(size & 0x7F | (0x80 if size > 0x7F else 0))
        size >>= 7
        if not size:
            return bytes(out) + payload


def test_hlo_op_names_from_wire_format():
    inst = _field(1, b"while.8") + _field(7, _field(2, b"jit(p)/join.x/w"))
    hlo = _field(1, _field(3, _field(2, inst)))            # HloProto
    stat_md = _field(5, b"\x08\x04" + _field(2, _field(2, b"Hlo Proto")))
    stat = b"\x08\x04" + _field(6, hlo)
    event_md = _field(4, b"\x08\x01" + _field(2, _field(2, b"jit_p(1)")
                                              + _field(5, stat)))
    plane = _field(2, b"/host:metadata") + stat_md + event_md
    other = _field(2, b"/host:CPU") + event_md
    raw = _field(1, other) + _field(1, plane)
    assert scopes.hlo_op_names(raw) == {"jit_p(1)": {"while.8":
                                                     "jit(p)/join.x/w"}}
    assert scopes.hlo_op_names(b"") == {}


def test_engine_spans_of_a_query_traced_on_the_cpu(tmp_path):
    import jax
    import numpy as np

    from repro.core import QueryServer, Relation, col

    cfg = run.load_config("tpch_sf1")
    tables = cfg.generate(2**33 + 3, 0.002)
    server = QueryServer({t: Relation(dict(c)) for t, c in tables.items()},
                         total_mem=8 << 20, work_mem=4 << 20,
                         policy="tensor")
    q = cfg.build("Q9.ps_join", server.session, col)
    server.submit(q)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("query:Q9.ps_join"):
        res = server.submit(q)
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    t = scopes.load(path)
    names = {n for n, *_ in t.spans}
    assert {"query:Q9.ps_join", "rel.query", "rel.plan", "rel.host_prep",
            "rel.dispatch", "rel.fetch", "rel.assemble"} <= names
    assert len({th for *_, th in t.spans}) == 1     # all on one thread
    assert t.ops == []                              # no TPU plane here
    s = scopes.summarize(t)
    assert s["queries"] == 1 and s["busy_s"] == 0
    assert all("/rel." in k for k, _ in s["idle_gaps"])
    assert np.isfinite(res.scalar)
    # on the CPU the XLA threads of the host plane stand in for a device:
    # the join core's ops are found under their scopes
    cpu = scopes.summarize(scopes.load(path, device_plane=tr.HOST_PLANE,
                                       op_line="tf_XLA"))
    assert cpu["scope_seconds"].get("join.sorted.search", 0) > 0
    assert cpu["scope_seconds"].get("join.expand", 0) > 0


class _Trace:
    """What a query's ``QueryResult.trace`` holds."""

    def __init__(self, seconds, fresh_programs=0):
        self.seconds = seconds
        self.fresh_programs = fresh_programs


def _run(traces, summary=None):
    qs = []
    for t in traces:
        q = run.QueryRecord("Q9.ps_join", latency_s=20.0, op_wall_s=19.0,
                            mem_wait_s=0.0, queue_wait_s=10.0, h2d_bytes=0)
        if t is not None:
            q.trace = t
        qs.append(q)
    return run.Run(config=None, table_rows={}, device_kind="TPU v5 lite",
                   queries=qs, window_s=60.0, trace=summary)


def _with_scopes(queries, scope_seconds):
    s = tr.summarize(tr.Trace(ops=[], spans=[]))
    s.queries = queries
    s.scope_seconds = scope_seconds
    return s


def test_counter_readers_of_query_traces():
    traces = [_Trace({"rel.plan": 2e-4, "rel.select": 1e-4,
                      "rel.host_prep": 5e-4, "rel.h2d": 4e-4,
                      "rel.assemble": 1e-4}, fresh_programs=1),
              _Trace({"rel.plan": 4e-4, "rel.host_prep": 3e-4})]
    r = _run(traces)
    assert run.load_reader("planner.plan_ms")(r) == pytest.approx(0.35)
    # rel.h2d lies inside rel.host_prep and is not added again
    assert run.load_reader("fused.host_prep_ms")(r) == pytest.approx(0.45)
    assert run.load_reader("serving.fresh_programs_per_query")(r) == \
        pytest.approx(0.5)
    assert run.load_reader("serving.fresh_programs_per_query")(
        _run([_Trace({}), _Trace({})])) == 0


def test_join_core_reader_sums_join_scopes_per_query():
    summary = _with_scopes(["Q9.ps_join"] * 3,
                           {"join.sorted.search": 26.2,
                            "join.prefix_sum": 2.3, "decode": 0.5,
                            "unscoped:reduce_window_sum": 0.4})
    r = _run([_Trace({})], summary)
    assert run.load_reader("fused.join_core_ms_per_query")(r) == \
        pytest.approx(1e3 * 28.5 / 3)


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_give_no_reading_without_their_input(metric):
    """No queries, query records without traces (the harness as it is),
    a trace summary without scope seconds: no reading, never 0."""
    read = run.load_reader(metric)
    plain = tr.summarize(tr.Trace(ops=[], spans=[]))
    assert read(_run([])) is None
    assert read(_run([None, None], plain)) is None
    if metric == "fused.join_core_ms_per_query":
        assert read(_run([_Trace({})], plain)) is None
        assert read(_run([_Trace({})], _with_scopes([], {"join.x": 1.0}))) \
            is None
