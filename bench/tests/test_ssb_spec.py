"""SSB SF1 at the spec's query shapes (``configs/ssb_sf1_spec.py``): every
template through ``QueryServer`` equals its numpy reference under each
policy, composite answers are keyed by tuples, the float32 control fails,
and the per-query kernel-time reader reads a synthetic trace."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import control  # noqa: E402
import oracle  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

SCALE = 0.002
SEED = 2**33 + 15
SPEC = run.load_config("ssb_sf1_spec")
S = 1e9   # nanoseconds per second


@pytest.fixture(scope="module")
def tables():
    return SPEC.generate(SEED, SCALE)


@pytest.mark.parametrize("policy", ["linear", "tensor", "auto"])
@pytest.mark.parametrize("template", SPEC.TEMPLATES)
def test_spec_template_through_query_server_equals_reference(
        tables, template, policy):
    from repro.core import QueryServer, Relation, col

    server = QueryServer({t: Relation(dict(c)) for t, c in tables.items()},
                         total_mem=8 << 20, work_mem=4 << 20, policy=policy)
    res = server.submit(SPEC.build(template, server.session, col))
    got = SPEC.answer_of(res)
    want = SPEC.references([template], tables)[template]
    assert len(want) >= 1
    assert oracle.answer_diff(got, want) == 0
    if template != "Q1.1":
        # one column per key, in the query's order, then the measure
        assert len(res.relation.names) == SPEC._KEYS[template] + 1
        assert all(isinstance(k, tuple) and len(k) == SPEC._KEYS[template]
                   for k in got)


def test_spec_references_group_by_the_spec_keys(tables):
    refs = SPEC.references(SPEC.TEMPLATES, tables)
    lo = tables["lineorder"]
    assert set(refs["Q1.1"]) == {"all"}
    assert refs["Q1.1"]["all"] > 0
    assert 0 < len(refs["Q2.1"]) <= SPEC._GROUPS["Q2.1"]
    years = {k[0] for k in refs["Q2.1"]}
    assert years <= set(range(1992, 1999))
    assert {k[2] for k in refs["Q3.1"]} <= set(range(1992, 1998))
    # Q4.1's profit: revenue less supply cost, summed over its groups
    total = sum(refs["Q4.1"].values())
    assert total != sum(SPEC._BASE.references(["Q4.1"], tables)["Q4.1"]
                        .values())
    assert np.issubdtype(lo["lo_supplycost"].dtype, np.integer)


def test_grouped_reference_keys_by_tuples():
    got = SPEC.grouped([np.array([1, 1, 2, 1]), np.array([5, 6, 5, 5])],
                       np.array([10, 20, 30, 40]), oracle.exact_segment_sum)
    assert got == {(1, 5): 50.0, (1, 6): 20.0, (2, 5): 30.0}


class _Result:
    def __init__(self, relation=None, scalar=None):
        self.relation, self.scalar = relation, scalar


def test_answer_of_keys_composite_groups_by_tuples():
    from repro.core import Relation

    rel = Relation({"b_d_year": np.array([1993, 1994]),
                    "b_c_nation": np.array([3, 7]),
                    "sum_profit": np.array([1.5, 2.0])})
    assert SPEC.answer_of(_Result(rel)) == {(1993, 3): 1.5, (1994, 7): 2.0}
    # one key and a scalar keep the harness's form
    one = Relation({"k": np.array([4, 5]), "sum_v": np.array([1.0, 2.0])})
    assert SPEC.answer_of(_Result(one)) == run.answer_of(_Result(one)) == \
        {4: 1.0, 5: 2.0}
    assert SPEC.answer_of(_Result(scalar=3)) == \
        run.answer_of(_Result(scalar=3)) == {"all": 3.0}


def test_float32_control_fails_on_the_spec_shapes():
    checks = control.control_checks(SPEC, SPEC.TEMPLATES,
                                    SPEC.generate(SEED, 0.01))
    assert not oracle.is_correct(checks, 1)
    assert checks["max_abs_diff"]["value"] > 0


def test_spec_byte_work_reads_the_measure_and_key_columns():
    full = SPEC.sizes(1.0)
    base = SPEC._BASE
    # Q1.1 reads extendedprice in place of revenue: the same bytes
    assert SPEC.query_bytes("Q1.1", full) == base.query_bytes("Q1.1", full)
    # Q3.1 also reads s_nation and d_year; Q4.1 also lo_supplycost, d_year
    assert SPEC.query_bytes("Q3.1", full) > base.query_bytes("Q3.1", full)
    assert SPEC.query_bytes("Q4.1", full) - base.query_bytes("Q4.1", full) \
        == 8 * (6_001_215 + 2_556) + 8 * 3 * 35 - 16 * 5
    for t in SPEC.TEMPLATES:
        assert SPEC.kernel_bytes(t, full) == base.kernel_bytes(t, full)


def _summary(with_kernel: bool):
    spans = [("query:Q2.1", 0, 4 * S), ("query:Q3.1", 4 * S, 8 * S)]
    ops = [("fusion.1", 0.5 * S, 2 * S, 0)]
    if with_kernel:
        ops += [("radix_hash_probe.3 custom-call (s32[8388608], "
                 "s32[8388608]) tpu_custom_call", 2 * S, 2.25 * S, 0),
                ("radix_partition.1 custom-call (s32[8388608], s32[128]) "
                 "tpu_custom_call", 5 * S, 5.5 * S, 0),
                ("while.2 while (u32[], s32[8388608])", 6 * S, 7 * S, 0)]
    return tr.summarize(tr.Trace(ops=ops, spans=spans))


def test_kernel_ms_per_query_reader():
    read = run.load_reader("kernels.segment_join_ms_per_query")
    r = run.Run(config=SPEC, table_rows={}, device_kind="TPU v5 lite",
                queries=[], window_s=8.0, trace=_summary(True))
    # 0.25 s + 0.5 s of Pallas calls over two traced queries
    assert read(r) == pytest.approx(375.0)
    for trace in (None, _summary(False)):
        r.trace = trace
        assert read(r) is None
