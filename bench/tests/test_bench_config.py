"""The benchmark's files: BENCHMARK.json's contract, the generators' key
rules, the byte-work functions and the peaks table."""
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import datagen  # noqa: E402
import run  # noqa: E402

BENCHMARK = run.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SCALE = 0.002


@pytest.fixture(scope="module")
def ssb():
    cfg = run.load_config("ssb_sf1")
    return cfg, cfg.generate(2**33 + 7, SCALE)


@pytest.fixture(scope="module")
def tpch():
    cfg = run.load_config("tpch_sf1")
    return cfg, cfg.generate(2**33 + 7, SCALE)


def test_benchmark_json_keys_and_names():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCHMARK[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert [m["name"] for m in BENCHMARK["end_to_end"]] == [
        "query_p50_s", "query_p95_s", "throughput_qps", "setup_s"]
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_piece_of_every_cell_is_found_by_name():
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for cell in BENCHMARK["workloads"]:
        assert cell["chips"] in (1, 4)
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        cfg = run.load_config(cell["config"])
        traffic = run.load_traffic(cell["config"], cell["traffic"])
        assert set(traffic["templates"]) <= set(cfg.TEMPLATES)
        layer = run.cell_metrics(BENCHMARK, cell["name"], "per_layer")
        assert layer, cell["name"]
        for m in layer:
            assert callable(run.load_reader(m["name"]))
            assert m["moves"] in e2e
    for c in BENCHMARK["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16


@pytest.mark.parametrize("cfg_name", ["ssb_sf1", "tpch_sf1"])
def test_generator_is_seeded_and_sizes_do_not_depend_on_the_seed(cfg_name):
    cfg = run.load_config(cfg_name)
    a, b, c = (cfg.generate(s, SCALE) for s in (1, 1, 2**32 + 3))
    for t in a:
        for col in a[t]:
            assert np.array_equal(a[t][col], b[t][col])
            assert len(a[t][col]) == len(c[t][col]) == cfg.sizes(SCALE)[t]
            assert a[t][col].dtype == np.int64
    assert cfg.sizes(1.0) == cfg.SIZES


def test_ssb_foreign_keys_in_range(ssb):
    cfg, t = ssb
    lo = t["lineorder"]
    for key, dim in (("custkey", "customer"), ("suppkey", "supplier"),
                     ("partkey", "part"), ("datekey", "date")):
        assert np.isin(lo[key], t[dim][key]).all(), key
        assert len(np.unique(t[dim][key])) == len(t[dim][key])
    assert lo["datekey"].max() <= 19980802
    assert cfg.SIZES["lineorder"] == 6_001_215
    assert (lo["lo_revenue"] == lo["lo_extendedprice"]
            * (100 - lo["lo_discount"]) // 100).all()


def test_tpch_key_rules(tpch):
    cfg, t = tpch
    li, ps, orders = t["lineitem"], t["partsupp"], t["orders"]
    # every lineitem (partkey, suppkey) is one of its part's four suppliers
    n_supp = int(ps["ps_suppkey"].max())
    s = ps["ps_suppkey"].reshape(-1, 4)
    p = ps["ps_partkey"].reshape(-1, 4)[:, 0]
    for i in range(4):
        assert (s[:, i] == (p + i * (n_supp // 4 + (p - 1) // n_supp))
                % n_supp + 1).all()
    assert np.isin(li["pskey"], ps["pskey"]).all()
    assert (li["pskey"] >> 32 == li["l_partkey"]).all()
    assert (li["pskey"] & 0xFFFFFFFF == li["l_suppkey"]).all()
    assert len(np.unique(ps["pskey"])) == len(ps["pskey"])
    # sparse order keys: 8 used of every 32
    assert ((orders["orderkey"] - 1) % 32 < 8).all()
    assert np.isin(li["orderkey"], orders["orderkey"]).all()
    assert (orders["o_custkey"] % 3 != 0).all()


def test_tpch_full_scale_keys_are_distinct():
    """At scale factor 1 the formula gives every part four distinct
    suppliers, so partsupp's packed keys are unique."""
    p = np.arange(1, 200_001)
    s = np.sort(np.stack([datagen.partsupp_suppkey(p, i, 10_000)
                          for i in range(4)], axis=1), axis=1)
    assert (s[:, 1:] != s[:, :-1]).all()
    assert s.min() == 1 and s.max() == 10_000


@pytest.mark.parametrize("cfg_name", ["ssb_sf1", "tpch_sf1"])
def test_byte_work_depends_only_on_sizes_and_template(cfg_name):
    cfg = run.load_config(cfg_name)
    full = cfg.sizes(1.0)
    for t in cfg.TEMPLATES:
        qb, kb = cfg.query_bytes(t, full), cfg.kernel_bytes(t, full)
        # the same from a second dict of the same sizes, and it grows
        # with the tables
        assert cfg.query_bytes(t, dict(full)) == qb
        assert cfg.query_bytes(t, {k: 2 * v for k, v in full.items()}) > qb
        assert qb > 8 * min(full.values())
        assert kb >= 0
    if cfg_name == "tpch_sf1":
        assert cfg.query_bytes("Q9.ps_join", full) == \
            8 * 6_001_215 + 16 * 800_000 + 8
    else:
        assert cfg.kernel_bytes("Q1.1", full) == 0
        assert cfg.kernel_bytes("Q2.1", full) == 4 * 2_000 + 8 * 6_001_215


def test_peaks_table_and_unknown_kind():
    assert run.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert run.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        run.peak("cpu", "hbm_bytes_per_s")
    assert json.loads((BENCH / "peaks.json").read_text())["source"]
