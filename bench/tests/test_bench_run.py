"""Whole runs of the harness on the CPU at a tiny scale: every template
equals its reference through ``QueryServer``, a cell added from new files
alone is found and run, planted faults make ``correct`` false, the control
fails, and ``run.py`` refuses a host without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import control  # noqa: E402
import oracle  # noqa: E402
import run  # noqa: E402

SCALE = 0.002
SEED = 2**33 + 11
#: the SSB cell is not in BENCHMARK.json while the Pallas probe returns
#: wrong rows on the chip (PERF.md, Open questions); on the CPU it runs
SSB_CELL = {"name": "ssb_sf1.flights", "config": "ssb_sf1",
            "traffic": "flights", "chips": 1, "why": "CPU tests"}
CELLS = [c["name"] for c in run.load_benchmark()["workloads"]] + \
    [SSB_CELL["name"]]
TEMPLATES = [(c, t) for c in ("ssb_sf1", "tpch_sf1")
             for t in run.load_config(c).TEMPLATES]


def _benchmark():
    benchmark = run.load_benchmark()
    benchmark["workloads"].append(SSB_CELL)
    return benchmark


def _run_cell(cell, **kw):
    kw.setdefault("scale", SCALE)
    return run.run_cell(cell, SEED, kw.pop("seconds", 0.5),
                        kw.pop("trace", False), benchmark=_benchmark(), **kw)


@pytest.mark.parametrize("cfg_name,template", TEMPLATES)
def test_template_through_query_server_equals_reference(cfg_name, template):
    from repro.core import QueryServer, Relation, col

    cfg = run.load_config(cfg_name)
    tables = cfg.generate(SEED, SCALE)
    server = QueryServer({t: Relation(dict(c)) for t, c in tables.items()},
                         total_mem=8 << 20, work_mem=4 << 20,
                         policy="tensor")
    got = run.answer_of(server.submit(cfg.build(template, server.session,
                                                col)))
    want = cfg.references([template], tables)[template]
    assert len(want) >= 1
    assert oracle.answer_diff(got, want) == 0


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_the_cpu(cell):
    out = _run_cell(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {"query_p50_s", "query_p95_s",
                                   "throughput_qps", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["info"]["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_traced_run_reports_per_layer_metrics():
    out = _run_cell("tpch_sf1.partsupp_join", trace=True)
    assert out["correct"]
    # the counters are read; the CPU has no TPU plane, so the trace
    # readers find nothing and are left out, not reported as 0
    assert set(out["metrics"]) == {"frontend.host_ms", "broker.wait_pct",
                                   "fused.h2d_mb_per_query"}
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] == 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_new_cell_from_new_files_alone(tmp_path):
    """A later PR adds a traffic mix, a metric and a cell as new files and
    entries only; the harness finds and runs them by name."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (bench / "traffic" / "ssb_sf1.q11_open.json").write_text(json.dumps({
        "templates": ["Q1.1"], "streams": 2, "policy": "tensor",
        "work_mem": 4 << 20, "total_mem": 8 << 20, "max_shards": 1,
        "trace_seconds": 1, "arrivals": {"rate_qps": 20}}))
    (bench / "metrics" / "test.queries.py").write_text(
        "def read(run):\n    return float(len(run.queries))\n")
    benchmark = run.load_benchmark()
    benchmark["workloads"].append({
        "name": "ssb_sf1.q11_open", "config": "ssb_sf1",
        "traffic": "q11_open", "chips": 1, "why": "test"})
    benchmark["per_layer"].append({
        "name": "test.queries", "unit": "queries", "better": "higher",
        "source": "program_counter", "layer": "front end",
        "moves": "throughput_qps", "workloads": ["ssb_sf1.q11_open"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    out = run.run_cell("ssb_sf1.q11_open", SEED, 0.5, True, scale=SCALE,
                       bench=bench)
    assert out["correct"]
    assert out["metrics"]["test.queries"]["value"] == out["info"]["queries"]
    assert out["info"]["queries"] >= 1
    assert set(out["info"]["per_template"]) == {"Q1.1"}


def _break_program(monkeypatch, fault):
    """Break the timed path underneath the harness: the planner's program
    run, which every query of every template goes through."""
    from repro.core import planner

    real = planner.Program.run

    def broken(self, executor):
        res = real(self, executor)
        if fault == "raises":
            raise RuntimeError("planted fault")
        if res.scalar is not None:
            res.scalar = res.scalar + (1.0 if fault == "altered"
                                       else -res.scalar / 2)
        else:
            rel = res.relation
            if fault == "altered":
                col = rel.names[1]
                rel[col][0] = rel[col][0] + 1
            else:
                half = len(rel) // 2
                res.relation = type(rel)({n: rel[n][:half]
                                          for n in rel.names})
        return res

    monkeypatch.setattr(planner.Program, "run", broken)


@pytest.mark.parametrize("fault", ["altered", "half_left_out", "raises"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_fault_makes_the_run_incorrect(monkeypatch, cell, fault):
    _break_program(monkeypatch, fault)
    out = _run_cell(cell)
    assert out["correct"] is False
    if fault == "raises":
        assert out["failed"] >= 1 and out["checks"]["unanswered"]["value"]
    else:
        assert out["checks"]["wrong_answers"]["value"] >= 1
        assert out["checks"]["max_abs_diff"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_fails(cell):
    c = run.find_cell(_benchmark(), cell)
    cfg = run.load_config(c["config"])
    templates = run.load_traffic(c["config"], c["traffic"])["templates"]
    checks = control.control_checks(cfg, templates,
                                    cfg.generate(SEED, 0.01))
    assert not oracle.is_correct(checks, 1)
    assert checks["max_abs_diff"]["value"] > 0


def _cli(cwd, *extra_env):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update({"JAX_PLATFORMS": "cpu", **dict(extra_env)})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "tpch_sf1.partsupp_join",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    return any(line.startswith("{") for line in stdout.splitlines())


def test_run_py_refuses_a_host_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "not a TPU" in p.stderr


def test_run_py_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and bench/ gives no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, ("PYTHONPATH", ""))
    assert p.returncode != 0
    assert not _has_result(p.stdout)
