#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything
that belongs to it is found by name:

* its configuration, ``bench/configs/<config>.py``: sizes, the seeded
  generator, the query templates, their numpy references and byte work;
* its traffic mix, ``bench/traffic/<config>.<traffic>.json``: templates,
  streams, policy, ``work_mem``, ``total_mem``, ``max_shards``, the traced
  window's length and, for an open loop, the arrival process;
* each per-layer metric, ``bench/metrics/<metric>.py``, a reader with
  ``read(run) -> float | None``.

A run generates the tables from ``--seed``, registers them with a
``QueryServer`` and warms every template (set-up), then serves the mix for
``--seconds``: in a closed loop each stream submits its next query when the
last one returns, cycling through the templates from its own offset.
Latency is taken at the client, from the call of ``QueryServer.submit`` to
the answer in hand.  Every query that completes in the window is compared
with its template's numpy reference once the window has closed.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` serves
a shorter window under the profiler and reports its per-layer metrics.
The last line of standard output is one JSON object; a host whose JAX
finds no TPU, or fewer chips than the cell asks for, exits non-zero
without it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import oracle  # noqa: E402
from arrivals import ArrivalProcess  # noqa: E402
from stats import latency_stats  # noqa: E402

NO_CHIP = 3   # exit code: no TPU, or fewer chips than the cell asks for


# ---------------------------------------------------------------------------
# Finding the pieces of a cell by name
# ---------------------------------------------------------------------------

def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(benchmark: dict, name: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in benchmark['workloads']]}")


def load_config(name: str, bench: Path = BENCH):
    return _load_module(bench / "configs" / f"{name}.py", f"config_{name}")


def load_traffic(config: str, traffic: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{config}.{traffic}.json")
                      .read_text())


def load_reader(metric: str, bench: Path = BENCH):
    return _load_module(bench / "metrics" / f"{metric}.py",
                        f"metric_{metric}").read


def cell_metrics(benchmark: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
    return [m for m in benchmark[kind]
            if cell in m.get("workloads", [cell])]


def peak(kind: str, field: str, bench: Path = BENCH) -> float:
    """A peak of the device ``kind`` from ``peaks.json``; an unknown kind
    is an error, not a default."""
    table = json.loads((bench / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(has {sorted(table)})")
    return float(table[kind][field])


# ---------------------------------------------------------------------------
# What the metric readers read
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class QueryRecord:
    template: str
    latency_s: float        # client side: submit (or arrival) to answer
    op_wall_s: float        # sum of the query's OpMetrics.wall_s
    mem_wait_s: float       # sum of OpMetrics.mem_wait_s
    queue_wait_s: float     # sum of OpMetrics.queue_wait_s
    h2d_bytes: int          # sum of OpMetrics.h2d_bytes (physical)


@dataclasses.dataclass
class Run:
    config: object          # the configuration module
    table_rows: Dict[str, int]
    device_kind: str
    queries: List[QueryRecord]
    window_s: float
    trace: Optional[object] = None     # trace_reduce.TraceSummary, if traced

    def peak(self, field: str) -> float:
        return peak(self.device_kind, field)


# ---------------------------------------------------------------------------
# Serving the window
# ---------------------------------------------------------------------------

def _compile_counter() -> Dict[str, int]:
    """Counts, from now on, the programs JAX builds (compiled, or read from
    its persistent cache) and its cache's hits and misses."""
    import jax

    count = {"programs": 0, "cache_hits": 0, "cache_misses": 0}
    names = {"/jax/core/compile/backend_compile_duration": "programs",
             "/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def listen(event, *_, **__):
        if event in names:
            count[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


def answer_of(result) -> dict:
    """A served ``QueryResult`` as ``{group key: sum}`` (``{"all": s}``)."""
    import numpy as np

    if result.scalar is not None:
        return {"all": float(result.scalar)}
    rel = result.relation
    key, value = rel.names[0], rel.names[1]
    return {int(k): float(v) for k, v in zip(np.asarray(rel[key]).tolist(),
                                             np.asarray(rel[value]).tolist())}


def serve(server, queries: dict, traffic: dict, seconds: float, seed: int,
          annotate) -> tuple:
    """Serve the mix for ``seconds``; returns (records, answers, failed,
    window_s).  Each stream finishes the query it has in flight when the
    time is up; the window runs from the first submission to the last
    completion."""
    templates = list(traffic["templates"])
    streams = int(traffic["streams"])
    lock = threading.Lock()
    records, answers, failed = [], [], []
    ends = []

    def run_one(template: str, t_from: float) -> None:
        try:
            with annotate(f"query:{template}"):
                res = server.submit(queries[template])
            done = time.perf_counter()
            ms = res.metrics
            rec = QueryRecord(
                template=template, latency_s=done - t_from,
                op_wall_s=sum(m.wall_s for m in ms),
                mem_wait_s=sum(m.mem_wait_s for m in ms),
                queue_wait_s=sum(m.queue_wait_s for m in ms),
                h2d_bytes=sum(m.h2d_bytes for m in ms))
            ans = answer_of(res)
            with lock:
                records.append(rec)
                answers.append((template, ans))
                ends.append(done)
        except Exception as e:   # a failed query is data, not a crash
            with lock:
                failed.append((template, f"{type(e).__name__}: {e}"))
                ends.append(time.perf_counter())

    if "arrivals" in traffic:
        arrive = ArrivalProcess(seed=seed, **traffic["arrivals"]).times(
            seconds)
        ready = list(enumerate(arrive.tolist()))

        def stream_loop(stream: int) -> None:
            while True:
                with lock:
                    if not ready:
                        return
                    i, t_off = ready.pop(0)
                wait = t0 + t_off - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                run_one(templates[i % len(templates)], t0 + t_off)
    else:
        def stream_loop(stream: int) -> None:
            k = stream
            while time.perf_counter() < t0 + seconds:
                run_one(templates[k % len(templates)], time.perf_counter())
                k += 1

    threads = [threading.Thread(target=stream_loop, args=(s,), daemon=True)
               for s in range(streams)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    window = (max(ends) if ends else time.perf_counter()) - t0
    return records, answers, failed, window


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float = None, scale: float = 1.0,
             benchmark: dict = None, bench: Path = BENCH) -> dict:
    """One run of a cell; returns the result line as a dictionary.  The
    caller has checked the device: this drives the rest of a run."""
    import jax

    from repro.core import QueryServer, Relation, col

    t_start = time.perf_counter() if t_start is None else t_start
    benchmark = load_benchmark(bench.parent) if benchmark is None \
        else benchmark
    cell = find_cell(benchmark, cell_name)
    cfg = load_config(cell["config"], bench)
    traffic = load_traffic(cell["config"], cell["traffic"], bench)
    devices = jax.devices()
    dev = devices[0]

    # -- set-up: data, server, warm-up ------------------------------------
    tables = cfg.generate(seed, scale)
    table_rows = {t: len(next(iter(c.values()))) for t, c in tables.items()}
    server = QueryServer({t: Relation(dict(c)) for t, c in tables.items()},
                         total_mem=int(traffic["total_mem"]),
                         work_mem=int(traffic["work_mem"]),
                         policy=traffic["policy"],
                         max_shards=int(traffic["max_shards"]))
    queries = {t: cfg.build(t, server.session, col)
               for t in traffic["templates"]}
    warm_failures = []
    compiles = _compile_counter()
    for t in traffic["templates"]:
        try:
            server.submit(queries[t])
        except Exception as e:   # the window will count it as failed too
            warm_failures.append((t, f"{type(e).__name__}: {e}"))
    compiles_in_setup = dict(compiles)
    setup_s = time.perf_counter() - t_start

    # -- the window --------------------------------------------------------
    logdir = None
    if trace:
        seconds = min(seconds, float(traffic["trace_seconds"]))
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    else:
        import contextlib

        def annotate(_name):
            return contextlib.nullcontext()
    programs_before = compiles["programs"]
    records, answers, failed, window_s = serve(
        server, queries, traffic, seconds, seed, annotate)
    compiles_in_window = compiles["programs"] - programs_before
    summary = None
    if trace:
        import trace_reduce

        jax.profiler.stop_trace()
        summary = trace_reduce.summarize(
            trace_reduce.load(trace_reduce.find_xplane(logdir)))
        shutil.rmtree(logdir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")

    # -- free the program's state, then the reference ----------------------
    del server, queries
    gc.collect()
    references = cfg.references(sorted({t for t, _ in answers}), tables)
    checks = oracle.compare(answers, references, unanswered=len(failed))
    correct = oracle.is_correct(checks, len(answers))

    # -- metrics -----------------------------------------------------------
    run = Run(config=cfg, table_rows=table_rows,
              device_kind=dev.device_kind, queries=records,
              window_s=window_s, trace=summary)
    metrics = {}
    if not trace:
        lat = latency_stats([r.latency_s for r in records]) if records \
            else None
        values = {"setup_s": setup_s,
                  "query_p50_s": lat.p50 if lat else None,
                  "query_p95_s": lat.p95 if lat else None,
                  "throughput_qps": len(records) / window_s
                  if window_s > 0 else None}
        for m in cell_metrics(benchmark, cell_name, "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell_metrics(benchmark, cell_name, "per_layer"):
            value = load_reader(m["name"], bench)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(records) + len(failed),
           "failed": len(failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["info"] = {"queries": len(records), "window_s": window_s,
                   "compiles_in_setup": compiles_in_setup,
                   "compiles_in_window": compiles_in_window,
                   "failures": failed[:5], "warm_up_failures": warm_failures,
                   "per_template": _per_template(records)}
    out["checks"] = checks
    return out


def _per_template(records) -> dict:
    by: Dict[str, list] = {}
    for r in records:
        by.setdefault(r.template, []).append(r.latency_s)
    return {t: {"n": len(v), "mean_s": sum(v) / len(v)}
            for t, v in sorted(by.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    benchmark = load_benchmark()
    cell = find_cell(benchmark, args.workload)
    # the compile cache lives at a fixed path inside this checkout, with no
    # size limit: an evicting cache drops the largest programs first, and
    # those are the ones that take minutes to compile
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import repro.core  # noqa: F401  (fails where the program is absent)

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: JAX found platform {devices[0].platform!r}, not a "
              f"TPU; no result", file=sys.stderr)
        return NO_CHIP
    if len(devices) < int(cell["chips"]):
        print(f"run.py: {args.workload} needs {cell['chips']} chips, JAX "
              f"sees {len(devices)}; no result", file=sys.stderr)
        return NO_CHIP
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, benchmark=benchmark)
    print(json.dumps(out["info"]), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
