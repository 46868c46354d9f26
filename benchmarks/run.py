"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (common.emit).  Results feed
EXPERIMENTS.md §Repro.  ``--only fig1,headline`` runs a subset; ``--fast``
trims repetition counts for CI-style smoke runs.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import pathlib
import sys
import time


def _ensure_device_mesh() -> None:
    """Give the benchmarks the same 8-way forced host mesh the test suite
    gets from tests/conftest.py (fig15 shards over it).  Must run before
    jax initializes, which is why `from .figures import ALL` stays inside
    main(); a user-provided XLA_FLAGS is always respected."""
    if "jax" in sys.modules:
        return  # too late to influence device discovery; leave it alone
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed threaded into workload/arrival/fault "
                         "generation (benchmarks that accept one); recorded "
                         "in the summary so a run can be replayed exactly")
    # default is NOT results/bench_summary.json: that file is the committed
    # p50 baseline benchmarks/compare.py gates against — rewrite it only on
    # purpose, with an explicit --save
    ap.add_argument("--save", default="results/bench_fresh.json")
    args = ap.parse_args()

    _ensure_device_mesh()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from .figures import ALL
    names = args.only.split(",") if args.only else list(ALL)
    print("name,us_per_call,derived")
    # seed first so every summary records how to replay it (compare.py only
    # reads numeric leaves whose key mentions p50, so this never gates)
    summary = {"run_config": {"seed": args.seed, "fast": bool(args.fast)}}
    for name in names:
        fn = ALL[name]
        t0 = time.time()
        kw = {}
        # inspect.signature sees through functools.wraps/partial wrappers,
        # unlike fn.__code__.co_varnames which only works on plain functions
        try:
            params = inspect.signature(fn).parameters
        except (TypeError, ValueError):
            params = {}
        if args.fast and "reps" in params:
            kw["reps"] = 3
        if "seed" in params:
            kw["seed"] = args.seed
        try:
            summary[name] = fn(**kw)
        except Exception as e:  # keep the harness going; record the failure
            print(f"{name}/ERROR,0,{e!r}", flush=True)
            summary[name] = {"error": repr(e)}
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr,
              flush=True)
    def _keys_to_str(obj):
        if isinstance(obj, dict):
            return {str(k): _keys_to_str(v) for k, v in obj.items()}
        return obj

    out = pathlib.Path(args.save)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(_keys_to_str(summary), indent=1, default=str))
    failed = [name for name, v in summary.items()
              if isinstance(v, dict) and "error" in v]
    if failed:
        # a benchmark that raised (e.g. fig9's warm-cache guard) must turn
        # the CI smoke gate red, not vanish into an ERROR csv row
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
