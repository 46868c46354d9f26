#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: it must fail.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

The configurations state exact integer sums.  The control puts each
template's reference in the program's place with its summation one
precision lower: float32, on the device.  For every seed it generates the
cell's tables at the cell's size, computes the control's answers and the
exact reference, and holds the control to the same comparison
(``oracle.compare``) as a served answer.  It prints one JSON line per
seed with the numbers compared; every seed should read not correct.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import oracle  # noqa: E402
import run  # noqa: E402


def float32_segment_sum(values, gid, n_groups):
    """Per-group sums in float32 on the default device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sums = jax.ops.segment_sum(jnp.asarray(values, jnp.float32),
                               jnp.asarray(gid), num_segments=n_groups)
    return np.asarray(sums)


def control_checks(cfg, templates, tables) -> dict:
    """The control's answers, one per template, held to the exact
    references; returns the numbers compared."""
    served = list(cfg.references(templates, tables,
                                 segment_sum=float32_segment_sum).items())
    refs = cfg.references(templates, tables)
    return oracle.compare(served, refs, unanswered=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = run.find_cell(run.load_benchmark(), args.workload)
    cfg = run.load_config(cell["config"])
    templates = run.load_traffic(cell["config"], cell["traffic"])["templates"]
    import jax

    jax.config.update("jax_enable_x64", True)
    for seed in args.seeds:
        checks = control_checks(cfg, templates, cfg.generate(seed,
                                                             args.scale))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": jax.devices()[0].device_kind,
                          "correct": oracle.is_correct(checks, 1),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
