"""Readings for the limits of ``correct``: the program over many seeds, and
its control, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 2

The control is the program's own lower-precision path put in the plan's
place: the same searched design, compiled with
``Target(dtype="bfloat16")`` (bf16 values and x, fp32 accumulation), the
step below the configuration's fp32 that would tempt a later change.
Each seed runs the cell's traffic twice, the plan and then the control,
and prints one JSON line with both readings of every compared number.
The benchmark's own runs never run this; it sets and re-checks the limits
in ``bench/workloads/<cell>.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import program  # noqa: E402
from bench.lib.registry import Registry  # noqa: E402


def lower_precision_hook():
    """A plan hook that swaps in the bf16 build of the searched design;
    the build is made once and reused across seeds."""
    built = {}

    def hook(plan, sm):
        key = plan.spec_json
        if key not in built:
            r = program.repro()
            built[key] = r.compile(
                sm, r.Target(backend="pallas", batch_size=plan.target.batch_size,
                             dtype="bfloat16"), graph=plan.graph)
        return built[key]
    return hook


def main(argv=None, registry=None, **kw) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from bench.run import run_cell
    registry = registry or Registry.from_file()
    hook = lower_precision_hook()
    for seed in (int(s) for s in args.seeds.split(",")):
        row = {"seed": seed}
        for side, plan_hook in (("program", None), ("control", hook)):
            res = run_cell(registry, args.workload, seed, args.seconds, False,
                           plan_hook=plan_hook, **kw)
            row[side] = {k: c["value"] for k, c in res["checks"].items()}
            row[side + "_correct"] = res["correct"]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
