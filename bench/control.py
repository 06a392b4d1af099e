"""Readings for the correctness limits, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

For each seed, one run of the cell at its own load (as ``bench/run.py``
makes it), then two sets of readings of the same compared designs: the
program's (what ``correct`` is decided on) and the control's, where the
reference computed in bfloat16 takes the program's place. One JSON line per
seed, then the widest program reading and the narrowest control reading of
each number. ``bench/checks.py`` sets each limit between the two.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    if not run.prepare():
        return 2
    from bench import checks, reference

    worst, least = {}, {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run.run_cell(run.ROOT, args.workload, seed, args.seconds, False,
                           t_start=time.perf_counter())
        cell, outcome = res["_cell"], res["_outcome"]
        prog = res["_readings"]
        ctrl = checks.readings(cell.cfg, outcome, cell.task_names, seed, reference.bf16)
        for k in checks.LIMITS:
            worst[k] = max(worst.get(k, 0.0), prog[k])
            least[k] = min(least.get(k, float("inf")), ctrl[k])
        print(json.dumps({"seed": seed, "correct": res["correct"], "program": prog,
                          "control": ctrl, "metrics": res["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "program_max": worst,
                      "control_min": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
