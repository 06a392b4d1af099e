"""Find the knee of a sessions mix on a configuration: the highest offered
rate at which sessions finished keep pace with sessions offered.

    python3 bench/sweep.py --config <name> --traffic <mix> --seed <n> --seconds <s> --rates 2,4,8

Runs the mix at each rate in turn, in one process, and prints one JSON line
per rate: sessions offered and finished inside the window, latency p50/p95
and the sessions lost. The mix need not be a cell yet: the rate a cell's
traffic file states is fixed from one such sweep, at about four fifths of
the knee.
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    if not run.prepare():
        return 2
    from bench import drive, stats

    bench = run.load_benchmark(run.ROOT)
    name = f"{args.config}.{args.traffic}.sweep"
    bench["workloads"] = bench["workloads"] + [
        {"name": name, "config": args.config, "traffic": args.traffic, "chips": 1}]
    found = run.find_cell(bench, run.ROOT, name)
    run.check_device(found["cell"]["chips"])
    for rate in [float(r) for r in args.rates.split(",")]:
        found["params"] = dict(found["params"], rate_per_s=rate)
        cell = run.build_cell(found)
        t0 = time.perf_counter()
        rec = drive.Recorder()
        out = found["driver"].drive_cell(cell, args.seed, args.seconds, rec)
        lat = out.latencies
        print(json.dumps({
            "rate_per_s": rate, "offered": out.attempted,
            "finished_in_window": out.completed_in_window,
            "sessions_per_s": out.completed_in_window / args.seconds,
            "p50_s": stats.percentile(lat, 50), "p95_s": stats.percentile(lat, 95),
            "lost": out.lost, "failed": out.failed,
            "mean_iterations": sum(out.notes["iterations"]) / max(1, len(out.notes["iterations"])),
            "compiles_in_window": rec.compiles, "wall_s": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
