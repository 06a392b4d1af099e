"""Benchmark harness — one bench per paper table/figure plus the §Roofline
table. Prints ``name,us_per_call,derived`` CSV per row.

  table4b   simulator accuracy + speedup vs the event-driven reference
  fig8      DSE time breakdown (design duplication hot-spot)
  fig9      convergence: simulator agility (9a) + awareness ladder (9b)
  fig10     co-design rates, contributions, ON/OFF ablation
  fig12/13  domain awareness (boundedness + parallelism exploitation)
  fig14/15  budget relaxation vs system complexity/heterogeneity
  fig17     divide-and-conquer suboptimality
  roofline  all (arch × shape) baseline roofline terms
  simbackend scalar-Python vs batched-JAX backend throughput, explorer
             iteration rate incl. the device-resident fused (R, K) chain
             blocks, heuristic-policy convergence comparison +
             synthetic-scenario policy sweep
             (also writes BENCH_simbackend.json for trajectory tracking)

After a full (non ``--smoke``) run, every ``benchmarks/BENCH_*.json`` is
mirrored to the repo root, where the perf-trajectory tracker looks for it.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import time

from repro.runtime.compile_cache import use_compile_cache

from . import (
    bench_budget_sweep,
    bench_codesign,
    bench_convergence,
    bench_divide_conquer,
    bench_domain,
    bench_generation,
    bench_roofline,
    bench_sim_validation,
    bench_simbackend,
)
from .common import emit

BENCHES = {
    "table4b": bench_sim_validation,
    "fig8": bench_generation,
    "fig9": bench_convergence,
    "fig10": bench_codesign,
    "fig12_13": bench_domain,
    "fig14_15": bench_budget_sweep,
    "fig17": bench_divide_conquer,
    "roofline": bench_roofline,
    "simbackend": bench_simbackend,
}


def _mirror_bench_json() -> None:
    """Copy every benchmarks/BENCH_*.json next to the repo root: the perf-
    trajectory tracker only reads root-level BENCH_*.json, so numbers that
    live solely inside benchmarks/ are invisible to it.

    Each mirror is written atomically (tmp + rename into the destination
    directory, so the rename never crosses filesystems): a run that dies
    mid-write can leave a stale root mirror, but never a torn one that the
    tracker would half-parse as a regression."""
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(bench_dir)
    for src in sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json"))):
        dst = os.path.join(root, os.path.basename(src))
        tmp = dst + ".tmp"
        shutil.copyfile(src, tmp)
        os.replace(tmp, dst)
        print(f"mirror,{os.path.basename(src)},0.0,copied to repo root", flush=True)


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", choices=sorted(BENCHES), default=None)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="perf-regression guard: runs the repro.analysis layout "
        "contracts first (non-zero exit on any cross-file desync), then a "
        "tiny simbackend run that *asserts* the "
        "JAX neighbour-eval path beats the Python path, both agree on the "
        "winner, multi-NoC batches dispatch at ≥0.5x the single-NoC "
        "throughput with zero fallbacks, the fused device loop sustains "
        "≥2x the host-driven loop at R=16 (n_compiles ≤ 4, n_fallback == "
        "0, R=1 parity), the "
        "mixed mapping+allocation block does the same on the widened move "
        "table (R=1 parity, ≥2x at R=16, n_compiles ≤ 6, n_fallback == 0), "
        "the root BENCH-json mirror is byte-identical to its source, and "
        "FarsiPolicy converges in ≤ NaiveSA's iterations on audio — "
        "non-zero exit on regression; invoked by tier-1",
    )
    args = ap.parse_args()
    if args.smoke:
        t0 = time.perf_counter()
        # layout contracts first: a desynced scal schema or taboo width
        # makes every perf number below meaningless, so fail before
        # timing anything (repro.analysis also runs standalone in tier-1)
        from repro.analysis.contracts import run_contracts

        contract_findings = run_contracts()
        if contract_findings:
            for f in contract_findings:
                print(f"contracts.ERROR,0.0,{f.render()}", flush=True)
            raise SystemExit("layout contracts violated — see above")
        print("contracts.ok,0.0,all layout contracts hold", flush=True)
        emit(bench_simbackend.run(smoke=True))  # raises on regression
        print(f"smoke.wall,{(time.perf_counter()-t0)*1e6:.0f},bench wall time", flush=True)
        return
    names = args.only or list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        t0 = time.perf_counter()
        try:
            rows = BENCHES[name].run()
        except Exception as e:  # keep the harness running; report the failure
            print(f"{name}.ERROR,0.0,{type(e).__name__}: {e}", flush=True)
            continue
        emit(rows)
        print(f"{name}.wall,{(time.perf_counter()-t0)*1e6:.0f},bench wall time", flush=True)
    _mirror_bench_json()


if __name__ == "__main__":
    main()
