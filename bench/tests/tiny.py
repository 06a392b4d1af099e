"""A copy of the benchmark's data at a size a CPU test run can hold: the
cells, configurations, generators, drivers and metric readers of the
repository, with each traffic mix cut to a few small blocks or sessions."""
from __future__ import annotations

import contextlib
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

SMALL = {
    "searches": {"chain_r": 8, "chain_k": 8, "max_iterations": 16},
    "sessions": {"rate_per_s": 3.0, "max_iterations": 8},
}
# the run-wide constants, cut to the same size
CONSTANTS = {("drive", "MAX_SEARCHES"): 3, ("drive", "PREWINDOW_S"): 0.5,
             ("checks", "CHECK_SAMPLE"): 64}


# Session cells whose traffic files, driver and readers are in bench/ but
# which wait for a knee swept on the chip before they join BENCHMARK.json;
# the tests run them all the same, at test sizes.
HELD_BACK = {
    "workloads": [
        {"name": "audio.serve_chains", "config": "audio", "traffic": "serve_chains",
         "chips": 1, "why": "open-loop chain sessions"},
        {"name": "ar_complex.serve", "config": "ar_complex", "traffic": "serve_mix",
         "chips": 1, "why": "open-loop host-loop sessions"},
    ],
    "end_to_end": [
        {"name": "session_p95_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"},
        {"name": "sessions_per_s", "unit": "sessions/s", "better": "higher", "bound": 0.25,
         "source": "host_clock"},
    ],
}


def _with_held_back(bench: dict) -> dict:
    names = [w["name"] for w in HELD_BACK["workloads"]]
    if any(w["name"] in names for w in bench["workloads"]):
        return bench
    bench["workloads"] += HELD_BACK["workloads"]
    for m in HELD_BACK["end_to_end"]:
        bench["end_to_end"].append(dict(m, workloads=names))
    return bench


def make_root(tmp: Path) -> Path:
    """``tmp`` laid out like a checkout: BENCHMARK.json and bench/ data."""
    bench = _with_held_back(json.loads((ROOT / "BENCHMARK.json").read_text()))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("configs", "metrics", "traffic", "generators", "drivers"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for path in (tmp / "bench" / "traffic").glob("*.json"):
        params = json.loads(path.read_text())
        params.update(SMALL[params["mode"]])
        if params.get("chain"):
            params["chain"] = {"chain_r": 4, "chain_k": 8}
        if "warm" in params:
            params["warm"] = {"batches": [4, 8], "nocs": params["warm"]["nocs"][:2]}
        path.write_text(json.dumps(params))
    return tmp


@contextlib.contextmanager
def small_constants():
    import importlib

    saved = {}
    for (mod, name), value in CONSTANTS.items():
        m = importlib.import_module(f"bench.{mod}")
        saved[(m, name)] = getattr(m, name)
        setattr(m, name, value)
    try:
        yield
    finally:
        for (m, name), value in saved.items():
            setattr(m, name, value)


def run(root: Path, workload: str, seed: int = 2**31 + 7, seconds: float = 1.0) -> dict:
    """One run of a cell on the CPU, the look for a chip skipped."""
    from bench import run as harness

    with small_constants():
        return harness.run_cell(root, workload, seed, seconds, trace=False, require_chip=False)
