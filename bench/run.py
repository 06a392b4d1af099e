"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``: its
configuration (``configs[].file``), its traffic mix
(``bench/traffic/<traffic>.json``), the generator and the driver the mix
names (``bench/generators/<generator>.py``, ``bench/drivers/<mode>.py``)
and one reader per metric (``bench/metrics/<metric>.py``). The run builds the
program's task graph, database and budget from the configuration, warms up
every shape the traffic reaches (``setup_s``), measures for ``--seconds``
with the profiler off (``--trace 0``: the end-to-end metrics) or on
(``--trace 1``: the per-layer metrics), checks what the window produced
against the plain reference (``bench/checks.py``), and prints one JSON
object as the last line of standard output. It refuses any platform but a
TPU: a run with no chip exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, root: Path, workload: str) -> dict:
    """The cell's entry, configuration file, traffic parameters, generator
    and driver, and the metrics it reports, all by name."""
    from bench import traffic

    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    params = traffic.load(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    mine = lambda m: workload in m.get("workloads", [workload])
    return {
        "cell": cell,
        "cfg": cfg,
        "params": params,
        "generator": traffic.load_module(root, "generators", params["generator"]),
        "driver": traffic.load_module(root, "drivers", params["mode"]),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def load_reader(root: Path, metric: str):
    """``read(window) -> float | None`` from ``bench/metrics/<metric>.py``."""
    from bench import traffic

    return traffic.load_module(root, "metrics", metric).read


def check_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def build_cell(found: dict):
    """The program's graph, database and budget, made from the
    configuration file (tasks and edges in the file's order)."""
    from repro.core import Budget, HardwareDatabase, Task, TaskGraph
    from repro.core.database import AreaModel, EnergyModel
    from bench.drive import Cell

    cfg = found["cfg"]
    gd, dbd = cfg["graph"], cfg["database"]
    g = TaskGraph(gd["name"])
    for t in gd["tasks"]:
        g.add_task(Task(t["name"], work_ops=t["work_ops"], i_read=t["i_read"],
                        i_write=t["i_write"], llp=t["llp"], burst_bytes=t["burst_bytes"]))
    for src, dst, nbytes in gd["edges"]:
        g.add_edge(src, dst, nbytes)
    g.validate()
    db = HardwareDatabase(
        gpp_ops_per_cycle=dbd["gpp_ops_per_cycle"], a_peak_range=tuple(dbd["a_peak_range"]),
        energy=EnergyModel(**dbd["energy"]), area=AreaModel(**dbd["area"]),
        sram_capacity_mb=dbd["sram_capacity_mb"],
    )
    for t in gd["tasks"]:
        if db.a_peak_base(t["name"]) != t["a_peak_base"]:
            raise ValueError(f"database speed-up of {t['name']!r} differs from the configuration")
    b = cfg["budget"]
    budget = Budget(latency_s=dict(b["latency_s"]), power_w=b["power_w"], area_mm2=b["area_mm2"])
    return Cell(cfg=cfg, params=found["params"], g=g, db=db, budget=budget,
                task_names=[t["name"] for t in gd["tasks"]], generator=found["generator"])


def memory_peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, t_start: float = T_START) -> dict:
    """One run of one cell; returns the result object (``checks`` last)."""
    from bench import checks, drive, traces

    found = find_cell(load_benchmark(root), root, workload)
    if require_chip:
        device = check_device(found["cell"]["chips"])
    else:
        import jax

        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices())}
    cell = build_cell(found)
    log_dir = root / "bench" / "out" / "trace" / workload
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
    rec = drive.Recorder(trace_dir=str(log_dir) if trace else None)
    outcome = found["driver"].drive_cell(cell, seed, seconds, rec)
    reduced = None
    if trace:
        import jax

        jax.profiler.stop_trace()
        ev = traces.load(traces.find_xplane(str(log_dir)))
        reduced = traces.reduce(ev["devices"], ev["host"])
    device["memory_peak_bytes"] = memory_peak_bytes()

    window = types.SimpleNamespace(
        mode=cell.params["mode"], outcome=outcome, spans=rec.spans, work=rec.work,
        setup_s=rec.t_open - t_start, trace=reduced, device_kind=device["kind"],
        before=outcome.notes.get("counters_before", {}),
        after=outcome.notes.get("counters_after", {}),
    )
    metrics = {}
    for m in found["per_layer"] if trace else found["end_to_end"]:
        value = load_reader(root, m["name"])(window)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    read = checks.readings(cell.cfg, outcome, cell.task_names, seed)
    correct, rows = checks.verdict(read)
    result = {
        "correct": bool(correct),
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    result["_readings"] = read
    result["_notes"] = summary(outcome, window.before, window.after, rec,
                               reduced, device["kind"])
    result["_cell"], result["_outcome"] = cell, outcome
    return result


def summary(outcome, before: dict, after: dict, rec, reduced, kind: str) -> list:
    """Lines for standard error, before the checks: what a reader of the run
    needs beside the metrics."""
    from bench import roofline, stats

    n = outcome.notes
    work = rec.work
    lines = [
        f"phase-sim backend: {n.get('backend')}",
        f"attempted {outcome.attempted}, failed {outcome.failed}, lost {outcome.lost}",
        f"compiles inside the window: {rec.compiles} by JAX, "
        f"{after.get('n_compiles', 0) - before.get('n_compiles', 0)} by the program's counters",
        f"scalar fallbacks inside the window: {after.get('n_fallback', 0) - before.get('n_fallback', 0)}",
    ]
    if outcome.latencies:
        lat = outcome.latencies
        late = n.get("generator_late_s", [0.0])
        conv = n.get("converged", [])
        its = n.get("iterations", [])
        lines += [
            f"session latency: p50 {stats.percentile(lat, 50)!r} s, p95 "
            f"{stats.percentile(lat, 95)!r} s over {len(lat)} sessions "
            f"({max(0, len(lat) - stats.percentile_rank(len(lat), 95))} beyond p95)",
            f"sessions finished inside the window: {outcome.completed_in_window}",
            f"generator late: p50 {stats.percentile(late, 50)!r} s, max {max(late)!r} s",
            f"iterations per session: mean {statistics.fmean(its) if its else 0!r}, "
            f"max {max(its) if its else 0}; converged share "
            f"{(sum(conv) / len(conv)) if conv else 0!r}",
            f"service: {n.get('service')}",
        ]
    else:
        lines.append(f"chain blocks in the window: "
                     f"{len(outcome.blocks)}, "
                     f"designs priced {outcome.evals}, window {outcome.window_s!r} s, "
                     f"searches finished {len(outcome.finished)}")
    if reduced is not None and work["designs"]:
        share, bound = roofline.roofline_share(work["bytes"], work["ops"],
                                               reduced["busy_s"], kind)
        lines.append(f"phase-sim roofline: {work['designs']} designs, "
                     f"{work['bytes']!r} B, {work['ops']!r} ops, bound by {bound}, "
                     f"{share!r} % of device busy {reduced['busy_s']!r} s")
    return lines


def prepare() -> bool:
    """Put the program and the benchmark on the path and JAX's persistent
    compilation cache at a fixed directory inside the checkout (the
    program's own helper keeps the directory named here). False where the
    checkout holds no program."""
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout", file=sys.stderr)
        return False
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / "out" / "jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not prepare():
        return 2
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"bench/run.py: {exc}", file=sys.stderr)
        return 3
    read = result.pop("_readings")
    result.pop("_cell"), result.pop("_outcome")
    for line in result.pop("_notes"):
        print(line, file=sys.stderr)
    print(f"compared designs: {int(read['n_priced_designs'])} priced in the window, "
          f"{int(read['n_best_designs'])} winners", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
