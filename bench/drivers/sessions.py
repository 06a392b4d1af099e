"""Open-loop exploration sessions: ``DseService.submit`` /
``DseService.step`` on ``DseService(db, backend="jax")``.

Traffic parameters: ``max_iterations``, ``chain`` (``chain_r``/``chain_k``
for chain sessions), ``initial`` (the starting design), ``budget_factors``
and ``warm`` (the candidate batch sizes and NoC counts the sessions reach,
compiled in set-up). Each request gives a ``due_s``, a ``policy``, a ``budget_factor``,
an ``explorer_seed`` and a ``platform_seed``.
"""
from __future__ import annotations

import math
import random
import time
from typing import Dict, List, Optional

from bench import checks, designs, drive

# candidates a host-loop session prices per iteration
NEIGHBORS_PER_ITER = 4


def session_config(p: dict, req: dict, alpha: float):
    from repro.core import ExplorerConfig

    chain = p.get("chain") or {}
    return ExplorerConfig(
        policy=req["policy"], seed=req["explorer_seed"], backend="jax",
        max_iterations=p["max_iterations"],
        neighbors_per_iter=NEIGHBORS_PER_ITER, alpha_met=alpha,
        chain_r=chain.get("chain_r", 0), chain_k=chain.get("chain_k", 32),
    )


def warm_shapes(cell: drive.Cell, be) -> None:
    """Compile every dispatch shape the cell's sessions reach: candidate
    batches of each padded size in ``warm.batches`` on designs of each NoC
    count in ``warm.nocs``, and, for chain sessions, one chain block."""
    from repro.core import Candidate, Design
    from repro.core.device_explore import ChainRequest

    p = cell.params
    warm = p["warm"]
    alpha = cell.cfg["alpha"]
    rng = random.Random(0)
    for n_noc in warm["nocs"]:
        d = (Design.base(cell.g) if n_noc == 1
             else designs.seeded_platform(cell.g, rng, 4, 2, n_noc))
        for b in warm["batches"]:
            hs = be.evaluate_candidates([Candidate.of_design(d, cell.budget, alpha)] * b)
            hs[0].fitness  # noqa: B018 — forces the dispatch
    chain = p.get("chain")
    if chain:
        d = drive.initial_design(cell, 0)
        be.run_chains(ChainRequest(
            design=d, budget=cell.budget, r=chain["chain_r"], k=chain["chain_k"],
            menu="farsi", alpha=alpha,
        ))
    be.flush()


def serve(svc, cell: drive.Cell, reqs: List[dict], seconds: float, drain_s: float,
          rec: drive.Recorder, out: Optional[drive.Outcome], stream: str) -> None:
    """Admit ``reqs`` open-loop at their due times and tick until each has
    finished or the drain limit passes. With ``out``, this is the window:
    latency runs from each session's due time to the tick it finished in."""
    alpha = cell.cfg["alpha"]
    p = cell.params
    factors = (out.notes if out else {}).setdefault("budget_factor_of", {})
    budgets = {}
    for f in p.get("budget_factors", [1.0]):
        budgets[f] = cell.budget.scaled(f)
        factors[id(budgets[f])] = f
    handles: Dict[str, tuple] = {}
    done_at: Dict[str, float] = {}
    late: List[float] = []
    t0 = time.perf_counter()
    if out is not None:
        rec.open_window()
        t0 = rec.t_open
    t_close, t_limit = t0 + seconds, t0 + seconds + drain_s
    i = 0
    while True:
        now = time.perf_counter()
        if out is not None and now >= t_close:
            rec.close_window()
        while i < len(reqs) and t0 + reqs[i]["due_s"] <= now:
            r = reqs[i]
            name = f"{stream}.{i}"
            init = (drive.initial_design(cell, r["platform_seed"])
                    if p.get("initial", {"kind": "base"})["kind"] != "base" else None)
            h = svc.submit(name, cell.g, budgets[r["budget_factor"]],
                           session_config(p, r, alpha), initial=init)
            handles[name] = (h, t0 + r["due_s"], r["budget_factor"])
            late.append(now - (t0 + r["due_s"]))
            i += 1
        if svc.n_live:
            with rec.span("serve.tick"):
                finished = svc.step()
            t_done = time.perf_counter()
            for h in finished:
                done_at[h.name] = t_done
        elif i < len(reqs):
            time.sleep(max(0.0, min(t0 + reqs[i]["due_s"] - time.perf_counter(), 0.01)))
        else:
            break
        if time.perf_counter() >= t_limit:
            break
    if out is not None:
        rec.close_window()
    svc.scheduler.flush()
    if out is None:
        return
    out.window_s = seconds
    out.attempted = len(handles)
    for name, (h, due, factor) in handles.items():
        if h.done and name in done_at:
            out.latencies.append(done_at[name] - due)
            if done_at[name] <= t_close:
                out.completed_in_window += 1
            drive.keep_best(out, h.result, factor, alpha)
            if h.degraded:
                out.failed += 1
        else:  # failed, or not finished by the drain limit
            out.latencies.append(t_limit - due)
            out.lost += 1
            out.failed += 1
    out.notes["generator_late_s"] = late
    out.notes["iterations"] = [f["iterations"] for f in out.finished]
    out.notes["converged"] = [f["converged"] for f in out.finished]


def drive_cell(cell: drive.Cell, seed: int, seconds: float, rec: drive.Recorder) -> drive.Outcome:
    """Set-up compiles the cell's shapes and serves an untimed pre-window of
    the same traffic to its end; then the window. The candidates and chain
    blocks the check keeps are drawn from the seed before the window, by
    their position among the most the window's sessions can dispatch."""
    from repro.serve import DseService

    p = cell.params
    out = drive.Outcome()
    svc = DseService(cell.db, backend="jax")
    be = svc.scheduler.backend_for(cell.g)
    keep = drive.wrap_backend(be, cell, rec, out)
    out.notes["backend"] = be.name
    warm_shapes(cell, be)
    pre = cell.requests(seed, drive.PREWINDOW_S, stream="warm")
    serve(svc, cell, pre, drive.PREWINDOW_S, drive.DRAIN_S, rec, None, "warm")
    out.notes["counters_before"] = drive.counters([be])
    stats0 = svc.stats()
    reqs = cell.requests(seed, seconds)
    n, its = len(reqs), p["max_iterations"]
    # each iteration prices at most NEIGHBORS_PER_ITER candidates, and a
    # session's winner is decoded once more
    keep["candidates"] = drive.picks(
        seed, n * (its * NEIGHBORS_PER_ITER + 1), checks.CHECK_SAMPLE, "candidates")
    if p.get("chain"):
        keep["blocks"] = drive.picks(
            seed, n * math.ceil(its / p["chain"]["chain_k"]), checks.CHECK_SAMPLE, "blocks")
    serve(svc, cell, reqs, seconds, drive.DRAIN_S, rec, out, "w")
    stats1 = svc.stats()
    out.notes["counters_after"] = drive.counters([be])
    # designs priced on the scalar fallback count as failures too
    out.failed += out.notes["counters_after"]["n_fallback"] - out.notes["counters_before"]["n_fallback"]
    out.notes["service"] = {
        "failed": stats1.n_failed - stats0.n_failed,
        "degraded": stats1.n_degraded - stats0.n_degraded,
        "fallback": stats1.n_fallback - stats0.n_fallback,
        "cache_hit_rate": stats1.cache_hit_rate,
    }
    return out
