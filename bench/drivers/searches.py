"""Back-to-back device chain searches (``Explorer.run_chains``) on one
shared ``make_backend("jax", ...)`` backend, closed loop: the next search
starts when the last one ends.

Traffic parameters: ``policy``, ``chain_r``, ``chain_k``, ``chain_alloc``,
``max_iterations`` and ``initial`` (the starting design). Each request gives
an ``explorer_seed`` and a ``platform_seed``.
"""
from __future__ import annotations

from typing import Optional

from bench import drive


def drive_cell(cell: drive.Cell, seed: int, seconds: float, rec: drive.Recorder) -> drive.Outcome:
    """Set-up runs one block and its winner's decode at the cell's own
    shapes. The window closes at the end of the first chain block that ends
    past ``seconds``; the search in flight then runs to its end, untimed,
    so that its winner is checked too. Every block of the window is kept
    for the check (one a second or so)."""
    from repro.core import Explorer, ExplorerConfig, make_backend

    p = cell.params
    alpha = cell.cfg["alpha"]
    out = drive.Outcome()
    deadline = [0.0]
    be = make_backend("jax", cell.g, cell.db)
    drive.wrap_backend(be, cell, rec, out, deadline)
    out.notes["backend"] = be.name

    def search(req: dict, max_it: Optional[int] = None):
        cfg = ExplorerConfig(
            policy=p["policy"], backend="jax", seed=req["explorer_seed"],
            chain_r=p["chain_r"], chain_k=p["chain_k"],
            chain_alloc=p["chain_alloc"], alpha_met=alpha,
            max_iterations=max_it or p["max_iterations"],
        )
        ex = Explorer(cell.g, cell.db, cell.budget, cfg, backend=be)
        rec.tag += 1
        with rec.span("search"):
            return ex.run_chains(drive.initial_design(cell, req["platform_seed"]))

    search(cell.requests(seed, seconds, stream="warm")[0], max_it=p["chain_k"])
    be.flush()
    out.notes["counters_before"] = drive.counters([be])

    reqs = cell.requests(seed, seconds)
    rec.open_window()
    deadline[0] = rec.t_open + seconds
    for req in reqs:
        if not rec.open:
            break
        out.attempted += 1
        try:
            res = search(req)
        except Exception as exc:  # a failed search counts; the run goes on
            out.failed += 1
            out.notes.setdefault("errors", []).append(repr(exc))
            continue
        drive.keep_best(out, res, 1.0, alpha)
    rec.close_window()
    out.window_s = rec.t_close - rec.t_open
    out.notes["counters_after"] = drive.counters([be])
    # designs priced on the scalar fallback count as failures too
    out.failed += out.notes["counters_after"]["n_fallback"] - out.notes["counters_before"]["n_fallback"]
    be.flush()
    return out
