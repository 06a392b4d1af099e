"""What every driver shares: the host spans and work counts of a run, the
cell a run builds, what a run keeps for the correctness check, and the
wrapper around the program's backend that records them.

A driver (``bench/drivers/<mode>.py``, found by the traffic file's
``mode``) drives the program with the requests its generator
(``bench/generators/<generator>.py``) makes, and returns an
:class:`Outcome`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import random
import time
from typing import Dict, List, Optional

import numpy as np

from bench import designs, roofline

# Searches a closed-loop mix offers a run: more than any window finishes.
MAX_SEARCHES = 64
# Seconds of the same traffic served, untimed, before a session window.
PREWINDOW_S = 3.0
# Seconds a session window waits, after it closes, for the sessions due
# inside it; one still unfinished then counts at this limit.
DRAIN_S = 60.0


class Recorder:
    """Host spans on the host clock, compiles inside the window, and (in a
    traced run) the phase-simulation work (bytes, operations) of every
    design priced while the window is open. With a ``trace_dir`` the
    profiler traces from the window's opening, and every span is mirrored
    into its trace as a ``TraceAnnotation``."""

    def __init__(self, trace_dir: Optional[str] = None) -> None:
        self.trace_dir = trace_dir
        self.spans: List[tuple] = []  # (name, t0, t1, tag), window only
        self.open = False
        self.t_open = self.t_close = 0.0
        self.work = {"bytes": 0.0, "ops": 0.0, "designs": 0}
        self.tag = 0  # the search a chain block belongs to
        self.compiles = 0  # JAX executables built or loaded in the window
        self._window_ann = None
        _listen_for_compiles(self)

    @property
    def counts_work(self) -> bool:
        return self.open and self.trace_dir is not None

    def _ann(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def span(self, name: str, tag=None):
        t0 = time.perf_counter()
        with self._ann(name):
            yield
        if self.open:
            self.spans.append((name, t0, time.perf_counter(), tag))

    def open_window(self) -> None:
        if self.trace_dir is not None:
            import jax

            jax.profiler.start_trace(self.trace_dir)
        self._window_ann = self._ann("bench.window")
        self._window_ann.__enter__()
        self.t_open = time.perf_counter()
        self.open = True

    def close_window(self) -> None:
        if self.open:
            self.t_close = time.perf_counter()
            self.open = False
            self._window_ann.__exit__(None, None, None)

    def add_work(self, n: int, t: int, s_pe: int, s_mem: int, n_noc: int, n_wl: int) -> None:
        if self.counts_work and n:
            w = roofline.design_work(t, s_pe, s_mem, n_noc, n_wl)
            self.work["bytes"] += n * w["bytes"]
            self.work["ops"] += n * w["ops"]
            self.work["designs"] += n


_RECORDERS: List[Recorder] = []
# the event JAX records each time it compiles an executable or loads one
# from its persistent cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _listen_for_compiles(rec: Recorder) -> None:
    """Count, for the newest recorder, every executable JAX builds or loads
    from its cache while the window is open (its backend-compile event)."""
    import jax

    if not _RECORDERS:
        def on_event(event: str, duration: float, **kw) -> None:
            if event == BACKEND_COMPILE_EVENT and _RECORDERS[-1].open:
                _RECORDERS[-1].compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
    _RECORDERS.append(rec)


@dataclasses.dataclass
class Cell:
    """What a run builds once: the program's graph, database and budget,
    beside the plain configuration the reference reads, the traffic
    parameters and the generator that turns them into requests."""

    cfg: dict  # bench/configs/<config>.json
    params: dict  # bench/traffic/<traffic>.json
    g: object
    db: object
    budget: object
    task_names: List[str]
    generator: object = None  # bench/generators/<params["generator"]>.py

    def requests(self, seed: int, seconds: float, stream: str = "window") -> List[dict]:
        return self.generator.generate(self.params, seed, seconds, stream)


@dataclasses.dataclass
class Block:
    """One chain block of the window: the design it started from, its output
    carry and fitness, and the budget factor it was scored under."""

    base: dict
    carry: object
    fitness: np.ndarray
    budget_factor: float


@dataclasses.dataclass
class Outcome:
    attempted: int = 0  # searches started / sessions due in the window
    # searches or sessions that failed or degraded, plus designs priced on
    # the scalar fallback
    failed: int = 0
    lost: int = 0  # due in the window and never finished
    window_s: float = 0.0
    evals: int = 0  # designs priced by chain blocks completed in the window
    latencies: List[float] = dataclasses.field(default_factory=list)
    completed_in_window: int = 0
    blocks: List[Block] = dataclasses.field(default_factory=list)
    # (design, budget factor, handle) of candidates sampled in the window
    priced: List[tuple] = dataclasses.field(default_factory=list)
    finished: List[dict] = dataclasses.field(default_factory=list)
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)


def picks(seed: int, space: int, n: int, what: str) -> set:
    """``n`` positions among the first ``space`` of the window's dispatched
    candidates (or chain blocks), drawn from the seed before the window
    opens: the ones the check keeps. ``space`` is the most the traffic can
    dispatch, so the sample is as large whatever the program's speed."""
    rng = random.Random(f"{what}:{seed}")
    return set(rng.sample(range(space), min(n, space)))


def counts(design) -> tuple:
    return len(design.pes()), len(design.mems()), len(design.noc_chain)


def candidate_counts(c) -> tuple:
    """PE, memory and NoC counts of the design a candidate prices: its base
    with the move's added and removed blocks."""
    pe, mem, noc = counts(c.base)
    if c.delta is None:
        return pe, mem, noc
    n = {"pe": pe, "mem": mem, "noc": noc}
    for b in c.delta.added:
        n[b.kind.value] += 1
    for name in c.delta.removed:
        n[c.base.blocks[name].kind.value] -= 1
    return n["pe"], n["mem"], n["noc"]


def initial_design(cell: Cell, platform_seed: int):
    init = cell.params.get("initial", {"kind": "base"})
    if init["kind"] == "base":
        from repro.core import Design

        return Design.base(cell.g)
    if init["kind"] == "seeded_platform":
        return designs.seeded_platform(
            cell.g, random.Random(platform_seed), init["accelerators"],
            init["memories"], init["nocs"],
        )
    raise ValueError(f"unknown initial design kind {init['kind']!r}")


def wrap_backend(be, cell: Cell, rec: Recorder, out: Outcome,
                 deadline: Optional[list] = None) -> dict:
    """Host spans around the backend's two entries, and what the check
    keeps: the chain blocks and candidates priced in the window whose
    positions are in the returned dict's ``"blocks"`` and ``"candidates"``
    sets (None keeps every one). The driver sets those before the window
    opens; in the window a candidate not picked costs a count. With
    ``deadline`` (a one-element list, set once the window opens) the block
    that ends past it closes the window."""
    t = len(cell.task_names)
    n_wl = len(cell.budget.latency_s)
    evaluate, run_chains = be.evaluate_candidates, be.run_chains
    factors = out.notes.setdefault("budget_factor_of", {})
    keep = {"blocks": None, "candidates": None}
    seen = {"blocks": 0, "candidates": 0}

    def kept(what: str) -> bool:
        i = seen[what]
        seen[what] += 1
        return keep[what] is None or i in keep[what]

    def evaluate_candidates(cands):
        with rec.span("backend.evaluate"):
            handles = evaluate(cands)
        if rec.counts_work:
            for c in cands:
                rec.add_work(1, t, *candidate_counts(c), n_wl)
        if rec.open:
            for c, h in zip(cands, handles):
                if kept("candidates"):
                    with c.materialized(cell.g) as d:
                        out.priced.append(
                            (designs.snapshot(d), factors.get(id(c.budget), 1.0), h))
        return handles

    def chain_block(req):
        counted = rec.open
        base = designs.snapshot(req.design) if counted and kept("blocks") else None
        with rec.span("chains.block", tag=rec.tag):
            res = run_chains(req)
        if counted:
            out.evals += req.r * req.k
            if base is not None:
                out.blocks.append(Block(base, res.carry, res.fitness,
                                        factors.get(id(req.budget), 1.0)))
            if rec.counts_work:
                rec.add_work(req.r * req.k, t, *counts(req.design), n_wl)
            if deadline and deadline[0] and time.perf_counter() >= deadline[0]:
                rec.close_window()
        return res

    be.evaluate_candidates = evaluate_candidates
    be.run_chains = chain_block
    return keep


def keep_best(out: Outcome, res, factor: float, alpha: float) -> None:
    out.finished.append({
        "design": designs.snapshot(res.best_design),
        "fitness": float(res.best_distance.fitness(alpha)),
        "history_fitness": (float(res.history[-1]["fitness"])
                            if res.chained and res.history else None),
        "latency_s": float(res.best_result.latency_s),
        "workload_latency_s": dict(res.best_result.workload_latency_s),
        "power_w": float(res.best_result.power_w),
        "area_mm2": float(res.best_result.area_mm2),
        "budget_factor": factor,
        "iterations": int(res.iterations),
        "converged": bool(res.converged),
    })


def counters(backends) -> dict:
    """Compile, dispatch, encode and fallback counts summed over backends
    and their chain runners."""
    out = {"n_compiles": 0, "n_dispatches": 0, "encode_s": 0.0, "n_fallback": 0}
    for be in backends:
        st = be.stats()
        out["n_compiles"] += st.n_compiles
        out["n_dispatches"] += st.n_dispatches
        out["encode_s"] += st.encode_s
        out["n_fallback"] += st.n_fallback
        runner = be.chain_runner()
        out["n_compiles"] += runner.n_compiles
        out["n_fallback"] += runner.n_fallback
    return out
