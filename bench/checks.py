"""Whether what the timed path produced is correct: the comparison with the
plain reference (``bench/reference.py``) that decides ``correct``.

After the window, for what the window's searches and sessions produced:

``priced_fit_gap``
    a seed-drawn sample of the designs the window priced: chain designs
    (each chain's final design, decoded from a block's carry) and
    candidates of the serve dispatches. Each is priced by the reference;
    the number is the widest gap between the fitness the device gave it and
    the reference's, over max(|reference|, 1) (a fitness is a sum of
    budget-normalised distances, so 1 is a metric at twice its budget; the
    floor keeps a fitness near zero from turning rounding into a large
    relative gap).
``priced_ppa_gap``
    the sampled candidates' latency, power and area as the device gave
    them, as relative gaps to the reference's.
``best_fit_gap``
    every finished search's or session's winner, re-priced: the fitness the
    program reported for it (its Eq.-7 distance, and for a chain search the
    winner chain's last traced fitness as well) against the reference's,
    measured as above.
``best_ppa_gap``
    the same winners' decoded results: latency, each workload's latency,
    power and area, as relative gaps to the reference.
``lost``
    searches or sessions due in the window that failed or never finished
    within the drain limit. Exact: the limit is 0.

With ``rnd=reference.bf16`` the same designs are priced by the reference
in bfloat16 (the precision below the float32 the configurations state) in
the program's place: the control, which each limit has to fail.
"""
from __future__ import annotations

import random
from typing import Callable, Dict, List

from bench import designs, reference

# Each limit lies between the widest gap sound runs of the program gave over
# a dozen seeds or more and the smallest the bfloat16 control gave; PERF.md
# gives both readings for each.
LIMITS = {
    "priced_fit_gap": 2e-5,
    "priced_ppa_gap": 2e-5,
    "best_fit_gap": 2e-5,
    "best_ppa_gap": 2e-5,
    "lost": 0,
}
FIT_FLOOR = 1.0
# chain designs a run compares, drawn from the seed over the window's
# blocks; and the most serve candidates it keeps
CHECK_SAMPLE = 256


def sample_chains(blocks, n: int, seed: int) -> List[tuple]:
    """``n`` (block, chain) pairs drawn from ``seed``, spread over every
    block the run priced."""
    pairs = [(b, c) for b in range(len(blocks)) for c in range(len(blocks[b].fitness))]
    rng = random.Random(f"check:{seed}")
    return pairs if len(pairs) <= n else rng.sample(pairs, n)


def _budget(cfg: dict, factor: float) -> dict:
    return reference.scaled_budget(cfg["budget"], factor)


def readings(cfg: dict, outcome, task_names: List[str], seed: int,
             rnd: Callable[[float], float] = reference.f64) -> Dict[str, float]:
    """The compared numbers of one run. With ``rnd=reference.bf16`` the
    program's numbers are replaced by the reference's own in bfloat16: the
    control."""
    control = rnd is not reference.f64
    out = {k: 0.0 for k in LIMITS}
    out["lost"] = float(outcome.lost)

    def worst(key, ref, got, floor=0.0):
        out[key] = max(out[key], _gap(ref, got, floor))

    def ppa(key, ref, got):
        for k in ("latency_s", "power_w", "area_mm2"):
            worst(key, ref[k], got[k])

    n_priced = 0
    for bi, c in sample_chains(outcome.blocks, CHECK_SAMPLE, seed):
        b = outcome.blocks[bi]
        design = designs.decode_chain(b.base, b.carry, c, task_names)
        bud = _budget(cfg, b.budget_factor)
        ref = reference.price(cfg, design, bud)["fitness"]
        got = (reference.price(cfg, design, bud, rnd)["fitness"] if control
               else float(b.fitness[c]))
        worst("priced_fit_gap", ref, got, FIT_FLOOR)
        n_priced += 1
    for design, factor, handle in outcome.priced:
        bud = _budget(cfg, factor)
        ref = reference.price(cfg, design, bud)
        if control:
            got = reference.price(cfg, design, bud, rnd)
        else:
            got = dict(handle.scalars(), fitness=handle.fitness)
        worst("priced_fit_gap", ref["fitness"], got["fitness"], FIT_FLOOR)
        ppa("priced_ppa_gap", ref, got)
        n_priced += 1
    for f in outcome.finished:
        bud = _budget(cfg, f["budget_factor"])
        ref = reference.price(cfg, f["design"], bud)
        got = reference.price(cfg, f["design"], bud, rnd) if control else f
        worst("best_fit_gap", ref["fitness"], got["fitness"], FIT_FLOOR)
        if f["history_fitness"] is not None and not control:
            worst("best_fit_gap", ref["fitness"], f["history_fitness"], FIT_FLOOR)
        ppa("best_ppa_gap", ref, got)
        for w, v in ref["workload_latency_s"].items():
            worst("best_ppa_gap", v, got["workload_latency_s"].get(w, float("nan")))
    out["n_priced_designs"] = float(n_priced)
    out["n_best_designs"] = float(len(outcome.finished))
    return out


def _gap(ref: float, got: float, floor: float) -> float:
    g = reference.rel_gap(ref, got, floor)
    return g if g == g else float("inf")  # a NaN answer is as wrong as can be


def verdict(read: Dict[str, float]) -> tuple:
    """(correct, [(name, number, limit)]): correct when every compared
    number is within its limit and something was compared."""
    rows = [(k, read[k], LIMITS[k]) for k in LIMITS]
    compared = read.get("n_priced_designs", 0) + read.get("n_best_designs", 0) > 0
    ok = compared and all(v <= lim for _, v, lim in rows)
    return ok, rows
