"""DS3's pulse-Doppler radar graph as a deployment: its configuration file
holds the program's generator as run, and the program prices it as the
plain reference does, on the device chain path, through candidate pricing
and in its benchmark cell."""
import json
import random
from pathlib import Path

import pytest

from bench import checks, designs, reference
from bench.tests import tiny

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
CELL = "pulse_doppler.chains"


def _config(g, db, budget) -> dict:
    """A graph in the configuration files' schema (database, alpha and
    assumptions those of ``pulse_doppler.json``)."""
    cfg = json.loads((CONFIGS / "pulse_doppler.json").read_text())
    tasks = [{"name": n, "work_ops": t.work_ops, "i_read": t.i_read, "i_write": t.i_write,
              "llp": t.llp, "burst_bytes": t.burst_bytes, "a_peak_base": db.a_peak_base(n)}
             for n, t in g.tasks.items()]
    edges = [[s, d, b] for (s, d), b in g.edge_bytes.items()]
    cfg["graph"] = {"name": g.name, "tasks": tasks, "edges": edges}
    cfg["budget"] = {"latency_s": dict(budget.latency_s), "power_w": budget.power_w,
                     "area_mm2": budget.area_mm2}
    return cfg


def _deployment(**size):
    from repro.core import HardwareDatabase, pulse_doppler
    from repro.core.workloads import synthetic_budget

    g, db = pulse_doppler(**size), HardwareDatabase()
    bud = synthetic_budget(g, db)
    return g, db, bud, _config(g, db, bud)


def _seeded(g, n, seed):
    rng = random.Random(seed)
    return [designs.seeded_platform(g, rng, 4, 2, 2) for _ in range(n)]


def test_the_configuration_is_the_generators_graph():
    g, _, _, made = _deployment()
    cfg = json.loads((CONFIGS / "pulse_doppler.json").read_text())
    audio = json.loads((CONFIGS / "audio.json").read_text())
    assert len(cfg["graph"]["tasks"]) == 449 == 3 * 128 + 64 + 1
    assert len(cfg["graph"]["edges"]) == 8512 == 2 * 128 + 128 * 64 + 64
    assert cfg["graph"] == made["graph"]  # tasks, a_peak_base and edges, in order
    assert cfg["budget"] == made["budget"]
    assert cfg["database"] == audio["database"]
    assert cfg["precision"] == "float32" and cfg["alpha"] == 0.05
    names = [t["name"] for t in cfg["graph"]["tasks"]]
    assert names[:4] == ["fft_p0", "vmul_p0", "ifft_p0", "fft_p1"]
    assert names[-2:] == ["dfft_r63", "detect"]


def _fit_gap(cfg, design, got) -> float:
    ref = reference.price(cfg, design, cfg["budget"])["fitness"]
    return reference.rel_gap(ref, got, checks.FIT_FLOOR)


@pytest.mark.parametrize("alloc", [True, False])
def test_device_chain_blocks_price_as_the_reference_does(alloc):
    """At 8 pulses and 4 Doppler tasks (29 tasks), every chain's final
    design of a mixed and of a mapping-only block from seeded platforms
    carries the fitness the reference gives it."""
    from repro.core import DeviceChainRunner

    g, db, bud, cfg = _deployment(pulses=8, doppler_tasks=4)
    assert len(g.tasks) == 29
    runner = DeviceChainRunner(g, db)
    for i, d in enumerate(_seeded(g, 2, 2**31 + 11)):
        res = runner.run_chains(d, bud, r=8, k=8, seed=i, menu="farsi", alloc=alloc)
        base = designs.snapshot(d)
        for c in range(8):
            design = designs.decode_chain(base, res.carry, c, list(g.tasks))
            gap = _fit_gap(cfg, design, float(res.fitness[c]))
            assert gap <= checks.LIMITS["priced_fit_gap"], (i, c, gap)
        assert runner.n_fallback == 0


def _priced(g, db, bud, ds):
    from repro.core import make_backend
    from repro.core.backend import Candidate

    be = make_backend("jax", g, db)
    handles = be.evaluate_candidates([Candidate.of_design(d, bud) for d in ds])
    assert be.stats().n_fallback == 0
    return [dict(h.scalars(), fitness=h.fitness) for h in handles]


def test_candidate_pricing_agrees_with_the_reference():
    g, db, bud, cfg = _deployment(pulses=8, doppler_tasks=4)
    ds = _seeded(g, 6, 2**31 + 12)
    for d, got in zip(ds, _priced(g, db, bud, ds)):
        design = designs.snapshot(d)
        ref = reference.price(cfg, design, cfg["budget"])
        assert _fit_gap(cfg, design, got["fitness"]) <= checks.LIMITS["priced_fit_gap"]
        for k in ("latency_s", "power_w", "area_mm2"):
            assert reference.rel_gap(ref[k], got[k]) <= checks.LIMITS["priced_ppa_gap"], k


def test_the_full_graph_prices_as_the_reference_does():
    """All 449 tasks: four seeded designs through the XLA path, within 2e-6
    of the float64 reference in latency, power and area."""
    g, db, bud, cfg = _deployment()
    ds = _seeded(g, 4, 2**31 + 13)
    for d, got in zip(ds, _priced(g, db, bud, ds)):
        ref = reference.price(cfg, designs.snapshot(d), cfg["budget"])
        for k in ("latency_s", "power_w", "area_mm2"):
            assert reference.rel_gap(ref[k], got[k]) <= 2e-6, (k, ref[k], got[k])


def test_the_cell_runs_correct_and_the_bfloat16_control_fails(tmp_path):
    root = tiny.make_root(tmp_path)
    res = tiny.run(root, CELL)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "chain_evals_per_s"}
    cell, outcome = res["_cell"], res["_outcome"]
    assert len(cell.task_names) == 449
    with tiny.small_constants():
        ctrl = checks.readings(cell.cfg, outcome, cell.task_names, 5, reference.bf16)
    ok, rows = checks.verdict(ctrl)
    assert not ok, rows
