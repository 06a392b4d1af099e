"""The plain reference prices designs as the program's own Python simulator
does, and the bfloat16 control is far enough from it to fail each limit."""
import json
import random
from pathlib import Path

import pytest

from bench import checks, designs, reference
from bench.tests import tiny

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("name", ["ar_complex", "audio"])
def test_reference_agrees_with_the_programs_python_simulator(name):
    from repro.core import (HardwareDatabase, ar_complex, audio, calibrated_budget,
                            distance, random_single_noc_designs, simulate)

    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    g = {"ar_complex": ar_complex, "audio": audio}[name]()
    db = HardwareDatabase()
    bud = calibrated_budget(db)
    rng = random.Random(2**31 + 9)
    ds = random_single_noc_designs(g, 6, seed=4)
    ds += [designs.seeded_platform(g, rng, 4, 2, n) for n in (2, 3, 4)]
    for d in ds:
        py = simulate(d, g, db)
        ref = reference.price(cfg, designs.snapshot(d), cfg["budget"])
        assert ref["latency_s"] == pytest.approx(py.latency_s, rel=1e-12)
        assert ref["power_w"] == pytest.approx(py.power_w, rel=1e-12)
        assert ref["area_mm2"] == pytest.approx(py.area_mm2, rel=1e-12)
        assert ref["fitness"] == pytest.approx(distance(py, bud).fitness(0.05), rel=1e-12)
        for t, f in py.task_finish_s.items():
            assert ref["task_finish_s"][t] == pytest.approx(f, rel=1e-12)


@pytest.mark.parametrize("workload", ["ar_complex.chains", "audio.serve_chains",
                                      "ar_complex.serve", "audio.chains"])
def test_the_bfloat16_control_fails_and_the_program_passes(tmp_path, workload):
    root = tiny.make_root(tmp_path)
    res = tiny.run(root, workload)
    assert res["correct"], res["checks"]
    cell, outcome = res["_cell"], res["_outcome"]
    with tiny.small_constants():
        ctrl = checks.readings(cell.cfg, outcome, cell.task_names, 5, reference.bf16)
    ok, rows = checks.verdict(ctrl)
    assert not ok, rows
    failing = [k for k, v, lim in rows if v > lim]
    assert "priced_fit_gap" in failing or "best_fit_gap" in failing
