"""Vectorized phase simulator ≡ the Python reference (single-NoC regime)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Design, HardwareDatabase, ar_complex, audio, edge_detection, random_single_noc_designs, simulate
from repro.core.phase_sim_jax import EncodedWorkload, encode_batch, simulate_batch


@pytest.mark.parametrize("graph_fn", [edge_detection, audio, ar_complex])
@pytest.mark.parametrize(
    "batch", [1, 8, pytest.param(64, marks=pytest.mark.slow)]
)
def test_vectorized_matches_python(graph_fn, batch):
    db = HardwareDatabase()
    g = graph_fn()
    enc = EncodedWorkload.of(g)
    designs = random_single_noc_designs(g, batch, seed=3)
    rows = encode_batch(designs, g, db, enc)
    out = jax.jit(lambda r: simulate_batch(enc, r))(rows)
    assert bool(out["all_done"].all())
    for i, d in enumerate(designs):
        ref = simulate(d, g, db)
        got = float(out["latency_s"][i])
        assert abs(got - ref.latency_s) / ref.latency_s < 1e-3, (i, got, ref.latency_s)
        # per-task finish times agree too
        for j, name in enumerate(enc.names):
            a, b = float(out["finish_s"][i, j]), ref.task_finish_s[name]
            assert abs(a - b) / max(b, 1e-12) < 1e-3
        # device-side PPA columns agree with the host rollup (f32 sums)
        assert abs(float(out["power_w"][i]) - ref.power_w) / ref.power_w < 1e-3
        assert abs(float(out["area_mm2"][i]) - ref.area_mm2) / ref.area_mm2 < 1e-4
        for w, lat in ref.workload_latency_s.items():
            got_wl = float(out["wl_latency_s"][i, enc.wl_names.index(w)])
            assert abs(got_wl - lat) / max(lat, 1e-12) < 1e-3


def test_device_side_fitness_matches_host_distance():
    """The kernel's Eq.-7 fitness column equals budgets.distance().fitness()
    computed from the decoded result (the explorer ranks by this column)."""
    from repro.core import calibrated_budget
    from repro.core.budgets import distance
    from repro.core.phase_sim_jax import fill_budget

    db = HardwareDatabase()
    g = ar_complex()
    enc = EncodedWorkload.of(g)
    designs = random_single_noc_designs(g, 6, seed=11)
    bud = calibrated_budget(db)
    alpha = 0.05
    rows = encode_batch(designs, g, db, enc)
    for j in range(len(designs)):
        fill_budget(rows, j, enc, bud.latency_s, bud.power_w, bud.area_mm2, alpha)
    out = jax.jit(lambda r: simulate_batch(enc, r))(rows)
    for i, d in enumerate(designs):
        ref = distance(simulate(d, g, db), bud).fitness(alpha)
        got = float(out["fitness"][i])
        assert abs(got - ref) / max(abs(ref), 1e-9) < 1e-3, (i, got, ref)


def test_batch_throughput_smoke():
    """One jit'd call evaluates a whole neighbour batch (the Fig-8 answer)."""
    db = HardwareDatabase()
    g = edge_detection()
    enc = EncodedWorkload.of(g)
    designs = random_single_noc_designs(g, 32, seed=9)
    rows = encode_batch(designs, g, db, enc)
    out = jax.jit(lambda r: simulate_batch(enc, r))(rows)
    assert out["latency_s"].shape == (32,)
    assert bool(jnp.isfinite(out["latency_s"]).all())
    assert out["fitness"].shape == (32,)
