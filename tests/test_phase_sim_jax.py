"""Vectorized phase simulator ≡ the Python reference, and the shape of its
phase body."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    HardwareDatabase,
    ar_complex,
    audio,
    edge_detection,
    pulse_doppler,
    random_single_noc_designs,
    simulate,
)
from repro.core.phase_sim_jax import EncodedWorkload, encode_batch, simulate_batch


def _single_noc(g, n):
    return random_single_noc_designs(g, n, seed=3)


def _seeded_two_noc(g, n):
    """4 accelerators, 2 memories and a 2-NoC chain: the platform the
    pulse-Doppler benchmark cell searches from."""
    from bench.designs import seeded_platform

    rng = random.Random(2**31 + 5)
    return [seeded_platform(g, rng, 4, 2, 2) for _ in range(n)]


CASES = [
    pytest.param(graph_fn, batch, _single_noc, id=f"{batch}-{graph_fn.__name__}",
                 marks=[pytest.mark.slow] if batch == 64 else [])
    for batch in (1, 8, 64)
    for graph_fn in (edge_detection, audio, ar_complex)
] + [
    # the full 449-task graph, past one 128-lane tile, on two NoCs
    pytest.param(pulse_doppler, 4, _seeded_two_noc, id="4-pulse_doppler"),
]


@pytest.mark.parametrize("graph_fn,batch,make_designs", CASES)
def test_vectorized_matches_python(graph_fn, batch, make_designs):
    db = HardwareDatabase()
    g = graph_fn()
    enc = EncodedWorkload.of(g)
    designs = make_designs(g, batch)
    rows = encode_batch(designs, g, db, enc)
    out = jax.device_get(jax.jit(lambda r: simulate_batch(enc, r))(rows))
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


def _loop_body(jaxpr):
    """The phase loop's body and how many of its inputs are loop-invariant
    (the first ones), found anywhere in ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            return eqn.params["body_jaxpr"].jaxpr, eqn.params["body_nconsts"]
        if eqn.primitive.name == "scan":
            return eqn.params["jaxpr"].jaxpr, eqn.params["num_consts"]
        for sub in _subs(eqn):
            found = _loop_body(sub)
            if found:
                return found
    return None


def _subs(eqn):
    out = []
    for v in eqn.params.values():
        for c in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(c, "eqns"):
                out.append(c)
            elif hasattr(c, "jaxpr") and hasattr(c.jaxpr, "eqns"):
                out.append(c.jaxpr)
    return out


def _walk(jaxpr, invariant):
    """Every equation under ``jaxpr``, each with the set of its variables
    that are loop-invariant inputs, followed through nested calls."""
    for eqn in jaxpr.eqns:
        yield eqn, invariant
        for sub in _subs(eqn):
            inner = set()
            if len(sub.invars) == len(eqn.invars):  # a call: inputs map 1:1
                inner = {iv for iv, ov in zip(sub.invars, eqn.invars)
                         if not hasattr(ov, "val") and ov in invariant}  # no literals
            yield from _walk(sub, inner)


def test_phase_body_does_no_per_row_tt_work():
    """At T = 449 on a 2-NoC bucket the phase loop works in O(T·S) a row: no
    equation of its body has an operand or result with two axes of length
    T, except the readiness matmul's (T, T) parent mask, which is the
    workload's — loop-invariant and unbatched, one matmul for all rows. Its
    operands are 0/1, so it alone may run at default precision; every other
    dot in the body sums non-0/1 floats and asks for HIGHEST."""
    g = pulse_doppler()
    t = len(g.tasks)
    enc = EncodedWorkload.of(g)
    rows = encode_batch(_seeded_two_noc(g, 3), g, HardwareDatabase(), enc)
    assert rows["noc_bw"].shape == (3, 2)
    jx = jax.make_jaxpr(lambda r: simulate_batch(enc, r))(rows)
    body, n_consts = _loop_body(jx.jaxpr)
    invariant = set(body.invars[:n_consts])
    shared, exact = [], 0
    for eqn, inv in _walk(body, invariant):
        tt = [v for v in list(eqn.invars) + list(eqn.outvars)
              if hasattr(v, "aval") and list(getattr(v.aval, "shape", ())).count(t) >= 2]
        if eqn.primitive.name == "dot_general":
            if tt:
                assert len(tt) == 1 and tt[0] in inv and tt[0].aval.shape == (t, t), eqn
                shared.append(eqn)
            else:
                hi = jax.lax.Precision.HIGHEST
                assert eqn.params["precision"] in ((hi, hi), hi), eqn
                exact += 1
        else:
            assert not tt, eqn
    assert len(shared) == 1  # the readiness matmul
    assert exact >= 4  # PE and memory slot sums, at least one per NoC


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
