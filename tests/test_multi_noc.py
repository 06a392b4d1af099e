"""Array-native multi-NoC regime: forked/joined chain designs price
identically through the scalar Python simulator and the batched JAX backend
on both paper workloads, topology-move-enabled explorations never hit
the scalar fallback, and the development-cost policy lands the §5.3
complexity-reduction comparison through ``Campaign.aggregate``."""
import random

import numpy as np
import pytest

from repro.core import (
    Campaign,
    Design,
    Explorer,
    ExplorerConfig,
    HardwareDatabase,
    JaxBatchedBackend,
    ar_complex,
    audio,
    calibrated_budget,
    synthetic_family,
)
from repro.core.backend import Candidate
from repro.core.blocks import make_accelerator, make_mem
from repro.core.moves import MoveDelta, apply_fork, apply_join
from repro.core.phase_sim import simulate

PARITY_REL_TOL = 1e-5  # acceptance bar: multi-NoC backends agree ≤ 1e-5


def chain_designs(g, n_noc: int, count: int, seed: int = 0):
    """``count`` designs with an ``n_noc``-deep chain, built the way the
    explorer builds them: real NoC forks (which re-home half the attached
    blocks per fork) on top of a randomized single-NoC design, then random
    remapping so routes span the chain. Link counts stay at the default 1 —
    the regime NoC forks explore (relief via more buses, not more links)."""
    rng = random.Random(seed)
    tasks = sorted(g.tasks)
    out = []
    for _ in range(count):
        d = Design.base(g)
        noc0 = d.noc_chain[0]
        for _ in range(rng.randint(2, 4)):
            if rng.random() < 0.5:
                t = rng.choice(tasks)
                b = d.add_block(make_accelerator(t, rng.choice((100, 400))),
                                attach_to=noc0)
                d.task_pe[t] = b.name
            else:
                d.add_block(make_mem(rng.choice(("dram", "sram")),
                                     rng.choice((100, 800)), 32),
                            attach_to=noc0)
        while len(d.noc_chain) < n_noc:
            assert apply_fork(d, g, rng.choice(d.noc_chain))
        pes, mems = d.pes(), d.mems()
        for t in tasks:
            d.task_pe[t] = rng.choice(pes)
            d.task_mem[t] = rng.choice(mems)
        assert len(d.noc_chain) == n_noc
        out.append(d)
    return out


@pytest.mark.parametrize("n_noc", [2, 3])
@pytest.mark.parametrize(
    "batch", [1, 8, pytest.param(64, marks=pytest.mark.slow)]
)
@pytest.mark.parametrize("graph_fn", [audio, ar_complex])
def test_multi_noc_parity_python_vs_jax(n_noc, batch, graph_fn):
    """Forked chains (N ∈ {2, 3}) priced by PythonBackend vs
    JaxBatchedBackend — audio and ar_complex, B ∈ {1, 8, 64} — agree
    ≤ 1e-5 on latency, per-task finish times, PPA, fitness, and the
    Algorithm-1 bottleneck attribution."""
    db = HardwareDatabase()
    g = graph_fn()
    bud = calibrated_budget(db)
    designs = chain_designs(g, n_noc, batch, seed=10 * n_noc + batch)
    jb = JaxBatchedBackend(g, db)
    cands = [Candidate.of_design(d, bud) for d in designs]
    handles = jb.evaluate_candidates(cands)
    assert jb.stats().n_fallback == 0 and jb.stats().n_batched == batch
    for i, (d, h) in enumerate(zip(designs, handles)):
        ref = simulate(d, g, db)
        got = h.result()
        rel = lambda a, b: abs(a - b) / max(abs(a), 1e-12)
        assert rel(ref.latency_s, got.latency_s) <= PARITY_REL_TOL, i
        for t, f in ref.task_finish_s.items():
            assert rel(f, got.task_finish_s[t]) <= PARITY_REL_TOL, (i, t)
        assert rel(ref.energy_j, got.energy_j) <= 1e-4, i
        assert rel(ref.area_mm2, got.area_mm2) <= 1e-4, i
        from repro.core.budgets import distance

        assert rel(distance(ref, bud).fitness(0.05), h.fitness) <= 1e-4, i
        # multi-hop routing shows up in the bottleneck attribution too
        assert got.task_bottleneck == ref.task_bottleneck, i
        assert got.task_bottleneck_block == ref.task_bottleneck_block, i
        for name, s in ref.block_bottleneck_s.items():
            tol = PARITY_REL_TOL * max(ref.latency_s, 1e-12) * len(g.tasks)
            assert abs(got.block_bottleneck_s[name] - s) <= tol, (i, name)


def test_all_join_batch_buckets_to_base_shape():
    """Regression (caught driving the DSE campaign): a batch whose every
    candidate REMOVES a NoC/block must still bucket to the BASE design's
    shape — the group fill broadcasts the base row before applying diffs,
    so a bucket sized off the (smaller) candidate encodings overflows."""
    from repro.core.moves import MoveSpec

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    (d,) = chain_designs(g, 3, 1, seed=11)
    ck = d.checkpoint()
    cands = []
    for noc in (d.noc_chain[1], d.noc_chain[2]):
        delta = MoveDelta()
        assert apply_join(d, g, noc, delta=delta)
        d.restore(ck)
        cands.append(Candidate(
            base=d, spec=MoveSpec("join", noc, None, -1, "noc", "area"),
            delta=delta, budget=bud,
        ))
    jb = JaxBatchedBackend(g, db)
    handles = jb.evaluate_candidates(cands)
    assert jb.stats().n_fallback == 0
    for c, h in zip(cands, handles):
        with c.materialized(g) as joined:
            ref = simulate(joined, g, db)
        got = h.result()
        assert abs(got.latency_s - ref.latency_s) / ref.latency_s <= 1e-4


def test_joined_chain_parity_after_noc_join():
    """A chain that grew and then shrank (fork → join) prices identically —
    the join's removed-NoC + re-attachment delta compacts the encoding the
    same way a from-scratch encode sees the design."""
    db = HardwareDatabase()
    g = audio()
    (d,) = chain_designs(g, 3, 1, seed=5)
    delta = MoveDelta()
    assert apply_join(d, g, d.noc_chain[1], delta=delta)
    assert len(d.noc_chain) == 2 and delta.removed and not delta.topology
    ref = simulate(d, g, db)
    got = JaxBatchedBackend(g, db).evaluate([d])[0]
    assert abs(got.latency_s - ref.latency_s) / ref.latency_s <= PARITY_REL_TOL


def test_topology_exploration_never_falls_back():
    """Acceptance bar: a topology-move-enabled exploration on the JAX
    backend — seeded from a multi-NoC design so NoC fork/join candidates
    are generated and accepted — completes with ``n_fallback == 0``."""
    db = HardwareDatabase()
    g = ar_complex()
    bud = calibrated_budget(db).scaled(0.5)  # tight: keeps the search moving
    (initial,) = chain_designs(g, 3, 1, seed=2)
    jb = JaxBatchedBackend(g, db)
    res = Explorer(
        g, db, bud, ExplorerConfig(max_iterations=120, seed=3), backend=jb
    ).run(initial=initial)
    s = jb.stats()
    assert s.n_fallback == 0, s
    assert s.n_batched > 0
    assert res.iterations > 0
    # the topology candidates really were priced (chain length varied) and
    # the final design still decodes cleanly against its own blocks
    assert set(res.best_result.task_bottleneck_block.values()) <= set(
        res.best_design.blocks
    )


def test_accepted_noc_fork_adopts_row_encoding():
    """Accepting a NoC fork promotes the winner's delta-encoding as the
    base's cached encoding — and it must equal a from-scratch encode of the
    mutated design (chain order, attachments, slots)."""
    from repro.core.phase_sim_jax import EncodedDesign

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    (d,) = chain_designs(g, 2, 1, seed=7)
    jb = JaxBatchedBackend(g, db)
    ck = d.checkpoint()
    delta = MoveDelta()
    assert apply_fork(d, g, d.noc_chain[0], delta=delta)
    d.restore(ck)
    from repro.core.moves import MoveSpec

    spec = MoveSpec("fork", d.noc_chain[0], None, +1, "noc", "latency")
    cand = Candidate(base=d, spec=spec, delta=delta, budget=bud)
    (h,) = jb.evaluate_candidates([cand])
    assert np.isfinite(h.fitness)
    cand.accept(g)
    jb.adopt_encoding(h)
    adopted = jb._adopted[id(d)][1]
    fresh = EncodedDesign.of(d, g, db, jb._enc)
    assert adopted.noc_slot == fresh.noc_slot
    assert np.array_equal(adopted.pe_noc, fresh.pe_noc)
    assert np.array_equal(adopted.mem_noc, fresh.mem_noc)
    assert np.array_equal(adopted.noc_bw, fresh.noc_bw)
    # and the adopted encoding prices the mutated design correctly
    (h2,) = jb.evaluate_candidates([Candidate.of_design(d, bud)])
    ref = simulate(d, g, db)
    assert abs(h2.result().latency_s - ref.latency_s) / ref.latency_s <= 1e-4


# ---------------------------------------------------------------------------
# §5.3 development-cost comparison through Campaign.aggregate
# ---------------------------------------------------------------------------
def test_dev_cost_policy_reduces_complexity_vs_farsi():
    """Acceptance bar: a dev_cost-vs-farsi policy sweep over the generated
    scenario family converges on both policies, never falls back, and
    ``Campaign.aggregate`` reports component-count/variation reductions
    (strict on ≥ 2 scenarios) — the §5.3 development-cost result."""
    db = HardwareDatabase()
    scens = synthetic_family(seed=0, n=4, db=db)
    camp = Campaign.policy_sweep(
        db, scens, policies=("farsi", "dev_cost"), seeds=(0,),
        backend="jax", max_iterations=150,
    )
    res = camp.run()
    for stats in res.backend_stats.values():
        assert stats.n_fallback == 0, stats
    pc = res.policy_complexity()
    assert set(pc) == {"farsi", "dev_cost"}
    for k in ("components", "noc_components", "variation"):
        assert pc["dev_cost"][k] <= pc["farsi"][k], (k, pc)
        assert f"complexity_{k}_mean" in res.aggregate
        assert res.aggregate[f"dev_cost_{k}_reduction"] >= 0.0, k
    # strictly simpler (fewer components and/or less variation) on ≥ 2
    # scenarios, and no scenario got MORE complex under dev_cost
    strict = 0
    for s in scens:
        mf = res.runs[f"{s.name}.farsi.s0"].best_design.complexity_metrics()
        md = res.runs[f"{s.name}.dev_cost.s0"].best_design.complexity_metrics()
        assert md["components"] <= mf["components"], s.name
        assert md["variation"] <= mf["variation"] + 1e-9, s.name
        strict += (md["components"] < mf["components"]) or (
            md["variation"] < mf["variation"] - 1e-9
        )
    assert strict >= 2, res.policy_complexity()
    # development-cost awareness must not wreck convergence: dev_cost still
    # reaches budget on every scenario
    assert all(
        res.runs[f"{s.name}.dev_cost.s0"].converged for s in scens
    ), res.aggregate


def test_dev_cost_penalty_shape():
    """The penalty is exact and signed: growing moves pay, simplifying
    moves are subsidised, knob swaps on uniform blocks are free."""
    import random as _random

    from repro.core import make_policy

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    pol = make_policy("dev_cost")
    pol.bind(g, db, bud, ExplorerConfig(), _random.Random(0))
    d = Design.base(g)
    base_pe = d.pes()[0]

    def cand_for(move, block, task=None):
        ck = d.checkpoint()
        delta = MoveDelta()
        from repro.core.moves import MoveSpec, apply_move

        ok = apply_move(d, g, move, block, task, +1, "pe", "latency",
                        _random.Random(0), delta)
        d.restore(ck)
        assert ok, move
        return Candidate(base=d, spec=MoveSpec(move, block, task, +1, "pe",
                                               "latency"), delta=delta)

    grow = pol.move_penalty(d, cand_for("fork", base_pe))
    assert grow > 0.0
    swap = pol.move_penalty(d, cand_for("swap", base_pe))
    assert abs(swap) < grow
    # an unmoved candidate (initial design pricing) costs nothing
    assert pol.move_penalty(d, Candidate.of_design(d)) == 0.0
