"""Heuristic-policy layer: golden-sequence equivalence with the pre-refactor
Explorer, registry plumbing, device bottleneck-telemetry parity, and the
telemetry-driven policies' behaviour."""
import json
import os

import pytest

from repro.core import (
    POLICIES,
    Explorer,
    ExplorerConfig,
    HardwareDatabase,
    JaxBatchedBackend,
    PythonBackend,
    SimTelemetry,
    ar_complex,
    audio,
    calibrated_budget,
    edge_detection,
    make_policy,
    random_single_noc_designs,
    simulate,
)
from repro.core.backend import Candidate
from repro.core.blocks import BlockKind
from repro.core.policy import AWARENESS_POLICY, FarsiPolicy, NaiveSA

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden_policy_seqs.json")
GRAPHS = {"audio": audio, "ar_complex": ar_complex, "ed": edge_detection}


def _seq(res):
    return [[h["iteration"], h["move"], int(h["accepted"])] for h in res.history]


# ---------------------------------------------------------------------------
# golden-sequence regression: the search replays the recorded sequences
# bit-for-bit per backend (fixtures regenerated by gen_golden_policy_seqs.py
# whenever behaviour changes DELIBERATELY — last: the apply_fork anchor-task
# bugfix + native multi-NoC pricing; anything else diverging is a bug)
# ---------------------------------------------------------------------------
with open(GOLDEN) as f:
    _GOLD = json.load(f)


@pytest.mark.slow  # full golden replays: minutes of wall — nightly, not tier-1
@pytest.mark.parametrize("key", sorted(_GOLD))
def test_policy_replays_pre_refactor_golden(key):
    ref = _GOLD[key]
    gname, aware, s, it = key.split("@")[0].split(".")
    seed, iters = int(s[1:]), int(it[2:])
    g = GRAPHS[gname]()
    db = HardwareDatabase()
    bud = calibrated_budget(db)
    for backend in ref["backends"]:
        res = Explorer(
            g, db, bud,
            ExplorerConfig(awareness=aware, max_iterations=iters, seed=seed,
                           backend=backend),
        ).run()
        assert _seq(res) == ref["seq"], (key, backend)
        assert res.n_sims == ref["n_sims"], (key, backend)


def test_device_chain_block_parity_with_host_loop():
    """The acceptance bar, device edition: at R=1 the fused K-step block
    replays the host-driven loop (same compiled step, one dispatch + one
    carry round trip per iteration) bit-for-bit — moves, accepts, fitness
    trace, and the full carry."""
    import numpy as np

    from repro.core import DeviceChainRunner

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    d = random_single_noc_designs(g, 1, seed=7)[0]
    runner = DeviceChainRunner(g, db)
    fused = runner.run_chains(d, bud, r=1, k=16, seed=7)
    host = runner.run_chains_host(d, bud, r=1, n_steps=16, seed=7)
    assert fused.seq(0) == host.seq(0)
    assert np.array_equal(fused.fit_trace, host.fit_trace)
    for a, b in zip(fused.carry, host.carry):
        assert np.array_equal(a, b)
    assert runner.n_fallback == 0


def test_device_chain_sequence_independent_of_population():
    """Per-chain PRNG keys are fold_in(seed, chain) — chain i's accepted-move
    sequence must be identical whether it runs in an R=16 or an R=256
    population (population size never perturbs a chain)."""
    import numpy as np

    from repro.core import DeviceChainRunner

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    d = random_single_noc_designs(g, 1, seed=11)[0]
    runner = DeviceChainRunner(g, db)
    small = runner.run_chains(d, bud, r=16, k=8, seed=3)
    big = runner.run_chains(d, bud, r=256, k=8, seed=3)
    for chain in (0, 5, 15):
        assert small.seq(chain) == big.seq(chain), chain
    assert np.array_equal(small.fit_trace, big.fit_trace[:16])
    assert np.array_equal(small.task_pe, big.task_pe[:16])
    assert np.array_equal(small.task_mem, big.task_mem[:16])


def test_device_sa_checkpoint_restores_device_carry_bitexact():
    """``device_sa`` checkpoints must deep-copy the device carry: mutating
    the live carry after checkpoint() cannot leak into the snapshot, and a
    block resumed from the restored carry replays the continuation that the
    uninterrupted run produced."""
    import numpy as np

    from repro.core import DeviceChainRunner, make_policy

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    d = random_single_noc_designs(g, 1, seed=5)[0]
    from repro.core.device_explore import copy_carry

    runner = DeviceChainRunner(g, db)
    first = runner.run_chains(d, bud, r=4, k=8, seed=9)
    pol = make_policy("device_sa")
    pol.device_carry = copy_carry(first.carry)  # the live (mutable) state
    ck = pol.checkpoint()
    snapshot = [np.array(x, copy=True) for x in first.carry]
    # the reference continuation, then clobber the live carry
    ref = runner.run_chains(d, bud, r=4, k=8, seed=9, it0=8, carry=first.carry)
    for x in pol.device_carry:
        x.fill(0)
    pol.restore(ck)
    for a, b in zip(pol.device_carry, snapshot):
        assert np.array_equal(a, b)
    resumed = runner.run_chains(
        d, bud, r=4, k=8, seed=9, it0=8, carry=pol.device_carry
    )
    assert resumed.seq(0) == ref.seq(0)
    assert np.array_equal(resumed.fit_trace, ref.fit_trace)
    for a, b in zip(resumed.carry, ref.carry):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# registry + config plumbing
# ---------------------------------------------------------------------------
def test_policy_registry_and_config_selection():
    assert len(POLICIES) >= 4
    assert set(AWARENESS_POLICY.values()) <= set(POLICIES)
    db = HardwareDatabase()
    g = edge_detection()
    bud = calibrated_budget(db)
    for name in POLICIES:
        res = Explorer(
            g, db, bud, ExplorerConfig(policy=name, max_iterations=8, seed=1)
        ).run()
        assert res.policy_name == name
        assert res.iterations >= 1
    with pytest.raises(ValueError):
        make_policy("nope")
    # the awareness ladder still maps onto policies
    res = Explorer(g, db, bud, ExplorerConfig(awareness="sa", max_iterations=5)).run()
    assert res.policy_name == "naive_sa"
    assert isinstance(make_policy("farsi"), FarsiPolicy)
    assert isinstance(make_policy("naive_sa"), NaiveSA)


# ---------------------------------------------------------------------------
# telemetry parity: device columns vs host SimResult attribution
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("graph_fn,seed", [(audio, 3), (ar_complex, 5)])
@pytest.mark.parametrize("n_noc", [1, 2])
def test_bottleneck_telemetry_matches_host_attribution(graph_fn, seed, n_noc):
    """Acceptance bar: the device-side per-block bottleneck telemetry agrees
    with the Python simulator's host attribution to ≤ 1e-5 (relative to the
    design's latency), on single-NoC designs and on forked 2-NoC chains;
    the top-bottleneck argmax column resolves to the same block."""
    # the forked chains are built as the multi-NoC parity test builds them
    from test_multi_noc import chain_designs

    db = HardwareDatabase()
    g = graph_fn()
    jb = JaxBatchedBackend(g, db)
    if n_noc == 1:
        designs = random_single_noc_designs(g, 6, seed=seed)
    else:
        designs = chain_designs(g, n_noc, 6, seed=seed)
    handles = jb.evaluate_candidates([Candidate.of_design(d) for d in designs])
    for d, h in zip(designs, handles):
        ref = simulate(d, g, db)
        got = h.result()
        tol = 1e-5 * max(ref.latency_s, 1e-12) * len(g.tasks)
        assert set(got.block_bottleneck_s) == set(ref.block_bottleneck_s)
        for name, s in ref.block_bottleneck_s.items():
            assert abs(got.block_bottleneck_s[name] - s) <= tol, (name, s)
        # kind sums tie the per-block split to the class attribution
        for kind, blocks in (
            ("pe", [n for n, b in d.blocks.items() if b.kind == BlockKind.PE]),
            ("mem", [n for n, b in d.blocks.items() if b.kind == BlockKind.MEM]),
        ):
            assert abs(
                sum(got.block_bottleneck_s[n] for n in blocks)
                - ref.bottleneck_s[kind]
            ) <= tol
        tel = h.telemetry()
        ref_tel = SimTelemetry.of_result(ref, g, d)
        assert tel.top_bneck_pe() == ref_tel.top_bneck_pe()
        assert tel.top_bneck_mem() == ref_tel.top_bneck_mem()
        assert abs(tel.comp_s - ref_tel.comp_s) <= tol
        assert abs(tel.comm_s - ref_tel.comm_s) <= tol


def test_telemetry_view_matches_decode_bitwise():
    """A row-backed telemetry view must produce the exact floats the lazy
    decode produces (shared scalar helpers) — this is what makes the
    telemetry-driven FarsiPolicy bit-identical to the decode-driven one."""
    from repro.core.budgets import distance

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    jb = JaxBatchedBackend(g, db)
    d = random_single_noc_designs(g, 1, seed=2)[0]
    (h,) = jb.evaluate_candidates([Candidate.of_design(d, bud)])
    tel = h.telemetry()
    res = h.result()
    assert tel.dist(bud).per_metric == distance(res, bud).per_metric
    assert tel.dist(bud).per_workload_latency == distance(res, bud).per_workload_latency
    for t in g.tasks:
        assert tel.task_finish_s(t) == res.task_finish_s[t]
        assert tel.task_energy_j(t) == res.task_energy_j[t]
        assert tel.task_bneck(t) == res.task_bottleneck[t]
        assert tel.task_bneck_block(t) == res.task_bottleneck_block[t]
    for m in d.mems():
        assert tel.mem_capacity(m) == res.mem_capacity_bytes[m]
    assert tel.block_bneck_s() == res.block_bottleneck_s


# ---------------------------------------------------------------------------
# telemetry-driven policies
# ---------------------------------------------------------------------------
def test_bottleneck_policy_targets_top_bottleneck_block():
    """BottleneckRelaxation must aim at the device's top-bottleneck column:
    on a fresh base design every task shares one PE, so the first focus is
    that PE (comp-bound) with the longest-duration hosted task."""
    from repro.core import Design

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    d = Design.base(g)
    py = PythonBackend(g, db)
    (h,) = py.evaluate_candidates([Candidate.of_design(d, bud)])
    tel = h.telemetry()
    pol = make_policy("bottleneck")
    import random

    pol.bind(g, db, bud, ExplorerConfig(), random.Random(0))
    focus = pol.select_focus(d, tel.dist(bud), tel)
    assert focus.block == tel.top_bneck_pe()
    assert focus.task in d.tasks_on_pe(focus.block)
    assert focus.task == max(d.tasks_on_pe(focus.block), key=tel.task_duration)


def test_policy_convergence_ordering_on_ed():
    """Paper §5.2 qualitative ordering at a fixed iteration budget: the
    architecture-aware policies must land at least as close to budget as
    naive SA, with FarsiPolicy converging."""
    db = HardwareDatabase()
    g = edge_detection()
    bud = calibrated_budget(db)
    dist = {}
    for name in ("naive_sa", "bottleneck", "locality", "farsi"):
        res = Explorer(
            g, db, bud, ExplorerConfig(policy=name, max_iterations=60, seed=3)
        ).run()
        dist[name] = res.best_distance.city_block()
    assert dist["farsi"] == 0.0
    assert max(dist["bottleneck"], dist["locality"], dist["farsi"]) <= dist["naive_sa"]
