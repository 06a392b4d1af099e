"""SimulatorBackend API: JAX≡Python equivalence, multi-NoC fallback,
one-dispatch-per-iteration Explorer contract, and Campaign aggregation."""
import pytest

from repro.core import (
    Campaign,
    Design,
    Explorer,
    ExplorerConfig,
    HardwareDatabase,
    JaxBatchedBackend,
    PythonBackend,
    ar_complex,
    audio,
    calibrated_budget,
    edge_detection,
    make_backend,
    random_single_noc_designs,
)
from repro.core.backend import SCAL_COLS, BackendStats
from repro.core.backend import Candidate as JaxCandidate
from repro.core.blocks import make_gpp, make_noc

REL_TOL = 1e-4  # acceptance bar: backends agree on latency within 1e-4


def _multi_noc_design(g):
    """Two-NoC chain: outside the vectorized regime, must take the fallback."""
    d = Design.base(g)
    noc2 = d.add_block(make_noc(), after_noc=d.noc_chain[0])
    pe2 = d.add_block(make_gpp(400), attach_to=noc2.name)
    tasks = sorted(g.tasks)
    for t in tasks[: len(tasks) // 2]:
        d.task_pe[t] = pe2.name
    return d


# ---- equivalence ---------------------------------------------------------
@pytest.mark.parametrize("graph_fn,seed", [(edge_detection, 3), (ar_complex, 5)])
def test_backend_equivalence_randomized(graph_fn, seed):
    """Property-style: random single-NoC designs price identically (within
    float32) through either backend — latency, finish times, power, area."""
    db = HardwareDatabase()
    g = graph_fn()
    designs = random_single_noc_designs(g, 12, seed=seed)
    rp = PythonBackend(g, db).evaluate(designs)
    rj = JaxBatchedBackend(g, db).evaluate(designs)
    for i, (a, b) in enumerate(zip(rp, rj)):
        assert abs(a.latency_s - b.latency_s) / a.latency_s < REL_TOL, i
        for t in a.task_finish_s:
            ref = max(a.task_finish_s[t], 1e-12)
            assert abs(a.task_finish_s[t] - b.task_finish_s[t]) / ref < REL_TOL, (i, t)
        for w in a.workload_latency_s:
            ref = max(a.workload_latency_s[w], 1e-12)
            assert abs(a.workload_latency_s[w] - b.workload_latency_s[w]) / ref < REL_TOL
        assert abs(a.power_w - b.power_w) / a.power_w < 1e-3, i
        assert abs(a.area_mm2 - b.area_mm2) / a.area_mm2 < 1e-6, i
        assert a.mem_capacity_bytes == pytest.approx(b.mem_capacity_bytes)
        # Algorithm-1 inputs must match: bottleneck attribution drives moves
        assert a.task_bottleneck == b.task_bottleneck, i
        assert a.task_bottleneck_block == b.task_bottleneck_block, i
        assert b.total_traffic_bytes == pytest.approx(
            a.total_traffic_bytes, rel=1e-3, abs=1.0
        ), i


def test_jax_backend_prices_multi_noc_natively():
    """Multi-NoC chain designs ride the vectorized path now (no fallback):
    supports() is True, results match Python, and n_fallback stays 0."""
    db = HardwareDatabase()
    g = edge_detection()
    singles = random_single_noc_designs(g, 3, seed=1)
    multi = _multi_noc_design(g)
    jb = JaxBatchedBackend(g, db)
    assert jb.supports(multi) and all(jb.supports(d) for d in singles)

    mixed = [singles[0], multi, singles[1], singles[2]]
    got = jb.evaluate(mixed)
    ref = PythonBackend(g, db).evaluate(mixed)
    for a, b in zip(ref, got):
        assert abs(a.latency_s - b.latency_s) / a.latency_s < REL_TOL
    s = jb.stats()
    assert s.n_sims == 4 and s.n_fallback == 0 and s.n_batched == 4
    assert s.n_dispatches == 1


def test_jax_backend_fallback_beyond_max_noc():
    """Chains the encoding cannot host (> MAX_NOC NoCs) raise the typed
    UnsupportedDesignError inside the backend, which routes exactly those
    candidates to the scalar Python path mid-batch — and the capability
    check survives `python -O` (it is an exception, not an assert)."""
    import pytest as _pytest

    from repro.core.phase_sim_jax import (
        MAX_NOC, EncodedDesign, EncodedWorkload, UnsupportedDesignError,
    )

    db = HardwareDatabase()
    g = edge_detection()
    wide = Design.base(g)
    for _ in range(MAX_NOC):  # chain of MAX_NOC + 1
        wide.add_block(make_noc(), after_noc=wide.noc_chain[-1])
    with _pytest.raises(UnsupportedDesignError):
        EncodedDesign.of(wide, g, db, EncodedWorkload.of(g))

    jb = JaxBatchedBackend(g, db)
    assert not jb.supports(wide)
    single = random_single_noc_designs(g, 1, seed=4)[0]
    got = jb.evaluate([single, wide])
    ref = PythonBackend(g, db).evaluate([single, wide])
    assert got[1].latency_s == ref[1].latency_s  # exact: same scalar path
    assert abs(got[0].latency_s - ref[0].latency_s) / ref[0].latency_s < REL_TOL
    s = jb.stats()
    assert s.n_fallback == 1 and s.n_batched == 1


# ---- explorer contract ---------------------------------------------------
class _CountingBackend:
    """Wraps a backend, recording every dispatch's batch size."""

    def __init__(self, inner):
        self.inner = inner
        self.name = f"counting[{inner.name}]"
        self.tdg, self.db = inner.tdg, inner.db
        self.batches = []

    def supports(self, design):
        return self.inner.supports(design)

    def stats(self):
        return self.inner.stats()

    def evaluate(self, designs):
        self.batches.append(len(designs))
        return self.inner.evaluate(designs)

    def evaluate_candidates(self, cands):
        self.batches.append(len(cands))
        return self.inner.evaluate_candidates(cands)


def test_explorer_one_dispatch_per_iteration():
    db = HardwareDatabase()
    g = edge_detection()
    spy = _CountingBackend(PythonBackend(g, db))
    ex = Explorer(g, db, calibrated_budget(db),
                  ExplorerConfig(max_iterations=25, seed=4), backend=spy)
    res = ex.run()
    # dispatch 0 is the initial design; every search iteration issues at most
    # one evaluate() (exactly one when neighbours were generated)
    assert spy.batches[0] == 1
    assert len(spy.batches) <= res.iterations + 1 + 25  # taboo'd iters skip
    assert all(b >= 1 for b in spy.batches)
    assert sum(spy.batches) == res.n_sims == spy.stats().n_sims
    assert res.sim_wall_s > 0.0


def test_explorer_backend_config_selection():
    db = HardwareDatabase()
    g = edge_detection()
    bud = calibrated_budget(db)
    res_p = Explorer(g, db, bud, ExplorerConfig(max_iterations=15, seed=2)).run()
    res_j = Explorer(
        g, db, bud, ExplorerConfig(max_iterations=15, seed=2, backend="jax")
    ).run()
    assert res_p.backend_name == "python" and res_j.backend_name == "jax"
    # same seed, same decisions modulo float32: the searches track each other
    assert res_j.n_sims == res_p.n_sims
    assert abs(res_j.best_result.latency_s - res_p.best_result.latency_s) / max(
        res_p.best_result.latency_s, 1e-12
    ) < 1e-3
    with pytest.raises(ValueError):
        make_backend("nope", g, db)


# ---- device chain blocks -------------------------------------------------
def test_backend_run_chains_accounting_and_flush():
    """`JaxBatchedBackend.run_chains` prices one fused (R, K) block per
    dispatch and accounts every chain step in the shared stats; handles
    issued before an explicit flush() stay readable after it."""
    from repro.core import ChainRequest

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    jx = JaxBatchedBackend(g, db)
    d = random_single_noc_designs(g, 1, seed=5)[0]
    block = jx.run_chains(ChainRequest(design=d, budget=bud, r=8, k=16, seed=5))
    assert block.fitness.shape == (8,)
    assert block.move_idx.shape == (8, 16)
    stats = jx.stats()
    assert stats.n_sims == 8 * 16 and stats.n_batched == 8 * 16
    assert stats.n_dispatches == 1 and stats.n_fallback == 0
    assert jx.chain_runner().n_fallback == 0
    # handles issued before an explicit flush stay readable after it
    designs = random_single_noc_designs(g, 3, seed=2)
    cands = [JaxCandidate.of_design(d) for d in designs]
    handles = jx.evaluate_candidates(cands)
    jx.flush()
    assert all(h.result().latency_s > 0 for h in handles)


def test_explorer_run_chains_e2e():
    """`Explorer.run_chains` drives the chain-batched coroutine end to end:
    chained result, per-step history, committed n_sims = R·K per block plus
    the winner's single decode."""
    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    jx = JaxBatchedBackend(g, db)
    ex = Explorer(
        g, db, bud,
        ExplorerConfig(policy="device_sa", max_iterations=32, seed=7,
                       chain_r=8, chain_k=16),
        backend=jx,
    )
    res = ex.run_chains()
    assert res.chained and res.chain_r == 8
    assert res.iterations == 32
    assert len(res.history) == 32
    assert all(h["move"] == "chain_migrate" for h in res.history)
    assert res.n_sims == 8 * 32 + 1  # two blocks of 16 + final decode
    assert res.best_result.latency_s > 0
    assert jx.chain_runner().n_compiles == 1  # one (R, K) shape, one jit


def test_adopt_encoding_invalidates_on_fallback_winner():
    """Accepting a fallback-priced (e.g. topology) move mutates the base
    design without producing a row encoding — adopt_encoding must DROP the
    previously adopted encoding rather than leave a stale one (regression:
    phantom missing-block KeyErrors deep into multi-seed campaigns)."""
    from repro.core.backend import _ReadyHandle
    from repro.core.phase_sim_jax import EncodedDesign

    db = HardwareDatabase()
    g = edge_detection()
    jx = JaxBatchedBackend(g, db)
    d = random_single_noc_designs(g, 1, seed=3)[0]
    cand = JaxCandidate.of_design(d)
    (h,) = jx.evaluate_candidates([cand])
    h.result()
    jx.adopt_encoding(h)
    assert id(d) in jx._adopted
    # same design comes back priced by the fallback path and gets accepted
    ready = _ReadyHandle(h.result(), 0.0, cand)
    jx.adopt_encoding(ready)
    assert id(d) not in jx._adopted
    # and a subsequent dispatch re-encodes from the real object graph
    (h2,) = jx.evaluate_candidates([JaxCandidate.of_design(d)])
    assert abs(h2.result().latency_s - h.result().latency_s) < 1e-12
    # re-adopting the fresh row matches a from-scratch encode of the design
    jx.adopt_encoding(h2)
    fresh = EncodedDesign.of(d, g, db, jx._enc)
    assert set(jx._adopted[id(d)][1].pe_slot) == set(fresh.pe_slot)
    assert set(jx._adopted[id(d)][1].mem_slot) == set(fresh.mem_slot)


# ---- campaign ------------------------------------------------------------
def test_campaign_smoke_two_seeds_two_workloads():
    """2 seeds × 2 workloads: per-run results come back, n_sims aggregates
    exactly, and all runs of one workload share one backend."""
    db = HardwareDatabase()
    g_ed, g_au = edge_detection(), audio()
    bud = calibrated_budget(db)
    camp = Campaign.sweep(
        db, {"ed": g_ed, "audio": g_au}, bud, seeds=(1, 2),
        backend="jax", max_iterations=40,
    )
    res = camp.run()
    assert set(res.runs) == {
        "ed.farsi.s1", "ed.farsi.s2", "audio.farsi.s1", "audio.farsi.s2"
    }
    assert res.aggregate["n_runs"] == 4
    assert res.aggregate["n_converged"] >= 1  # edge_detection converges fast
    assert res.aggregate["n_sims_total"] == sum(r.n_sims for r in res.runs.values())
    # one shared backend per workload, cross-batching all its runs
    assert set(res.backend_stats) == {"ed", "audio"}
    assert isinstance(res.backend_stats["ed"], BackendStats)
    for wl, prefix in (("ed", "ed."), ("audio", "audio.")):
        # every dispatched candidate belongs to exactly one run — the shared
        # backend's count is the sum of the per-run committed n_sims
        per_run = sum(
            r.n_sims for n, r in res.runs.items() if n.startswith(prefix)
        )
        assert res.backend_stats[wl].n_sims == per_run
        # cross-batched: far fewer dispatches than sims (≥2 runs per dispatch)
        assert res.backend_stats[wl].n_dispatches < per_run
    assert res.aggregate["n_sims_total"] == sum(
        s.n_sims for s in res.backend_stats.values()
    )
    assert res.aggregate["sim_wall_s_total"] > 0.0
    assert res.converged_runs()


def test_campaign_distinct_graphs_same_name_keep_separate_stats():
    """Two distinct graph objects sharing a name get distinct backends AND
    distinct backend_stats entries (suffix-disambiguated)."""
    db = HardwareDatabase()
    g1, g2 = edge_detection(), edge_detection()
    bud = calibrated_budget(db)
    camp = (
        Campaign(db)
        .add("a", g1, bud, ExplorerConfig(max_iterations=5))
        .add("b", g2, bud, ExplorerConfig(max_iterations=5))
    )
    res = camp.run()
    assert set(res.backend_stats) == {"ed", "ed#1"}
    assert res.backend_stats["ed"].n_sims == res.runs["a"].n_sims
    assert res.backend_stats["ed#1"].n_sims == res.runs["b"].n_sims


def test_campaign_duplicate_name_rejected():
    db = HardwareDatabase()
    g = edge_detection()
    bud = calibrated_budget(db)
    camp = Campaign(db).add("a", g, bud, ExplorerConfig(max_iterations=5))
    with pytest.raises(ValueError):
        camp.add("a", g, bud, ExplorerConfig(max_iterations=5))
    # a per-run backend that conflicts with the shared campaign backend is
    # refused rather than silently overridden
    with pytest.raises(ValueError):
        camp.add("b", g, bud, ExplorerConfig(max_iterations=5, backend="jax"))


# ---- packed scal layout ----------------------------------------------------
# simulate_batch's bneck_kind_s columns, in its own order
_KIND_COL = {"kind_pe_s": 0, "kind_mem_s": 1, "kind_noc_s": 2}


def _named(out, col):
    """The output of ``simulate_batch`` (or of the host unpack) named ``col``."""
    if col in _KIND_COL:
        return out["bneck_kind_s"][:, _KIND_COL[col]]
    return out[col]


@pytest.fixture(scope="module")
def audio_packed():
    """One audio batch priced twice from the same rows: ``simulate_batch``'s
    named outputs, and the backend's packed dispatch with its host unpack."""
    import jax
    import numpy as np

    from repro.core.backend import _JaxBatch
    from repro.core.phase_sim_jax import encode_batch, fill_budget, simulate_batch

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    jb = JaxBatchedBackend(g, db)
    designs = random_single_noc_designs(g, 8, seed=1)
    slots = max(max(len(d.pes()), len(d.mems())) for d in designs)
    rows = encode_batch(designs, g, db, jb._enc, n_pe=slots, n_mem=slots)
    for j in range(len(designs)):
        fill_budget(rows, j, jb._enc, bud.latency_s, bud.power_w, bud.area_mm2, 0.05)
    ref = jax.device_get(jax.jit(lambda r: simulate_batch(jb._enc, r))(rows))
    # no two named columns agree on this batch, so a column that lands
    # under another's name cannot pass by coincidence
    cols = [np.asarray(_named(ref, c), np.float32) for c in SCAL_COLS]
    assert not any(
        np.array_equal(a, b) for i, a in enumerate(cols) for b in cols[i + 1:]
    )
    packed = jb._fn()(rows)
    raw = np.asarray(jax.device_get(packed["scal"]))
    dims = (slots, rows["noc_bw"].shape[1])
    host = _JaxBatch(packed, BackendStats(), [None] * len(designs), dims).host()
    return ref, raw, host


@pytest.mark.parametrize("col", SCAL_COLS)
def test_packed_scal_column_lands_under_its_name(audio_packed, col):
    """The packed scal matrix holds ``simulate_batch``'s output named ``col``
    at ``SCAL_COLS.index(col)``, and the host unpack returns it under that
    name (the kind triple as ``bneck_kind_s``, the top-bottleneck pair as
    ``top_bneck_pe``/``top_bneck_mem``)."""
    import numpy as np

    ref, raw, host = audio_packed
    want = np.asarray(_named(ref, col), np.float32)
    np.testing.assert_allclose(raw[:, SCAL_COLS.index(col)], want, rtol=1e-6)
    np.testing.assert_allclose(_named(host, col), want, rtol=1e-6)
