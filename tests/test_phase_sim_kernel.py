"""Fused Pallas phase-sim kernel ≡ the vmap'd XLA oracle ≡ the Python
simulator — across pow2 batch buckets and both paper workload scales, with
interpret mode forced so CPU tier-1 exercises the REAL kernel path (grid,
block specs, VMEM scratch, padded-task masking), not just the oracle."""
import numpy as np
import pytest

from repro.core import (
    HardwareDatabase,
    PythonBackend,
    ar_complex,
    audio,
    calibrated_budget,
    make_backend,
    random_single_noc_designs,
)
from repro.core.phase_sim_jax import EncodedWorkload, encode_batch, fill_budget

KERNEL_REL_TOL = 1e-5  # acceptance bar: Pallas vs ref parity
# every output the kernel must reproduce (bit-compatible math, f32 rounding)
_CHECK_KEYS = (
    "latency_s", "finish_s", "bneck_code", "bneck_kind_s", "alp_time_s",
    "traffic_bytes", "n_phases", "wl_latency_s", "energy_j", "power_w",
    "area_mm2", "fitness", "all_done", "pe_bneck_s", "mem_bneck_s",
    "noc_bneck_s", "top_bneck_pe", "top_bneck_mem",
)


@pytest.mark.parametrize("graph_fn", [audio, ar_complex])
@pytest.mark.parametrize(
    "batch", [1, 8, pytest.param(64, marks=pytest.mark.slow)]
)
def test_kernel_matches_ref_oracle(graph_fn, batch):
    """Interpret-mode kernel vs the pure-jnp oracle, every output column,
    ≤ 1e-5 relative — including the Eq.-7 fitness the explorer ranks by."""
    _assert_kernel_matches_ref_oracle(graph_fn, batch)


def test_kernel_matches_ref_oracle_at_mosaic_lane_width(monkeypatch):
    """The same parity with the task axis padded to the 128 lanes Mosaic
    compiles (interpret mode otherwise pads to 8): the chip's layout, with
    100 padded tasks on ``ar_complex``, exercised on CPU."""
    from repro.kernels.phase_sim import ops

    monkeypatch.setattr(ops, "INTERPRET_LANE", ops.LANE)
    _assert_kernel_matches_ref_oracle(ar_complex, 8)


def _assert_kernel_matches_ref_oracle(graph_fn, batch):
    import jax

    from repro.kernels.phase_sim import phase_sim, phase_sim_ref

    db = HardwareDatabase()
    g = graph_fn()
    enc = EncodedWorkload.of(g)
    designs = random_single_noc_designs(g, batch, seed=batch + 1)
    bud = calibrated_budget(db)
    rows = encode_batch(designs, g, db, enc)
    for j in range(batch):
        fill_budget(rows, j, enc, bud.latency_s, bud.power_w, bud.area_mm2, 0.05)
    ref = jax.jit(lambda r: phase_sim_ref(enc, r))(rows)
    got = jax.jit(lambda r: phase_sim(enc, r, interpret=True))(rows)
    assert set(_CHECK_KEYS) <= set(got)
    for k in _CHECK_KEYS:
        a = np.asarray(ref[k], np.float64)
        b = np.asarray(got[k], np.float64)
        assert a.shape == b.shape, k
        rel = np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-12)) if a.size else 0.0
        assert rel <= KERNEL_REL_TOL, (k, rel)
    # integer outputs keep integer dtypes through the packed scal block
    assert np.asarray(got["bneck_code"]).dtype == np.int32
    assert np.asarray(got["n_phases"]).dtype == np.int32
    assert np.asarray(got["all_done"]).dtype == bool


@pytest.mark.parametrize("graph_fn", [audio, ar_complex])
def test_pallas_backend_matches_python(graph_fn, monkeypatch):
    """The registered "pallas" backend (kernel forced through interpret mode
    on CPU) prices designs identically to the scalar Python simulator."""
    db = HardwareDatabase()
    g = graph_fn()
    designs = random_single_noc_designs(g, 8, seed=13)
    jb = make_backend("pallas", g, db)
    assert jb.name == "jax_pallas"
    got = jb.evaluate(designs)
    ref = PythonBackend(g, db).evaluate(designs)
    for i, (a, b) in enumerate(zip(ref, got)):
        assert abs(a.latency_s - b.latency_s) / a.latency_s < 1e-4, i
        for t in a.task_finish_s:
            r = max(a.task_finish_s[t], 1e-12)
            assert abs(a.task_finish_s[t] - b.task_finish_s[t]) / r < 1e-4, (i, t)
        assert a.task_bottleneck == b.task_bottleneck, i
        assert abs(a.power_w - b.power_w) / a.power_w < 1e-3, i
        assert abs(a.area_mm2 - b.area_mm2) / a.area_mm2 < 1e-6, i


def test_kernel_env_var_forces_kernel_path(monkeypatch):
    """REPRO_PHASE_SIM_KERNEL=1 flips the default backend onto the kernel."""
    from repro.core import JaxBatchedBackend

    db = HardwareDatabase()
    g = audio()
    monkeypatch.setenv("REPRO_PHASE_SIM_KERNEL", "1")
    assert JaxBatchedBackend(g, db).name == "jax_pallas"
    monkeypatch.setenv("REPRO_PHASE_SIM_KERNEL", "0")
    assert JaxBatchedBackend(g, db).name == "jax"


def test_default_backend_on_tpu_is_xla_path(monkeypatch):
    """On TPU the default backend and its chain runner price through the XLA
    path; the kernel is reached only by name or REPRO_PHASE_SIM_KERNEL=1."""
    import jax

    from repro.core import JaxBatchedBackend

    db = HardwareDatabase()
    g = audio()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PHASE_SIM_KERNEL", raising=False)
    jb = JaxBatchedBackend(g, db)
    assert jb.name == "jax"
    assert jb.chain_runner().use_kernel is False
    assert make_backend("jax", g, db).name == "jax"
    pallas = make_backend("pallas", g, db)
    assert pallas.name == "jax_pallas"
    assert pallas.chain_runner().use_kernel is True
    assert not pallas.stats().kernel_interpret
    monkeypatch.setenv("REPRO_PHASE_SIM_KERNEL", "1")
    assert JaxBatchedBackend(g, db).name == "jax_pallas"
