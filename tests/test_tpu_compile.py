"""The main path's device programs compile for a TPU v5e chip.

Nothing here runs on a chip: each test lowers a program for one device of a
described (not attached) ``v5e:2x2`` topology and compiles it with the
TPU's own compiler, which refuses what interpret mode and the CPU accept —
block shapes off the (8, 128) tiling, primitives Mosaic cannot lower, more
VMEM than a kernel may use. Only shapes are passed, never arrays.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and it holds it until it exits.
"""
import jax
import numpy as np
import pytest

from repro.core import (
    DeviceChainRunner,
    HardwareDatabase,
    MoveTable,
    ar_complex,
    audio,
    calibrated_budget,
    random_single_noc_designs,
)
from repro.core.phase_sim_jax import EncodedDesign, EncodedWorkload, encode_batch, simulate_batch

B = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=sharding),
        tree,
    )


def _rows(g, n_noc):
    db = HardwareDatabase()
    enc = EncodedWorkload.of(g)
    rows = encode_batch(random_single_noc_designs(g, B, seed=1), g, db, enc, n_noc=n_noc)
    return enc, rows


@pytest.mark.parametrize("n_noc", [1, 2, 8])
@pytest.mark.parametrize("graph_fn", [audio, ar_complex])
def test_phase_sim_kernel_compiles(one_chip, graph_fn, n_noc):
    """The fused Pallas kernel lowers through Mosaic for both paper
    workloads (T=15 and T=28, padded to 128 lanes) on 1-, 2- and 8-deep
    NoC chains."""
    from repro.kernels.phase_sim import phase_sim

    enc, rows = _rows(graph_fn(), n_noc)
    compiled = (
        jax.jit(lambda r: phase_sim(enc, r, interpret=False))
        .lower(_shapes(rows, one_chip))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_simulate_batch_compiles(one_chip):
    """The XLA formulation of the same batch compiles too."""
    enc, rows = _rows(ar_complex(), 1)
    jax.jit(lambda r: simulate_batch(enc, r)).lower(_shapes(rows, one_chip)).compile()


def test_kernel_chain_block_compiles(one_chip):
    """The fused mixed mapping+allocation chain block (R=16 chains, K=8
    steps) with the kernel pricing every step."""
    db = HardwareDatabase()
    g = ar_complex()
    design = random_single_noc_designs(g, 1, seed=3)[0]
    runner = DeviceChainRunner(g, db, use_kernel=True, interpret=False)
    ed = EncodedDesign.of(design, g, db, runner.enc)
    cap_pe, cap_mem = runner._capacities(ed, True, None, None, None)
    table = MoveTable.of(ed, runner.enc, alloc=True, cap_pe=cap_pe, cap_mem=cap_mem)
    carry = runner.fresh_carry(design, ed, 16, 0, cap_pe=cap_pe, cap_mem=cap_mem, alloc=True)
    row0 = runner._row0(ed, calibrated_budget(db), 0.05)
    fn = runner._block(16, 8, ed, "farsi", 0.05, 0.997, 5, True, cap_pe, cap_mem)
    args = (carry, np.int32(0), row0, table.kind, table.task, table.dest)
    compiled = fn.lower(*_shapes(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
