"""The main path's device programs compile for a TPU v5e chip.

Nothing here runs on a chip: each test lowers a program for one device of a
described (not attached) ``v5e:2x2`` topology and compiles it with the
TPU's own compiler, which refuses what the CPU accepts — programs that do
not fit the device's memory, primitives the TPU backend cannot lower.
Only shapes are passed, never arrays.

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
    pulse_doppler,
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
def test_xla_simulate_batch_compiles(one_chip, graph_fn, n_noc):
    """The batched phase simulator compiles for both paper workloads (T=15
    and T=28) on 1-, 2- and 8-deep NoC chains."""
    enc, rows = _rows(graph_fn(), n_noc)
    jax.jit(lambda r: simulate_batch(enc, r)).lower(_shapes(rows, one_chip)).compile()


@pytest.mark.parametrize(
    "graph_fn,alloc",
    [(audio, False), (ar_complex, True), (pulse_doppler, True)],
)
def test_chain_block_compiles(one_chip, graph_fn, alloc):
    """The fused chain block (R=16 chains, K=8 steps) of each benchmarked
    workload, on the move menu its cell runs: mapping-only on audio, mixed
    mapping+allocation on ar_complex and on the 449-task pulse_doppler."""
    db = HardwareDatabase()
    g = graph_fn()
    design = random_single_noc_designs(g, 1, seed=3)[0]
    runner = DeviceChainRunner(g, db)
    ed = EncodedDesign.of(design, g, db, runner.enc)
    cap_pe, cap_mem = runner._capacities(ed, alloc, None, None, None)
    table = MoveTable.of(ed, runner.enc, alloc=alloc, cap_pe=cap_pe, cap_mem=cap_mem)
    carry = runner.fresh_carry(design, ed, 16, 0, cap_pe=cap_pe, cap_mem=cap_mem, alloc=alloc)
    row0 = runner._row0(ed, calibrated_budget(db), 0.05)
    fn = runner._block(16, 8, ed, "farsi", 0.05, 0.997, 5, alloc, cap_pe, cap_mem)
    args = (carry, np.int32(0), row0, table.kind, table.task, table.dest)
    fn.lower(*_shapes(args, one_chip)).compile()
