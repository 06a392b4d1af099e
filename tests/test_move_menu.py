"""The chain step's move menu against an independent row-by-row reference,
and the fused block against sequences recorded before the menu was built
in the move table's block shapes.

``_menu`` builds the (R, M) validity mask and logits from per-task and
per-slot blocks. The reference below walks the table one row at a time,
reading each row's operands from the packed ``kind``/``task``/``dest``
columns, as a gather per row would; only the f32 ``log`` is shared with the
device, since it is the one primitive whose last bit is the platform's."""
import json
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DeviceChainRunner,
    HardwareDatabase,
    MoveTable,
    audio,
    pulse_doppler,
    random_single_noc_designs,
)
from repro.core.device_explore import (
    _KIND_PRECEDENCE,
    _N_RUNG,
    MENUS,
    MV_ATT_MEM,
    MV_ATT_PE,
    MV_FORK_MEM,
    MV_FORK_PE,
    MV_JOIN_MEM,
    MV_JOIN_PE,
    MV_MIG_MEM,
    MV_MIG_PE,
    MV_SWAP_MEM,
    MV_SWAP_PE,
    ChainCarry,
    _menu,
)
from repro.core.phase_sim_jax import EncodedDesign

import gen_golden_chain_seqs as golden

R = 6


def _seeded(g, seed):
    from bench.designs import seeded_platform

    return seeded_platform(g, random.Random(seed), 4, 2, 2)


def _small_pulse_doppler():
    return pulse_doppler(pulses=8, range_bins=32, doppler_tasks=4)


# name -> (graph, design maker, alloc, extra capacity over the real slots)
CASES = {
    "mapping": (audio, lambda g: _seeded(g, 11), False, None),
    "alloc_noc1": (
        audio, lambda g: random_single_noc_designs(g, 1, seed=7)[0], True,
        None,
    ),
    "alloc_noc2": (audio, lambda g: _seeded(g, 11), True, None),
    "alloc_wide_caps": (audio, lambda g: _seeded(g, 3), True, (5, 3)),
    "pulse_doppler": (_small_pulse_doppler, lambda g: _seeded(g, 2**31 + 11),
                      True, None),
}


def _table_and_carry(case, seed):
    """The case's move table and a random carry over its capacities: any
    task→slot map, active masks, rungs, NoC positions, telemetry (with
    exact zeros) and taboo counters."""
    make_g, make_d, alloc, extra = CASES[case]
    g, db = make_g(), HardwareDatabase()
    d = make_d(g)
    runner = DeviceChainRunner(g, db)
    ed = EncodedDesign.of(d, g, db, runner.enc)
    s_pe, s_mem = int(ed.pe_peak.shape[0]), int(ed.mem_bw.shape[0])
    cap_pe = cap_mem = None
    if extra is not None:
        cap_pe, cap_mem = s_pe + extra[0], s_mem + extra[1]
    table = MoveTable.of(ed, runner.enc, alloc=alloc, cap_pe=cap_pe,
                         cap_mem=cap_mem)
    cap_pe, cap_mem = runner._capacities(ed, alloc, cap_pe, cap_mem, None)
    carry = runner.fresh_carry(d, ed, R, seed, cap_pe=cap_pe,
                               cap_mem=cap_mem, alloc=alloc)
    rng = np.random.default_rng(seed)
    t, n_noc = len(g.tasks), int(ed.noc_bw.shape[0])

    def task_map(cap):
        # each chain maps onto a random subset of the slots, so that empty
        # slots (join rows) and crowded ones (fork rows) both occur
        rows = []
        for _ in range(R):
            used = rng.choice(cap, rng.integers(1, cap + 1), replace=False)
            rows.append(rng.choice(used, t))
        return np.asarray(rows, np.int32)

    def telemetry(cap):
        b = rng.uniform(0.0, 2e-3, (R, cap)).astype(np.float32)
        return np.where(rng.random((R, cap)) < 0.3, np.float32(0), b)

    carry = carry._replace(
        task_pe=task_map(cap_pe),
        task_mem=task_map(cap_mem),
        taboo=rng.choice([0, 0, 0, 1, 3], (R, table.n_moves)).astype(np.int32),
        pe_bneck=telemetry(cap_pe),
        mem_bneck=telemetry(cap_mem),
        pe_active=(rng.random((R, cap_pe)) < 0.7).astype(np.float32),
        mem_active=(rng.random((R, cap_mem)) < 0.7).astype(np.float32),
        pe_rung=rng.integers(0, _N_RUNG, (R, cap_pe)).astype(np.int32),
        mem_rung=rng.integers(0, _N_RUNG, (R, cap_mem)).astype(np.int32),
        pe_noc=rng.integers(0, n_noc, (R, cap_pe)).astype(np.int32),
        mem_noc=rng.integers(0, n_noc, (R, cap_mem)).astype(np.int32),
    )
    return table, carry


def reference_menu(c: ChainCarry, table: MoveTable, menu: str):
    """Row by row: each row's validity and log-weight from its kind and
    operands (the fused block's original per-row formula)."""
    prec_log = np.asarray(jnp.log(jnp.asarray(_KIND_PRECEDENCE)))
    r, m = c.taboo.shape
    valid = np.zeros((r, m), bool)
    w = np.zeros((r, m), np.float32)
    for i in range(r):
        cls = []
        for tm, act, rung, noc, bneck in (
            (c.task_pe, c.pe_active, c.pe_rung, c.pe_noc, c.pe_bneck),
            (c.task_mem, c.mem_active, c.mem_rung, c.mem_noc, c.mem_bneck),
        ):
            load = np.bincount(tm[i], minlength=act.shape[1])
            cls.append((tm[i], act[i], rung[i], noc[i], bneck[i], load))
        for row in range(m):
            k, a, d = (int(table.kind[row]), int(table.task[row]),
                       int(table.dest[row]))
            tm, act, rung, noc, bneck, load = cls[k % 2]
            if k in (MV_MIG_PE, MV_MIG_MEM):
                ok = d != tm[a] and act[d] > 0
            elif k in (MV_FORK_PE, MV_FORK_MEM):
                ok = not act[d] > 0 and load[tm[a]] >= 2
            elif k in (MV_JOIN_PE, MV_JOIN_MEM):
                ok = act[a] > 0 and load[a] == 0
            elif k in (MV_SWAP_PE, MV_SWAP_MEM):
                ok = act[a] > 0 and 0 <= rung[a] + 2 * d - 1 < _N_RUNG
            else:
                assert k in (MV_ATT_PE, MV_ATT_MEM)
                ok = act[a] > 0 and d != noc[a]
            valid[i, row] = ok and c.taboo[i, row] == 0
            focus = tm[a] if k <= MV_FORK_MEM else a
            w[i, row] = bneck[focus] + np.float32(1e-6)
    if menu == "naive_sa":
        logw = np.zeros((r, m), np.float32)
    else:
        logw = np.asarray(jnp.log(w))
        if menu == "farsi":
            logw = logw + prec_log[table.kind][None, :]
    return valid, np.where(valid, logw, np.float32(-1e30))


@pytest.mark.parametrize("menu", MENUS)
@pytest.mark.parametrize("case", list(CASES))
def test_menu_matches_row_by_row_reference(case, menu):
    """Bit for bit, on seeded random carries: the block-built mask and
    logits are the per-row formula's."""
    prec_log = jnp.log(jnp.asarray(_KIND_PRECEDENCE))
    seen_valid, seen_invalid = set(), set()
    for seed in (1, 2, 3):
        table, carry = _table_and_carry(case, seed)
        fn = jax.jit(lambda c: _menu(c, table.segments, menu, prec_log))
        valid, logits = (np.asarray(x) for x in fn(carry))
        ref_valid, ref_logits = reference_menu(carry, table, menu)
        assert valid.shape == (R, table.n_moves)
        assert np.array_equal(valid, ref_valid)
        assert np.array_equal(logits.view(np.uint32),
                              ref_logits.view(np.uint32))
        seen_valid |= set(np.unique(table.kind[valid.any(axis=0)]).tolist())
        seen_invalid |= set(
            np.unique(table.kind[~valid.all(axis=0)]).tolist()
        )
    # the random carries reach every kind's valid and invalid rows
    kinds = set(np.unique(table.kind).tolist())
    assert seen_valid == kinds and seen_invalid == kinds


@pytest.mark.parametrize("case", list(CASES))
def test_segments_tile_the_table_in_row_order(case):
    """Each segment of the layout is its (arg × dest) cross product, in
    row order, and the segments cover every row once."""
    table, _ = _table_and_carry(case, 0)
    off = 0
    for k, n_a, n_d in table.segments:
        sl = slice(off, off + n_a * n_d)
        assert np.all(table.kind[sl] == k)
        assert np.array_equal(table.task[sl], np.repeat(np.arange(n_a), n_d))
        assert np.array_equal(table.dest[sl], np.tile(np.arange(n_d), n_a))
        off += n_a * n_d
    assert off == table.n_moves


GOLDEN = json.load(open(golden.GOLDEN, encoding="utf-8"))


@pytest.mark.parametrize("entry", sorted(GOLDEN))
def test_chain_block_replays_golden_sequences(entry):
    """The fused block reproduces, bit for bit, the move rows, accepts and
    fitness traces recorded before the menu left its per-row gathers
    (``tests/gen_golden_chain_seqs.py``)."""
    name, menu = entry.rsplit(".", 1)
    assert golden.record(name, menu) == GOLDEN[entry]
