"""Device-resident exploration: fused accept loop + vmapped chain populations.

The host-driven accept loop caps the explorer at ~1.2k it/s while the
batched evaluator sustains ~19k evals/s (BENCH_simbackend.json): every SA
iteration pays a dispatch, a device→host fitness transfer, and a Python
accept/taboo update before the next candidate can even be proposed. This
module moves the whole explore step onto the device:

  * :class:`MoveTable` — ``propose_moves`` in packed array form. Every
    candidate move is enumerated up front as three flat int32 columns
    (``kind``/``arg``/``dest``); the loop *samples* an index from this
    table on device instead of materializing `MoveDelta` objects on host.
    Beyond the PR-8 mapping moves (task → PE/MEM slot migrates), the
    ``alloc`` table adds FARSI's allocation moves as shape-preserving
    array operations over *capacity-padded slot inventories*: PE/MEM
    fork (clone a slot's coefficient columns into an inactive slot and
    re-home one task), join (deactivate an emptied slot — its leak/area
    stop pricing via the active masks), swap (step the slot's frequency
    rung, scaling the closed-form coefficient columns by static ladder
    ratios), and NoC attach (re-home a slot to another chain position).
    Validity is masked dynamically per chain: join only when the slot is
    empty, fork only into an inactive slot and only off a slot hosting
    ≥ 2 tasks, swap only inside the ladder — so the table is samplable
    inside a jitted loop even though each chain's platform differs.
  * A ``lax.scan`` accept loop: K iterations of propose → mutate carry
    → re-simulate → SA accept/reject run entirely on device. The carry
    (:class:`ChainCarry`) holds the full per-chain platform state:
    task→slot maps, active-slot masks, per-slot coefficient columns
    (the allocation moves' mutable state), frequency rungs, fork
    provenance, the (T, cap) acceleration table, fitness, PRNG key,
    per-move taboo TTLs, and the incumbent bottleneck telemetry.
  * Chain populations: the R chains ARE the batch axis of the simulator —
    each scan step prices an (R,)-rows dict through the usual batched
    path (Pallas kernel or XLA reference; ``kernels.phase_sim.chain``).
    Per-chain PRNG keys are ``fold_in(base_key, chain_index)``, so chain
    i's stream — and therefore its accepted-move sequence — is identical
    at R=16 and R=256 (population size never perturbs a chain).

Menus: ``naive_sa`` samples uniformly over the valid rows; ``telemetry``
weights rows by the bottleneck seconds of the move's focus slot (FARSI's
bottleneck-directed neighbour selection); ``farsi`` further multiplies in
the Algorithm-1 move-kind precedence (join > migrate ≈ attach > fork >
swap), making the full FARSI move ordering device-eligible.

One dispatch prices an (R, K) exploration block. The host calls
:meth:`DeviceChainRunner.run_chains` once per block and reconciles the
winning chain onto the live design — :func:`reconcile_mapping` for
mapping-only blocks, :func:`reconcile_alloc` (fork/join/retune/attach
replayed through ``moves.py``'s allocation bridge) for mixed blocks.
:meth:`DeviceChainRunner.run_chains_host` is the same compiled step
driven one iteration per dispatch — the classic host-loop regime — which
makes it both the parity oracle (bit-identical accepted-move sequences,
same threefry draws, same f32 accept math) and the speedup baseline the
bench reports against.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.spans import span
from .blocks import FREQ_LADDER_MHZ
from .budgets import Budget
from .database import HardwareDatabase
from .design import Design
from .moves import MoveDelta, apply_mapping, mapping_delta
from .phase_sim_jax import (
    BIG,
    EncodedDesign,
    EncodedWorkload,
    alloc_rows,
    fill_budget,
    fill_row,
)
from .tdg import TaskGraph

__all__ = [
    "MENUS",
    "MoveTable",
    "ChainCarry",
    "ChainRequest",
    "ChainBlockResult",
    "DeviceChainRunner",
    "copy_carry",
    "reconcile_mapping",
    "reconcile_alloc",
]

MENUS = ("naive_sa", "telemetry", "farsi")

# packed move-kind codes (MoveTable.kind). Even codes act on the PE class,
# odd on the MEM class; ``arg`` is a task index for migrate/fork and a slot
# index for join/swap/attach; ``dest`` is a slot index (migrate/fork), a
# ladder direction 0/1 (swap), or a NoC chain index (attach).
MV_MIG_PE, MV_MIG_MEM = 0, 1
MV_FORK_PE, MV_FORK_MEM = 2, 3
MV_JOIN_PE, MV_JOIN_MEM = 4, 5
MV_SWAP_PE, MV_SWAP_MEM = 6, 7
MV_ATT_PE, MV_ATT_MEM = 8, 9

# Algorithm-1 move precedence (moves.MOVE_PRECEDENCE), indexed by kind code:
# join 5 > migrate/attach 4 > fork 3 > swap 2 — the ``farsi`` menu folds
# log(precedence) into the sampling logits
_KIND_PRECEDENCE = np.asarray(
    [4.0, 4.0, 3.0, 3.0, 5.0, 5.0, 2.0, 2.0, 4.0, 4.0], np.float32
)

# frequency-rung ratio tables for the device swap move: stepping slot s from
# rung i to i±1 multiplies its closed-form coefficient columns in place —
# peak ops, mem bandwidth and leakage all scale linearly with f
# (db.pe_peak_ops / Block.peak_bandwidth / db.leakage_w), PE area scales
# with the timing-closure factor 0.6 + 0.4·f/800 (db.block_area_mm2); MEM
# area terms are frequency-independent in the encoding (DRAM PHY is fixed,
# SRAM per-MB carries no f-scale) and are left untouched.
_F = np.asarray(FREQ_LADDER_MHZ, np.float64)
_AREA_FS = 0.6 + 0.4 * (_F / 800.0)


def _ratio_table(vals: np.ndarray) -> np.ndarray:
    """(8, 2) f32: [i, 0] = vals[i-1]/vals[i] (step down), [i, 1] =
    vals[i+1]/vals[i] (step up); ladder ends hold 1.0 (masked invalid)."""
    r = np.ones((len(vals), 2), np.float32)
    r[1:, 0] = (vals[:-1] / vals[1:]).astype(np.float32)
    r[:-1, 1] = (vals[1:] / vals[:-1]).astype(np.float32)
    return r


_RATIO_F = _ratio_table(_F)
_RATIO_AREA = _ratio_table(_AREA_FS)
_N_RUNG = len(FREQ_LADDER_MHZ)


def _rung_of(freq_mhz: int) -> int:
    return int(np.argmin(np.abs(_F - float(freq_mhz))))


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class ChainCarry(NamedTuple):
    """The full per-chain device state of an (R, K) block. The first seven
    leaves are the PR-8 mapping-only carry (order preserved — checkpoints
    and parity tests iterate leaves positionally); the rest is the
    allocation state: active-slot masks over the capacity-padded slot
    inventories, the per-slot coefficient columns allocation moves mutate
    (fork copies, swap scales, join strands), frequency rungs, fork
    provenance (the *base-encoding* slot each slot was transitively cloned
    from — what :func:`reconcile_alloc` replays on the host design), and
    the per-chain (T, cap_pe) acceleration table."""

    task_pe: jnp.ndarray  # (R, T) i32
    task_mem: jnp.ndarray  # (R, T) i32
    fitness: jnp.ndarray  # (R,) f32
    key: jnp.ndarray  # (R, 2) u32 per-chain PRNG key
    taboo: jnp.ndarray  # (R, M) i32 per-move taboo TTL
    pe_bneck: jnp.ndarray  # (R, cap_pe) f32 incumbent telemetry
    mem_bneck: jnp.ndarray  # (R, cap_mem) f32
    pe_active: jnp.ndarray  # (R, cap_pe) f32 active-slot mask
    mem_active: jnp.ndarray  # (R, cap_mem) f32
    pe_peak: jnp.ndarray  # (R, cap_pe) f32 coefficient columns …
    pe_pj: jnp.ndarray
    pe_leak: jnp.ndarray
    pe_area: jnp.ndarray
    pe_noc: jnp.ndarray  # (R, cap_pe) i32 NoC chain attachment
    pe_rung: jnp.ndarray  # (R, cap_pe) i32 frequency-ladder rung
    pe_src: jnp.ndarray  # (R, cap_pe) i32 fork provenance (base slot)
    mem_bw: jnp.ndarray  # (R, cap_mem) f32 …
    mem_pj: jnp.ndarray
    mem_leak: jnp.ndarray
    mem_area_fixed: jnp.ndarray
    mem_area_per_mb: jnp.ndarray
    mem_noc: jnp.ndarray  # (R, cap_mem) i32
    mem_rung: jnp.ndarray  # (R, cap_mem) i32
    mem_src: jnp.ndarray  # (R, cap_mem) i32
    accel: jnp.ndarray  # (R, T, cap_pe) f32 per-slot task acceleration


def reconcile_mapping(
    design: Design,
    res: "ChainBlockResult",
    g: TaskGraph,
    db: HardwareDatabase,
    enc: EncodedWorkload,
    ed: Optional[EncodedDesign] = None,
    delta: Optional[MoveDelta] = None,
) -> Dict[str, Dict[str, str]]:
    """Apply the winning chain's final mapping onto ``design`` in place
    (slot indices → block names via the encoding's slot dicts). Returns the
    changed assignments — empty dicts mean the block improved nothing over
    the incumbent mapping. Mapping-only: allocation state in the carry (if
    any) is ignored; mixed blocks reconcile via :func:`reconcile_alloc`."""
    if ed is None:
        ed = EncodedDesign.of(design, g, db, enc)
    inv_pe = {s: n for n, s in ed.pe_slot.items()}
    inv_mem = {s: n for n, s in ed.mem_slot.items()}
    w = res.winner
    ch_pe: Dict[str, str] = {}
    ch_mem: Dict[str, str] = {}
    for i, name in enumerate(enc.names):
        s = int(res.task_pe[w, i])
        if s != int(ed.task_pe[i]):
            ch_pe[name] = inv_pe[s]
        s = int(res.task_mem[w, i])
        if s != int(ed.task_mem[i]):
            ch_mem[name] = inv_mem[s]
    if ch_pe or ch_mem:
        apply_mapping(design, ch_pe, ch_mem, delta)
    return {"task_pe": ch_pe, "task_mem": ch_mem}


def _reconcile_class(
    design: Design,
    inv: Dict[int, str],
    active: np.ndarray,
    src: np.ndarray,
    rung: np.ndarray,
    noc: np.ndarray,
    base_noc: np.ndarray,
    out: Dict[str, object],
) -> Dict[int, str]:
    """One slot class (PE or MEM) of :func:`reconcile_alloc`: returns the
    carry-slot → block-name map after creating clones for forked slots and
    retuning/re-homing preserved originals. Removals are deferred to the
    caller (tasks must be re-mapped off doomed originals first)."""
    from .moves import attach_block, fork_block, retune_block

    s_base = len(inv)
    slot_name: Dict[int, str] = {}
    for j in range(active.shape[0]):
        if active[j] <= 0.5:
            continue
        f = int(FREQ_LADDER_MHZ[int(rung[j])])
        noc_name = design.noc_chain[int(noc[j])]
        if j < s_base and int(src[j]) == j:
            name = inv[j]
            slot_name[j] = name
            if design.blocks[name].freq_mhz != f:
                retune_block(design, name, f)
                out["retuned"][name] = f
            if j < len(base_noc) and int(noc[j]) != int(base_noc[j]):
                attach_block(design, name, noc_name)
                out["attached"][name] = noc_name
        else:
            origin = inv[int(src[j])]
            name = fork_block(design, origin, freq_mhz=f, noc=noc_name)
            slot_name[j] = name
            out["forked"].append(name)
    return slot_name


def reconcile_alloc(
    design: Design,
    res: "ChainBlockResult",
    g: TaskGraph,
    db: HardwareDatabase,
    enc: EncodedWorkload,
    ed: Optional[EncodedDesign] = None,
) -> Dict[str, object]:
    """Replay the winning chain's *platform* onto ``design`` in place: the
    mixed-move inverse of :func:`reconcile_mapping`. Uses the carry's fork
    provenance (``pe_src``/``mem_src`` point at the base-encoding slot each
    active slot was transitively cloned from) to rebuild the winner through
    ``moves.py``'s allocation bridge — clones for forked slots
    (:func:`~repro.core.moves.fork_block`), frequency retunes for stepped
    rungs, NoC re-homes for attaches, then the task mapping, then removal
    of originals the winner joined away. ``design`` must be the same design
    that seeded the block's fresh carry (provenance indexes its encoding)."""
    if ed is None:
        ed = EncodedDesign.of(design, g, db, enc)
    from .moves import join_block

    cc = ChainCarry(*res.carry)
    w = res.winner
    out: Dict[str, object] = {
        "task_pe": {}, "task_mem": {}, "forked": [], "removed": [],
        "retuned": {}, "attached": {},
    }
    inv_pe = {s: n for n, s in ed.pe_slot.items()}
    inv_mem = {s: n for n, s in ed.mem_slot.items()}
    pe_names = _reconcile_class(
        design, inv_pe, np.asarray(cc.pe_active[w]), np.asarray(cc.pe_src[w]),
        np.asarray(cc.pe_rung[w]), np.asarray(cc.pe_noc[w]), ed.pe_noc, out,
    )
    mem_names = _reconcile_class(
        design, inv_mem, np.asarray(cc.mem_active[w]),
        np.asarray(cc.mem_src[w]), np.asarray(cc.mem_rung[w]),
        np.asarray(cc.mem_noc[w]), ed.mem_noc, out,
    )
    # task re-mapping (after clones exist, before doomed originals go)
    for i, name in enumerate(enc.names):
        p = pe_names[int(res.task_pe[w, i])]
        if design.task_pe[name] != p:
            design.task_pe[name] = p
            out["task_pe"][name] = p
        m = mem_names[int(res.task_mem[w, i])]
        if design.task_mem[name] != m:
            design.task_mem[name] = m
            out["task_mem"][name] = m
    # originals the winner joined away (or re-populated with a clone)
    for inv, act, src in (
        (inv_pe, np.asarray(cc.pe_active[w]), np.asarray(cc.pe_src[w])),
        (inv_mem, np.asarray(cc.mem_active[w]), np.asarray(cc.mem_src[w])),
    ):
        for j, name in inv.items():
            if act[j] <= 0.5 or int(src[j]) != j:
                join_block(design, name)
                out["removed"].append(name)
    return out


def copy_carry(carry: Optional[tuple]) -> Optional[tuple]:
    """Deep-copy a chain-block carry so policy checkpoints round-trip
    bit-exactly even if the live carry advances. Preserves the carry's
    tuple type (:class:`ChainCarry` stays a ChainCarry)."""
    if carry is None:
        return None
    return type(carry)(*(np.array(x, copy=True) for x in carry))


@dataclasses.dataclass(frozen=True)
class MoveTable:
    """``propose_moves`` as packed arrays: row m is one candidate move
    (``kind[m]`` ∈ the ``MV_*`` codes) with operand columns ``arg`` (task
    index for migrate/fork, slot index for join/swap/attach) and ``dest``
    (destination slot / ladder direction / NoC chain index). Every row is
    shape-preserving over the capacity-padded inventories, so the whole
    table is samplable inside a jitted loop; validity (no-op destinations,
    inactive slots, full capacity, ladder ends, taboo) is masked
    dynamically per chain from the carry."""

    kind: np.ndarray  # (M,) int32 MV_* code
    task: np.ndarray  # (M,) int32 operand (task or slot index — see class)
    dest: np.ndarray  # (M,) int32 destination operand
    segments: Tuple[Tuple[int, int, int], ...]  # see :meth:`layout`

    @property
    def n_moves(self) -> int:
        return int(self.kind.shape[0])

    @staticmethod
    def layout(
        t: int, cap_pe: int, cap_mem: int, n_noc: int, alloc: bool
    ) -> Tuple[Tuple[int, int, int], ...]:
        """The table's row order, declared once: ``(kind, n_arg, n_dest)``
        segments in row order, each the cross product of its operands —
        segment row ``a·n_dest + d`` has ``arg = a``, ``dest = d``. Migrate
        and fork cross the T tasks with the class's slots; join takes one
        row per slot, swap two (ladder down/up), attach one per NoC chain
        position (only when there are two or more)."""
        segs = [(MV_MIG_PE, t, cap_pe), (MV_MIG_MEM, t, cap_mem)]
        if alloc:
            segs += [
                (MV_FORK_PE, t, cap_pe), (MV_FORK_MEM, t, cap_mem),
                (MV_JOIN_PE, cap_pe, 1), (MV_JOIN_MEM, cap_mem, 1),
                (MV_SWAP_PE, cap_pe, 2), (MV_SWAP_MEM, cap_mem, 2),
            ]
            if n_noc > 1:
                segs += [
                    (MV_ATT_PE, cap_pe, n_noc), (MV_ATT_MEM, cap_mem, n_noc),
                ]
        return tuple(segs)

    @staticmethod
    def of(
        ed: EncodedDesign,
        enc: EncodedWorkload,
        *,
        alloc: bool = False,
        cap_pe: Optional[int] = None,
        cap_mem: Optional[int] = None,
    ) -> "MoveTable":
        """Enumerate the move rows of ``ed``. Mapping-only (default): all
        T·(S_pe + S_mem) single-task migrates, bit-compatible with the
        PR-8 table. ``alloc=True`` additionally enumerates fork/join/swap/
        NoC-attach rows over ``cap_pe``/``cap_mem`` padded slot inventories
        (default: pow2 ≥ real + 1, so at least one fork slot is free)."""
        s_pe = int(ed.pe_peak.shape[0])
        s_mem = int(ed.mem_bw.shape[0])
        if not alloc:
            cap_pe, cap_mem = s_pe, s_mem
        else:
            cap_pe = cap_pe or _pow2_at_least(s_pe + 1)
            cap_mem = cap_mem or _pow2_at_least(s_mem + 1)
        segs = MoveTable.layout(
            len(enc.names), cap_pe, cap_mem, int(ed.noc_bw.shape[0]), alloc
        )
        return MoveTable(
            kind=np.concatenate(
                [np.full(n_a * n_d, k, np.int32) for k, n_a, n_d in segs]
            ),
            task=np.concatenate(
                [np.repeat(np.arange(n_a, dtype=np.int32), n_d)
                 for _, n_a, n_d in segs]
            ),
            dest=np.concatenate(
                [np.tile(np.arange(n_d, dtype=np.int32), n_a)
                 for _, n_a, n_d in segs]
            ),
            segments=segs,
        )

    def delta_of(
        self, m: int, enc: EncodedWorkload, ed: EncodedDesign
    ) -> MoveDelta:
        """Unpack a *migrate* row ``m`` into an ordinary :class:`MoveDelta`
        (absolute task→block-name mapping) — the bridge back to the host
        move system. Allocation rows have no single-delta form; whole
        blocks reconcile through :func:`reconcile_alloc` instead."""
        k = int(self.kind[m])
        if k not in (MV_MIG_PE, MV_MIG_MEM):
            raise ValueError(f"row {m} (kind {k}) is not a migrate move")
        tname = enc.names[int(self.task[m])]
        d = int(self.dest[m])
        if k == MV_MIG_PE:
            inv = {s: n for n, s in ed.pe_slot.items()}
            return mapping_delta({tname: inv[d]}, {})
        inv = {s: n for n, s in ed.mem_slot.items()}
        return mapping_delta({}, {tname: inv[d]})


def _menu(
    c: ChainCarry,
    segments: Tuple[Tuple[int, int, int], ...],
    menu: str,
    prec_log: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One chain step's move menu: the (R, M) ``valid`` mask and sampling
    ``logits`` of every :class:`MoveTable` row, for a carry whose ``taboo``
    is the step's (already decremented).

    No (R, M) gather: a row's operands are a task or a slot crossed with a
    destination, so each segment of :meth:`MoveTable.layout` is a broadcast
    of per-task (R, T) or per-slot (R, cap) quantities to its
    (R, n_arg, n_dest) block, flattened and concatenated in row order. A
    task's current slot's load and telemetry come through the (R, T, cap)
    task→slot one-hot.

    Valid rows: migrate a task to another active slot; fork it into an
    inactive slot off a slot hosting ≥ 2 tasks; join an active empty slot;
    swap an active slot one rung inside the ladder; attach an active slot
    to another NoC position; never a taboo row. Log-weights: 0 for
    ``naive_sa``; for ``telemetry`` log(bottleneck seconds + 1e-6) of the
    row's focus slot (the task's current slot for migrate/fork, the operand
    slot otherwise); ``farsi`` adds ``prec_log`` of the row's kind."""
    r = c.fitness.shape[0]
    eps = jnp.float32(1e-6)
    classes = []  # [PE, MEM]: a kind code's class is its parity
    for tm, act, rung, noc, bneck in (
        (c.task_pe, c.pe_active, c.pe_rung, c.pe_noc, c.pe_bneck),
        (c.task_mem, c.mem_active, c.mem_rung, c.mem_noc, c.mem_bneck),
    ):
        hot = tm[:, :, None] == jnp.arange(act.shape[1])  # (R, T, cap)
        load = jnp.sum(hot, axis=1)  # (R, cap) tasks per slot
        x = {
            "slot": tm, "on": act > 0, "rung": rung, "noc": noc, "load": load,
            # (R, T): the load of each task's current slot
            "load_cur": jnp.sum(jnp.where(hot, load[:, None, :], 0), axis=2),
        }
        if menu != "naive_sa":
            b_cur = jnp.sum(jnp.where(hot, bneck[:, None, :], 0.0), axis=2)
            x["lw_task"] = jnp.log(b_cur + eps)
            x["lw_slot"] = jnp.log(bneck + eps)
        classes.append(x)
    vs, lws = [], []
    for kd, n_a, n_d in segments:
        x = classes[kd % 2]
        dd = jnp.arange(n_d, dtype=jnp.int32)
        if kd in (MV_MIG_PE, MV_MIG_MEM):
            v = (dd != x["slot"][:, :, None]) & x["on"][:, None, :]
        elif kd in (MV_FORK_PE, MV_FORK_MEM):
            v = ~x["on"][:, None, :] & (x["load_cur"] >= 2)[:, :, None]
        elif kd in (MV_JOIN_PE, MV_JOIN_MEM):
            v = (x["on"] & (x["load"] == 0))[:, :, None]
        elif kd in (MV_SWAP_PE, MV_SWAP_MEM):
            rung = x["rung"][:, :, None] + (2 * dd - 1)
            v = x["on"][:, :, None] & (rung >= 0) & (rung < _N_RUNG)
        elif kd in (MV_ATT_PE, MV_ATT_MEM):
            v = x["on"][:, :, None] & (dd != x["noc"][:, :, None])
        else:
            raise ValueError(f"no validity rule for move kind {kd}")
        flat = lambda a: jnp.broadcast_to(a, (r, n_a, n_d)).reshape(r, -1)
        vs.append(flat(v))
        if menu != "naive_sa":
            lw = x["lw_task"] if kd <= MV_FORK_MEM else x["lw_slot"]
            if menu == "farsi":
                lw = lw + prec_log[kd]
            lws.append(flat(lw[:, :, None]))
    valid = jnp.concatenate(vs, axis=1) & (c.taboo == 0)
    logw = (
        jnp.concatenate(lws, axis=1) if lws
        else jnp.zeros(valid.shape, jnp.float32)
    )
    return valid, jnp.where(valid, logw, jnp.float32(-1e30))


@dataclasses.dataclass
class ChainRequest:
    """One (R, K) exploration block the explorer asks its backend to price.

    Yielded by ``Explorer.run_chain_steps`` in place of a candidate list;
    the serve scheduler (or ``Explorer.run_chains``) answers it with the
    :class:`ChainBlockResult` of ``backend.run_chains``. ``carry`` resumes
    the chain population from a previous block (or a ``device_sa`` policy
    checkpoint); ``it0`` keeps the SA temperature schedule global across
    blocks. ``alloc`` widens the move table to the mixed
    mapping+allocation menu over ``cap_pe``/``cap_mem`` padded slot
    inventories (pinned by the first block of a run so resumed carries
    stay shape-compatible; None derives pow2 capacities from the design)."""

    design: Design
    budget: Budget
    r: int
    k: int
    seed: int = 0
    it0: int = 0
    menu: str = "naive_sa"
    alpha: float = 0.05
    temperature0: float = 0.05
    temp_decay: float = 0.997
    taboo_ttl: int = 5
    carry: Optional[tuple] = None
    alloc: bool = False
    cap_pe: Optional[int] = None
    cap_mem: Optional[int] = None


@dataclasses.dataclass
class ChainBlockResult:
    """Host-side view of one priced (R, K) block. ``carry`` is the full
    device state pulled back as numpy (the checkpointable object); the
    per-step traces cover every chain so parity/trajectory tests can replay
    any of them."""

    task_pe: np.ndarray  # (R, T) final task→PE-slot map per chain
    task_mem: np.ndarray  # (R, T) final task→MEM-slot map per chain
    fitness: np.ndarray  # (R,) final Eq.-7 fitness per chain
    move_idx: np.ndarray  # (R, K) sampled MoveTable row per step
    accepted: np.ndarray  # (R, K) bool accept/reject per step
    fit_trace: np.ndarray  # (R, K) incumbent fitness after each step
    carry: tuple  # numpy ChainCarry (resume / checkpoint)
    winner: int  # argmin-fitness chain index
    wall_s: float  # dispatch wall-clock (including device sync)
    n_moves: int  # MoveTable rows (M)

    def seq(self, chain: int = 0) -> List[Tuple[int, int]]:
        """(move_idx, accepted) sequence of one chain — the parity object."""
        return [
            (int(m), int(a))
            for m, a in zip(self.move_idx[chain], self.accepted[chain])
        ]


class DeviceChainRunner:
    """Owns the jitted (R, K) chain blocks for one workload.

    The jit cache is keyed on everything that changes the traced program:
    (R, K, slot capacities, chain length, menu, alloc flag, SA constants).
    ``n_compiles`` counts distinct cache entries — the smoke guard asserts
    the whole bench run stays within a handful. There is no fallback path:
    a design the flat encoding cannot host (``UnsupportedDesignError``)
    fails loudly instead of silently degrading to a host loop, so
    ``n_fallback`` is 0 by construction and asserted in the bench."""

    def __init__(
        self,
        g: TaskGraph,
        db: HardwareDatabase,
        enc: Optional[EncodedWorkload] = None,
        *,
        use_kernel: bool = False,
        interpret: bool = False,
    ):
        self.g = g
        self.db = db
        self.enc = enc if enc is not None else EncodedWorkload.of(g)
        self.use_kernel = use_kernel
        self.interpret = interpret
        self._blocks: Dict[tuple, object] = {}
        self.n_compiles = 0
        self.n_fallback = 0
        self.n_dispatches = 0
        self.n_chain_steps = 0

    # -- host-side staging -------------------------------------------------
    def _row0(self, ed: EncodedDesign, budget: Budget, alpha: float):
        t = len(self.enc.names)
        rows = alloc_rows(
            1, t, int(ed.pe_peak.shape[0]), int(ed.mem_bw.shape[0]),
            len(self.enc.wl_names), int(ed.noc_bw.shape[0]),
        )
        fill_row(rows, 0, ed)
        fill_budget(
            rows, 0, self.enc,
            budget.latency_s, budget.power_w, budget.area_mm2, alpha,
        )
        return {k: v[0] for k, v in rows.items()}

    def _accel_table(
        self, design: Design, ed: EncodedDesign, cap_pe: Optional[int] = None
    ) -> np.ndarray:
        """(T, cap_pe) effective acceleration of task t if mapped to PE slot
        p — ``pe_accel`` is a per-task column, so a device migrate re-gathers
        it from this table instead of asking the hardware DB mid-loop.
        Padded slots accelerate nothing (1.0); a device fork copies its
        source slot's column, so clones inherit the hardened profile."""
        t = len(self.enc.names)
        cap = cap_pe or int(ed.pe_peak.shape[0])
        tab = np.ones((t, cap), np.float32)
        tasks = self.g.tasks
        for name, s in ed.pe_slot.items():
            b = design.blocks[name]
            if b.subtype == "acc" and b.hardened_for in self.enc.index:
                k = self.enc.index[b.hardened_for]
                tab[k, s] = self.db.a_peak(
                    b.hardened_for, tasks[b.hardened_for].llp, b.unroll
                )
        return tab

    @staticmethod
    def _pad_cols(col: np.ndarray, cap: int, pad: float, dtype) -> np.ndarray:
        out = np.full(cap, pad, dtype)
        out[: col.shape[0]] = col
        return out

    def fresh_carry(
        self,
        design: Design,
        ed: EncodedDesign,
        r: int,
        seed: int,
        *,
        cap_pe: Optional[int] = None,
        cap_mem: Optional[int] = None,
        alloc: Optional[bool] = None,
    ) -> ChainCarry:
        """Initial chain-population carry: every chain starts from the live
        design with fitness BIG (the first finite candidate is accepted,
        exactly like the host explorer pricing its seed), zero taboo, zero
        telemetry, all real slots active / padded slots inactive, rungs
        read off the blocks' frequency knobs, provenance = own slot, and
        key ``fold_in(PRNGKey(seed), chain_index)`` — the per-chain stream
        is a function of (seed, chain) only, never of R."""
        t = len(self.enc.names)
        s_pe = int(ed.pe_peak.shape[0])
        s_mem = int(ed.mem_bw.shape[0])
        cap_pe = cap_pe or s_pe
        cap_mem = cap_mem or s_mem
        if alloc is None:
            alloc = cap_pe > s_pe or cap_mem > s_mem
        m = MoveTable.of(
            ed, self.enc, alloc=alloc, cap_pe=cap_pe, cap_mem=cap_mem
        ).n_moves
        base = jax.random.PRNGKey(seed)
        keys = np.asarray(
            jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(r))
        )
        inv_pe = {s: n for n, s in ed.pe_slot.items()}
        inv_mem = {s: n for n, s in ed.mem_slot.items()}
        pe_rung = np.zeros(cap_pe, np.int32)
        for s in range(s_pe):
            pe_rung[s] = _rung_of(design.blocks[inv_pe[s]].freq_mhz)
        mem_rung = np.zeros(cap_mem, np.int32)
        for s in range(s_mem):
            mem_rung[s] = _rung_of(design.blocks[inv_mem[s]].freq_mhz)
        pad = self._pad_cols
        bc = lambda a: np.broadcast_to(a, (r,) + a.shape).copy()
        accel = np.ones((t, cap_pe), np.float32)
        accel[:, :s_pe] = self._accel_table(design, ed)[:, :s_pe]
        return ChainCarry(
            task_pe=np.broadcast_to(ed.task_pe, (r, t)).copy(),
            task_mem=np.broadcast_to(ed.task_mem, (r, t)).copy(),
            fitness=np.full((r,), BIG, np.float32),
            key=keys,
            taboo=np.zeros((r, m), np.int32),
            pe_bneck=np.zeros((r, cap_pe), np.float32),
            mem_bneck=np.zeros((r, cap_mem), np.float32),
            pe_active=bc(pad(ed.pe_active, cap_pe, 0.0, np.float32)),
            mem_active=bc(pad(ed.mem_active, cap_mem, 0.0, np.float32)),
            pe_peak=bc(pad(ed.pe_peak, cap_pe, 1.0, np.float32)),
            pe_pj=bc(pad(ed.pe_pj, cap_pe, 0.0, np.float32)),
            pe_leak=bc(pad(ed.pe_leak, cap_pe, 0.0, np.float32)),
            pe_area=bc(pad(ed.pe_area, cap_pe, 0.0, np.float32)),
            pe_noc=bc(pad(ed.pe_noc, cap_pe, 0, np.int32)),
            pe_rung=bc(pe_rung),
            pe_src=bc(np.arange(cap_pe, dtype=np.int32)),
            mem_bw=bc(pad(ed.mem_bw, cap_mem, 1.0, np.float32)),
            mem_pj=bc(pad(ed.mem_pj, cap_mem, 0.0, np.float32)),
            mem_leak=bc(pad(ed.mem_leak, cap_mem, 0.0, np.float32)),
            mem_area_fixed=bc(pad(ed.mem_area_fixed, cap_mem, 0.0, np.float32)),
            mem_area_per_mb=bc(pad(ed.mem_area_per_mb, cap_mem, 0.0, np.float32)),
            mem_noc=bc(pad(ed.mem_noc, cap_mem, 0, np.int32)),
            mem_rung=bc(mem_rung),
            mem_src=bc(np.arange(cap_mem, dtype=np.int32)),
            accel=bc(accel),
        )

    # -- the fused block ---------------------------------------------------
    def _block(
        self, r: int, k: int, ed: EncodedDesign, menu: str,
        t0: float, decay: float, ttl: int, alloc: bool,
        cap_pe: int, cap_mem: int,
    ):
        key = (
            r, k, cap_pe, cap_mem,
            int(ed.noc_bw.shape[0]), menu, float(t0), float(decay), int(ttl),
            alloc,
        )
        fn = self._blocks.get(key)
        if fn is None:
            fn = self._build_block(
                r, k, menu, float(t0), float(decay), int(ttl),
                cap_pe, cap_mem, int(ed.noc_bw.shape[0]), alloc,
            )
            self._blocks[key] = fn
            self.n_compiles += 1
        return fn

    def _build_block(
        self, r: int, k: int, menu: str, t0: float, decay: float, ttl: int,
        cap_pe: int, cap_mem: int, n_noc: int, alloc: bool,
    ):
        # deferred: core must stay importable before kernels.phase_sim
        # finishes initializing (chain.py itself imports core.phase_sim_jax,
        # so a module-level import here closes an import cycle whenever the
        # kernels package is imported first)
        from ..kernels.phase_sim.chain import resimulate_chains

        enc = self.enc
        use_kernel, interpret = self.use_kernel, self.interpret
        t = len(enc.names)
        segments = MoveTable.layout(t, cap_pe, cap_mem, n_noc, alloc)
        tidx = jnp.arange(t)
        ridx = jnp.arange(r)
        t0f, decayf = jnp.float32(t0), jnp.float32(decay)
        prec_log = jnp.log(jnp.asarray(_KIND_PRECEDENCE))
        ratio_f = jnp.asarray(_RATIO_F)
        ratio_a = jnp.asarray(_RATIO_AREA)
        # carry leaves the accept step swaps wholesale on accept/reject
        # (everything mutable except fitness/key/taboo/telemetry)
        _STATE = (
            "task_pe", "task_mem", "pe_active", "mem_active",
            "pe_peak", "pe_pj", "pe_leak", "pe_area", "pe_noc", "pe_rung",
            "pe_src",
            "mem_bw", "mem_pj", "mem_leak", "mem_area_fixed",
            "mem_area_per_mb", "mem_noc", "mem_rung", "mem_src", "accel",
        )

        def apply_move(c: ChainCarry, kd, a, d) -> ChainCarry:
            """Apply each chain's sampled row (kind ``kd``, operands ``a``,
            ``d``; all (R,)) to its platform state. Every move class writes
            through a sentinel-gated scatter (``mode="drop"``): rows of
            another class point the update at an out-of-range index, so the
            write vanishes — one fused graph, no per-kind branches."""
            s = {f: getattr(c, f) for f in _STATE}
            a_task = jnp.clip(a, 0, t - 1)
            dsw = jnp.clip(d, 0, 1)  # swap rows: dest is the direction bit
            step = 2 * dsw - 1
            for cls, cap, mig, frk, jn, sw, att in (
                ("pe", cap_pe, MV_MIG_PE, MV_FORK_PE, MV_JOIN_PE,
                 MV_SWAP_PE, MV_ATT_PE),
                ("mem", cap_mem, MV_MIG_MEM, MV_FORK_MEM, MV_JOIN_MEM,
                 MV_SWAP_MEM, MV_ATT_MEM),
            ):
                tm = s["task_pe"] if cls == "pe" else s["task_mem"]
                act = s[f"{cls}_active"]
                rung = s[f"{cls}_rung"]
                cols_f = (
                    ("pe_peak", "pe_pj", "pe_leak", "pe_area")
                    if cls == "pe"
                    else ("mem_bw", "mem_pj", "mem_leak", "mem_area_fixed",
                          "mem_area_per_mb")
                )
                # rung-ratio columns: rates/leak scale with f, PE area with
                # the timing-closure factor; MEM area is f-independent
                sw_cols = (
                    (("pe_peak", ratio_f), ("pe_leak", ratio_f),
                     ("pe_area", ratio_a))
                    if cls == "pe"
                    else (("mem_bw", ratio_f), ("mem_leak", ratio_f))
                )
                misc = (f"{cls}_noc", f"{cls}_rung", f"{cls}_src")
                # mapping write (migrate/fork re-home task ``a`` to ``d``)
                ti = jnp.where((kd == mig) | (kd == frk), a, t)
                tm = tm.at[ridx, ti].set(d, mode="drop")
                s["task_pe" if cls == "pe" else "task_mem"] = tm
                # fork: clone the forked task's pre-move slot into slot
                # ``d`` (gather via the OLD map — the mapping write above
                # already re-pointed the task at d)
                old_tm = getattr(c, "task_pe" if cls == "pe" else "task_mem")
                src_slot = jnp.clip(old_tm[ridx, a_task], 0, cap - 1)
                fi = jnp.where(kd == frk, d, cap)
                for f in cols_f + misc:
                    s[f] = s[f].at[ridx, fi].set(
                        s[f][ridx, src_slot], mode="drop"
                    )
                s[f"{cls}_active"] = s[f"{cls}_active"].at[ridx, fi].set(
                    1.0, mode="drop"
                )
                if cls == "pe":
                    s["accel"] = s["accel"].at[
                        ridx[:, None], tidx[None, :], fi[:, None]
                    ].set(
                        s["accel"][ridx[:, None], tidx[None, :],
                                   src_slot[:, None]],
                        mode="drop",
                    )
                # join: deactivate the (empty) slot ``a``
                ji = jnp.where(kd == jn, a, cap)
                s[f"{cls}_active"] = s[f"{cls}_active"].at[ridx, ji].set(
                    0.0, mode="drop"
                )
                # swap: step slot ``a`` one frequency rung, scaling the
                # closed-form columns by the static ladder ratios
                si = jnp.where(kd == sw, a, cap)
                r_cur = jnp.clip(rung[ridx, jnp.clip(a, 0, cap - 1)],
                                 0, _N_RUNG - 1)
                for f, tab in sw_cols:
                    s[f] = s[f].at[ridx, si].multiply(
                        tab[r_cur, dsw], mode="drop"
                    )
                s[f"{cls}_rung"] = s[f"{cls}_rung"].at[ridx, si].add(
                    step, mode="drop"
                )
                # attach: re-home slot ``a`` to NoC chain position ``d``
                ai = jnp.where(kd == att, a, cap)
                s[f"{cls}_noc"] = s[f"{cls}_noc"].at[ridx, ai].set(
                    d, mode="drop"
                )
            return c._replace(**s)

        def block(carry, it0, row0, kind, arg, dest):
            # static per-block columns: the NoC chain + budget rows
            # broadcast once; the carry supplies every PE/MEM column
            rows_static = {
                n: jnp.broadcast_to(v, (r,) + jnp.shape(v))
                for n, v in row0.items()
                if n.startswith("noc_") or n in (
                    "wl_budget", "power_budget", "area_budget", "alpha",
                )
            }

            def step(c: ChainCarry, it):
                with jax.named_scope("chain.sample"):
                    taboo = jnp.maximum(c.taboo - 1, 0)
                    keys = jax.vmap(lambda kk: jax.random.split(kk, 3))(c.key)
                    key, k_move, k_acc = keys[:, 0], keys[:, 1], keys[:, 2]
                    c = c._replace(key=key, taboo=taboo)
                    valid, logits = _menu(c, segments, menu, prec_log)
                    any_valid = jnp.any(valid, axis=1)  # (R,)
                    m = jax.vmap(jax.random.categorical)(k_move, logits)
                # ---- apply the move, price the candidate platform -------
                with jax.named_scope("chain.apply"):
                    cand = apply_move(c, kind[m], arg[m], dest[m])
                    rows = dict(rows_static)
                    rows["task_pe"] = cand.task_pe
                    rows["task_mem"] = cand.task_mem
                    rows["pe_accel"] = jnp.take_along_axis(
                        cand.accel, cand.task_pe[:, :, None], axis=2
                    )[:, :, 0]
                    for f in (
                        "pe_peak", "pe_pj", "pe_leak", "pe_area", "pe_noc",
                        "pe_active", "mem_bw", "mem_pj", "mem_leak",
                        "mem_area_fixed", "mem_area_per_mb", "mem_noc",
                        "mem_active",
                    ):
                        rows[f] = getattr(cand, f)
                with jax.named_scope("chain.price"):
                    res = resimulate_chains(
                        enc, rows, use_kernel=use_kernel, interpret=interpret
                    )
                    f_new = res["fitness"].astype(jnp.float32)
                with jax.named_scope("chain.accept"):
                    # SA accept, f32 mirror of PolicyBase.accept; chains whose
                    # whole menu was masked (all-taboo / degenerate platform)
                    # force-reject and leave every state leaf untouched
                    temp = t0f * decayf ** it.astype(jnp.float32)
                    u = jax.vmap(
                        lambda kk: jax.random.uniform(kk, dtype=jnp.float32)
                    )(k_acc)
                    ok = jnp.isfinite(f_new) & (
                        (f_new < c.fitness)
                        | (
                            (temp > 0)
                            & (
                                u
                                < jnp.exp(
                                    -(f_new - c.fitness)
                                    / jnp.maximum(temp, jnp.float32(1e-9))
                                )
                            )
                        )
                    )
                    ok = ok & any_valid
                    sel = lambda n, o: jnp.where(
                        ok.reshape((r,) + (1,) * (o.ndim - 1)), n, o
                    )
                    merged = {
                        f: sel(getattr(cand, f), getattr(c, f)) for f in _STATE
                    }
                    fit = jnp.where(ok, f_new, c.fitness)
                    tab_wr = taboo.at[ridx, m].set(jnp.int32(ttl))
                    taboo2 = jnp.where(
                        (ok | ~any_valid)[:, None], taboo, tab_wr
                    )
                    pe_b = jnp.where(
                        ok[:, None], res["pe_bneck_s"].astype(jnp.float32),
                        c.pe_bneck,
                    )
                    mem_b = jnp.where(
                        ok[:, None], res["mem_bneck_s"].astype(jnp.float32),
                        c.mem_bneck,
                    )
                    c = c._replace(
                        fitness=fit, taboo=taboo2, pe_bneck=pe_b, mem_bneck=mem_b,
                        **merged,
                    )
                return c, (m.astype(jnp.int32), ok, fit)

            its = it0 + jnp.arange(k, dtype=jnp.int32)
            carry, (mv, acc, ft) = jax.lax.scan(step, carry, its)
            return carry, (mv.T, acc.T, ft.T)

        return jax.jit(block)

    def _capacities(
        self, ed: EncodedDesign, alloc: bool,
        cap_pe: Optional[int], cap_mem: Optional[int],
        carry: Optional[tuple],
    ) -> Tuple[int, int]:
        """Resolve the padded slot capacities of a block: an explicit
        override wins, then a resumed carry's shape (capacity is pinned for
        a whole exploration), then pow2 ≥ real+1 (alloc) / real (mapping)."""
        if carry is not None:
            cc = ChainCarry(*carry)
            return int(cc.pe_active.shape[1]), int(cc.mem_active.shape[1])
        s_pe = int(ed.pe_peak.shape[0])
        s_mem = int(ed.mem_bw.shape[0])
        if not alloc:
            return s_pe, s_mem
        return (
            cap_pe or _pow2_at_least(s_pe + 1),
            cap_mem or _pow2_at_least(s_mem + 1),
        )

    # -- entry points ------------------------------------------------------
    def run_chains(
        self,
        design: Design,
        budget: Budget,
        *,
        r: int,
        k: int,
        seed: int = 0,
        it0: int = 0,
        menu: str = "naive_sa",
        alpha: float = 0.05,
        temperature0: float = 0.05,
        temp_decay: float = 0.997,
        taboo_ttl: int = 5,
        carry: Optional[tuple] = None,
        alloc: bool = False,
        cap_pe: Optional[int] = None,
        cap_mem: Optional[int] = None,
    ) -> ChainBlockResult:
        """Price one fused (R, K) exploration block in a single dispatch.
        ``alloc=True`` samples the mixed mapping+allocation menu over
        capacity-padded slot inventories; the default is the PR-8
        mapping-only table (bit-compatible sequences)."""
        if menu not in MENUS:
            raise ValueError(f"unknown device move menu: {menu!r}")
        with span("chains.prep"):
            ed = EncodedDesign.of(design, self.g, self.db, self.enc)
            cap_pe, cap_mem = self._capacities(ed, alloc, cap_pe, cap_mem, carry)
            s_pe = int(ed.pe_peak.shape[0])
            s_mem = int(ed.mem_bw.shape[0])
            alloc = alloc or cap_pe > s_pe or cap_mem > s_mem
            table = MoveTable.of(
                ed, self.enc, alloc=alloc, cap_pe=cap_pe, cap_mem=cap_mem
            )
            row0 = self._row0(ed, budget, alpha)
            fn = self._block(
                r, k, ed, menu, temperature0, temp_decay, taboo_ttl, alloc,
                cap_pe, cap_mem,
            )
            if carry is None:
                carry = self.fresh_carry(
                    design, ed, r, seed, cap_pe=cap_pe, cap_mem=cap_mem,
                    alloc=alloc,
                )
            elif not isinstance(carry, ChainCarry):
                carry = ChainCarry(*carry)
        t_start = time.perf_counter()
        with span("chains.dispatch"):
            out = fn(
                carry, jnp.int32(it0), row0,
                table.kind, table.task, table.dest,
            )
        with span("chains.wait"):
            out_carry, (mv, acc, ft) = jax.block_until_ready(out)
        with span("chains.readback"):
            out_carry = ChainCarry(*(np.asarray(x) for x in out_carry))
            mv, acc, ft = np.asarray(mv), np.asarray(acc), np.asarray(ft)
        wall = time.perf_counter() - t_start
        self.n_dispatches += 1
        self.n_chain_steps += r * k
        return ChainBlockResult(
            task_pe=out_carry.task_pe,
            task_mem=out_carry.task_mem,
            fitness=out_carry.fitness,
            move_idx=mv,
            accepted=acc,
            fit_trace=ft,
            carry=out_carry,
            winner=int(np.argmin(out_carry.fitness)),
            wall_s=wall,
            n_moves=table.n_moves,
        )

    def run_chains_host(
        self,
        design: Design,
        budget: Budget,
        *,
        r: int = 1,
        n_steps: int,
        seed: int = 0,
        it0: int = 0,
        menu: str = "naive_sa",
        alpha: float = 0.05,
        temperature0: float = 0.05,
        temp_decay: float = 0.997,
        taboo_ttl: int = 5,
        carry: Optional[tuple] = None,
        alloc: bool = False,
        cap_pe: Optional[int] = None,
        cap_mem: Optional[int] = None,
    ) -> ChainBlockResult:
        """The host-driven reference accept loop: the SAME compiled chain
        step, dispatched K=1 at a time with the carry pulled back to host
        between iterations — one dispatch + one round trip per SA step,
        the regime of the classic host explorer. Because it shares the
        block body (same threefry draws, same f32 accept math — for the
        mixed mapping+allocation menu too), a fused K-step block must
        replay it bit-for-bit; this is the parity oracle and the speedup
        baseline."""
        t_start = time.perf_counter()
        mvs, accs, fts = [], [], []
        res = None
        for i in range(n_steps):
            res = self.run_chains(
                design, budget, r=r, k=1, seed=seed, it0=it0 + i, menu=menu,
                alpha=alpha, temperature0=temperature0, temp_decay=temp_decay,
                taboo_ttl=taboo_ttl, carry=carry, alloc=alloc,
                cap_pe=cap_pe, cap_mem=cap_mem,
            )
            carry = res.carry  # numpy — the per-iteration host round trip
            mvs.append(res.move_idx)
            accs.append(res.accepted)
            fts.append(res.fit_trace)
        wall = time.perf_counter() - t_start
        return ChainBlockResult(
            task_pe=res.task_pe,
            task_mem=res.task_mem,
            fitness=res.fitness,
            move_idx=np.concatenate(mvs, axis=1),
            accepted=np.concatenate(accs, axis=1),
            fit_trace=np.concatenate(fts, axis=1),
            carry=res.carry,
            winner=res.winner,
            wall_s=wall,
            n_moves=res.n_moves,
        )

    def reconcile(
        self,
        design: Design,
        res: ChainBlockResult,
        ed: Optional[EncodedDesign] = None,
        delta: Optional[MoveDelta] = None,
    ) -> Dict[str, Dict[str, str]]:
        """:func:`reconcile_mapping` against this runner's workload."""
        return reconcile_mapping(
            design, res, self.g, self.db, self.enc, ed=ed, delta=delta
        )

    def reconcile_alloc(
        self,
        design: Design,
        res: ChainBlockResult,
        ed: Optional[EncodedDesign] = None,
    ) -> Dict[str, object]:
        """:func:`reconcile_alloc` against this runner's workload."""
        return reconcile_alloc(
            design, res, self.g, self.db, self.enc, ed=ed
        )
