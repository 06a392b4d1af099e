"""Vectorized phase-driven simulator: evaluate a *batch* of SA neighbours in
one `vmap`'d XLA call.

The paper profiles its DSE at 79.9% design-duplication overhead (Fig. 8) —
a Python object-copy problem. We remove the object graph entirely: a design
is a flat array encoding (task→PE map, task→MEM map, per-slot knobs and PPA
coefficients), the TDG is dense matrices, and the phase loop is a
`lax.fori_loop` (every phase retires ≥1 task, so ≤T phases). `vmap` over the
design axis then evaluates all candidate neighbours of an explorer iteration
— or entire populations — in one dispatch.

Three things keep the *whole* explore→price→rank loop array-native:

  * **Incremental encoding** — a move emits a
    :class:`~repro.core.moves.MoveDelta`; :func:`apply_delta` turns the
    cached encoding of the current design into the neighbour's encoding
    (bit-identical to a from-scratch :meth:`EncodedDesign.of`) without
    cloning or re-walking the Python object graph.
  * **Device-side scoring** — the kernel folds the Eq.-7 budget distance
    and fitness (latency per workload, energy incl. leakage, area rollup)
    so one dispatch returns a ``(B,)`` fitness vector plus scalar PPA
    columns; the explorer ranks candidates from that small array.
  * **Lazy decode** — per-task dict reconstruction lives in
    ``backend.JaxBatchedBackend`` and is only paid by the winning candidate.

Scope: chain-topology designs with up to ``MAX_NOC`` NoCs. The encoding is
multi-NoC native: per-NoC ``(N,)`` knob/coefficient arrays in chain order, a
per-slot NoC-attachment index for every PE/MEM, and hop distances derived
from chain positions — so NoC fork/join moves emit ordinary encoding deltas
and ride the vectorized path instead of falling back to the Python
simulator. ``N`` pads to a power-of-two bucket per dispatch; the single-NoC
case (``N == 1``) compiles to exactly the formulation this module always
had, so the dominant regime pays nothing for the generality. Designs the
encoding still cannot host (chains beyond ``MAX_NOC``) raise
:class:`UnsupportedDesignError`, which the backend catches to route those
candidates to the scalar fallback. Equivalence against
`phase_sim.simulate` is asserted in tests for both regimes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import Block, BlockKind
from .database import HardwareDatabase
from .design import Design
from .moves import MoveDelta
from .tdg import TaskGraph, workload_of

BIG = 1e30

# the widest NoC chain the flat encoding hosts: chain positions are int32
# slot indices and the kernels unroll the per-NoC striping loop, so the cap
# is a compile-footprint guard, not a numerics limit (the link ladder tops
# out at 8 channels; explorations never grow chains past a handful)
MAX_NOC = 8


class UnsupportedDesignError(ValueError):
    """The design's shape falls outside what the flat encoding can host
    (today: NoC chains longer than ``MAX_NOC``). Typed — rather than a bare
    ``assert`` that vanishes under ``python -O`` — so the batched backend can
    catch it and route the candidate to the scalar Python fallback instead of
    silently mis-pricing it."""


@dataclasses.dataclass
class EncodedWorkload:
    """Static per-workload tensors (shared across all candidate designs)."""

    work_ops: jnp.ndarray  # (T,)
    read_bytes: jnp.ndarray  # (T,)
    write_bytes: jnp.ndarray  # (T,)
    burst: jnp.ndarray  # (T,)
    llp: jnp.ndarray  # (T,)
    parent_mask: jnp.ndarray  # (T, T) bool: [i, j] = j is a parent of i
    wl_id: jnp.ndarray  # (T,) int32 workload index per task
    names: List[str]
    wl_names: List[str]  # index -> workload name (graph name if unnamespaced)
    index: Dict[str, int] = dataclasses.field(default_factory=dict)

    @staticmethod
    def of(g: TaskGraph) -> "EncodedWorkload":
        names = list(g.tasks)
        idx = {n: i for i, n in enumerate(names)}
        t = len(names)
        pm = np.zeros((t, t), bool)
        for n in names:
            for p in g.parents[n]:
                pm[idx[n], idx[p]] = True
        wl_names: List[str] = []
        wl_id = np.zeros(t, np.int32)
        for i, n in enumerate(names):
            w = workload_of(n) if "." in n else g.name
            if w not in wl_names:
                wl_names.append(w)
            wl_id[i] = wl_names.index(w)
        f = lambda attr: jnp.asarray([getattr(g.tasks[n], attr) for n in names], jnp.float32)
        return EncodedWorkload(
            work_ops=f("work_ops"),
            read_bytes=jnp.asarray([g.tasks[n].read_bytes for n in names], jnp.float32),
            write_bytes=jnp.asarray([g.tasks[n].write_bytes for n in names], jnp.float32),
            burst=f("burst_bytes"),
            llp=f("llp"),
            parent_mask=jnp.asarray(pm),
            wl_id=jnp.asarray(wl_id),
            names=names,
            wl_names=wl_names,
            index=idx,
        )


# ---------------------------------------------------------------------------
# per-slot PPA coefficients (host-side closed forms the kernel sums on device)
# ---------------------------------------------------------------------------
def _pe_coeffs(b: Block, db: HardwareDatabase):
    """(peak ops/s, pJ/op, leak W, area mm²) of one PE block."""
    e = db.energy
    pj = e.acc_pj_per_op if b.subtype == "acc" else e.gpp_pj_per_op
    return db.pe_peak_ops(b), pj, db.leakage_w(b), db.block_area_mm2(b)


def _mem_coeffs(b: Block, db: HardwareDatabase):
    """(peak B/s, pJ/B, leak W, fixed area mm², area mm²/MB) of one MEM.

    SRAM area scales with resident capacity (CACTI-style), so it is split
    into a per-MB term the kernel multiplies by the segment-summed write
    bytes; DRAM is a fixed PHY block."""
    e = db.energy
    pj = e.sram_pj_per_byte if b.subtype == "sram" else e.dram_pj_per_byte
    if b.subtype == "sram":
        fixed, per_mb = 0.0, db.area.sram_mm2_per_mb
    else:
        fixed, per_mb = db.block_area_mm2(b), 0.0
    return b.peak_bandwidth(db), pj, db.leakage_w(b), fixed, per_mb


def _accel_of(b: Block, task_name: str, llp: float, db: HardwareDatabase) -> float:
    if b.hardened_for == task_name and b.subtype == "acc":
        return db.a_peak(task_name, llp, b.unroll)
    return 1.0


@dataclasses.dataclass
class EncodedDesign:
    """Flat design encoding: task maps, per-slot knobs *and* per-slot PPA
    coefficients, so pricing never revisits the Python object graph. Slot
    order is the design's block insertion order (PEs and MEMs separately),
    which is what makes :func:`apply_delta` reproducible bit-for-bit."""

    task_pe: np.ndarray  # (T,) int32 PE slot per task
    task_mem: np.ndarray  # (T,) int32 MEM slot per task
    pe_accel: np.ndarray  # (T,) effective acceleration of the task's PE for it
    pe_peak: np.ndarray  # (S_pe,) ops/s at a=1 (freq × ops/cycle)
    pe_pj: np.ndarray  # (S_pe,) dynamic pJ/op
    pe_leak: np.ndarray  # (S_pe,) leakage W
    pe_area: np.ndarray  # (S_pe,) mm²
    mem_bw: np.ndarray  # (S_mem,) bytes/s
    mem_pj: np.ndarray  # (S_mem,) dynamic pJ/byte
    mem_leak: np.ndarray  # (S_mem,) leakage W
    mem_area_fixed: np.ndarray  # (S_mem,) mm² (DRAM PHY; 0 for SRAM)
    mem_area_per_mb: np.ndarray  # (S_mem,) mm²/MB (SRAM; 0 for DRAM)
    # per-class active-slot masks (1.0 = slot exists in the design). Host
    # encodes are always all-ones — padding stays a *buffer* concept — but
    # the device-resident explorer prices allocation moves by toggling these
    # in place over capacity-padded inventories: an inactive slot keeps its
    # pad-neutral rates yet contributes nothing to the leak/area rollup.
    pe_active: np.ndarray  # (S_pe,) f32 mask
    mem_active: np.ndarray  # (S_mem,) f32 mask
    # per-NoC arrays in CHAIN order (index = chain position, so the hop
    # distance between two NoCs is |i − j| and a task's route is the index
    # interval between its PE's and its MEM's attachment)
    noc_bw: np.ndarray  # (N,) bytes/s per link
    noc_links: np.ndarray  # (N,) int32 channels
    noc_leak: np.ndarray  # (N,) leakage W
    noc_area: np.ndarray  # (N,) mm²
    noc_active: np.ndarray  # (N,) f32 mask (see pe_active)
    pe_noc: np.ndarray  # (S_pe,) int32 chain index each PE attaches to
    mem_noc: np.ndarray  # (S_mem,) int32 chain index each MEM attaches to
    noc_pj: np.float32  # dynamic pJ/byte·hop (db constant, rides the row so
    # the kernel never hardcodes an energy-model default)
    pe_slot: Dict[str, int]  # block name -> slot
    mem_slot: Dict[str, int]
    noc_slot: Dict[str, int]  # NoC name -> chain index

    @staticmethod
    def of(design: Design, g: TaskGraph, db: HardwareDatabase, enc: EncodedWorkload) -> "EncodedDesign":
        if not 1 <= len(design.noc_chain) <= MAX_NOC:
            raise UnsupportedDesignError(
                f"NoC chain of {len(design.noc_chain)} outside the encodable "
                f"range [1, {MAX_NOC}]"
            )
        noc_i = {n: i for i, n in enumerate(design.noc_chain)}
        # single pass over blocks: slot index maps + per-slot rates/coefficients
        pe_i: Dict[str, int] = {}
        mem_i: Dict[str, int] = {}
        pe_cols: List[tuple] = []
        mem_cols: List[tuple] = []
        pe_noc: List[int] = []
        mem_noc: List[int] = []
        for n, b in design.blocks.items():
            if b.kind == BlockKind.PE:
                pe_i[n] = len(pe_cols)
                pe_cols.append(_pe_coeffs(b, db))
                pe_noc.append(noc_i[design.attached_noc[n]])
            elif b.kind == BlockKind.MEM:
                mem_i[n] = len(mem_cols)
                mem_cols.append(_mem_coeffs(b, db))
                mem_noc.append(noc_i[design.attached_noc[n]])
        t = len(enc.names)
        d_pe, d_mem, blocks, tasks = design.task_pe, design.task_mem, design.blocks, g.tasks
        task_pe = np.fromiter((pe_i[d_pe[n]] for n in enc.names), np.int32, t)
        task_mem = np.fromiter((mem_i[d_mem[n]] for n in enc.names), np.int32, t)
        accel = np.ones(t, np.float32)
        for k, n in enumerate(enc.names):
            b = blocks[d_pe[n]]
            if b.hardened_for == n and b.subtype == "acc":
                accel[k] = db.a_peak(n, tasks[n].llp, b.unroll)
        nocs = [blocks[n] for n in design.noc_chain]
        f32col = lambda cols, j: np.asarray([c[j] for c in cols], np.float32)
        return EncodedDesign(
            task_pe=task_pe,
            task_mem=task_mem,
            pe_accel=accel,
            pe_peak=f32col(pe_cols, 0),
            pe_pj=f32col(pe_cols, 1),
            pe_leak=f32col(pe_cols, 2),
            pe_area=f32col(pe_cols, 3),
            mem_bw=f32col(mem_cols, 0),
            mem_pj=f32col(mem_cols, 1),
            mem_leak=f32col(mem_cols, 2),
            mem_area_fixed=f32col(mem_cols, 3),
            mem_area_per_mb=f32col(mem_cols, 4),
            pe_active=np.ones(len(pe_cols), np.float32),
            mem_active=np.ones(len(mem_cols), np.float32),
            noc_bw=np.asarray([b.peak_bandwidth(db) for b in nocs], np.float32),
            noc_links=np.asarray([b.n_links for b in nocs], np.int32),
            noc_leak=np.asarray([db.leakage_w(b) for b in nocs], np.float32),
            noc_area=np.asarray([db.block_area_mm2(b) for b in nocs], np.float32),
            noc_active=np.ones(len(nocs), np.float32),
            pe_noc=np.asarray(pe_noc, np.int32),
            mem_noc=np.asarray(mem_noc, np.int32),
            noc_pj=np.float32(db.energy.noc_pj_per_byte_hop),
            pe_slot=pe_i,
            mem_slot=mem_i,
            noc_slot=noc_i,
        )


def _append1(arr: np.ndarray, v) -> np.ndarray:
    """np.append without its ravel/concatenate overhead (hot path)."""
    out = np.empty(arr.shape[0] + 1, arr.dtype)
    out[:-1] = arr
    out[-1] = v
    return out


def _delete1(arr: np.ndarray, s: int) -> np.ndarray:
    """np.delete of one index without its mask machinery (hot path)."""
    out = np.empty(arr.shape[0] - 1, arr.dtype)
    out[:s] = arr[:s]
    out[s:] = arr[s + 1:]
    return out


def _insert1(arr: np.ndarray, s: int, v) -> np.ndarray:
    """np.insert of one value without its generic machinery (hot path)."""
    out = np.empty(arr.shape[0] + 1, arr.dtype)
    out[:s] = arr[:s]
    out[s] = v
    out[s + 1:] = arr[s:]
    return out


_NOC_ARRAY_FIELDS = ("noc_bw", "noc_links", "noc_leak", "noc_area", "noc_active")


def _noc_cols(b: Block, db: HardwareDatabase) -> tuple:
    return (
        np.float32(b.peak_bandwidth(db)), np.int32(b.n_links),
        np.float32(db.leakage_w(b)), np.float32(db.block_area_mm2(b)),
        np.float32(1.0),
    )


def apply_delta(
    base: "EncodedDesign",
    delta: MoveDelta,
    design: Design,
    g: TaskGraph,
    db: HardwareDatabase,
    enc: EncodedWorkload,
) -> "EncodedDesign":
    """Incremental re-encode: the neighbour's :class:`EncodedDesign` from the
    *current* design's cached encoding plus the move's recorded delta —
    bit-identical to ``EncodedDesign.of`` on the mutated design (asserted in
    tests/test_encoding_delta.py), at a handful of O(S)/O(T) numpy edits
    instead of a full Python-object walk.

    ``design`` is the *base* (pre-move) design: only blocks the delta did not
    touch are read from it, so it may be called before or after rollback.
    """
    if delta.topology:
        raise UnsupportedDesignError("delta flagged as unencodable (topology)")
    # copy-on-write: fields the delta does not touch stay *shared* with the
    # base encoding (`ed.f is base.f`), which both keeps a typical swap/
    # migrate delta at a couple of tiny array copies and lets the backend
    # detect exactly which buffer fields need rewriting per candidate
    ed = dataclasses.replace(base)

    def own(*fields: str) -> None:
        for f in fields:
            v = getattr(ed, f)
            if v is getattr(base, f):
                setattr(ed, f, v.copy() if isinstance(v, np.ndarray) else dict(v))

    touched_pe_slots: List[int] = []

    # 1) removals (join): compact slots exactly like a from-scratch encode.
    # A removed NoC compacts the chain; blocks it hosted carry explicit
    # re-attachment edits (delta.attached), applied in step 4b below.
    for name in delta.removed:
        if name in ed.pe_slot:
            s = ed.pe_slot[name]
            for f in ("pe_peak", "pe_pj", "pe_leak", "pe_area", "pe_active"):
                setattr(ed, f, _delete1(getattr(ed, f), s))
            ed.pe_slot = {n: i - (i > s) for n, i in ed.pe_slot.items() if n != name}
            ed.task_pe = ed.task_pe - (ed.task_pe > s)
            ed.pe_noc = _delete1(ed.pe_noc, s)
        elif name in ed.mem_slot:
            s = ed.mem_slot[name]
            for f in (
                "mem_bw", "mem_pj", "mem_leak", "mem_area_fixed",
                "mem_area_per_mb", "mem_active",
            ):
                setattr(ed, f, _delete1(getattr(ed, f), s))
            ed.mem_slot = {n: i - (i > s) for n, i in ed.mem_slot.items() if n != name}
            ed.task_mem = ed.task_mem - (ed.task_mem > s)
            ed.mem_noc = _delete1(ed.mem_noc, s)
        elif name in ed.noc_slot:
            s = ed.noc_slot[name]
            for f in _NOC_ARRAY_FIELDS:
                setattr(ed, f, _delete1(getattr(ed, f), s))
            ed.noc_slot = {n: i - (i > s) for n, i in ed.noc_slot.items() if n != name}
            ed.pe_noc = ed.pe_noc - (ed.pe_noc > s)
            ed.mem_noc = ed.mem_noc - (ed.mem_noc > s)

    # 2a) NoC additions (fork): INSERT at the recorded chain position — chain
    # order is the slot order, so every downstream chain index shifts by one
    for b in delta.added:
        if b.kind != BlockKind.NOC:
            continue
        p = ed.noc_slot[delta.noc_after] + 1 if delta.noc_after else ed.noc_bw.shape[0]
        ed.noc_slot = {n: i + (i >= p) for n, i in ed.noc_slot.items()}
        ed.noc_slot[b.name] = p
        for f, v in zip(_NOC_ARRAY_FIELDS, _noc_cols(b, db)):
            setattr(ed, f, _insert1(getattr(ed, f), p, v))
        ed.pe_noc = ed.pe_noc + (ed.pe_noc >= p)
        ed.mem_noc = ed.mem_noc + (ed.mem_noc >= p)

    # 2b) PE/MEM additions (fork): append at the end, matching dict insertion
    # order; the new slot's NoC attachment is the recorded one
    for b in delta.added:
        if b.kind == BlockKind.PE:
            own("pe_slot")
            ed.pe_slot[b.name] = ed.pe_peak.shape[0]
            cols = _pe_coeffs(b, db)
            for f, v in zip(("pe_peak", "pe_pj", "pe_leak", "pe_area"), cols):
                setattr(ed, f, _append1(getattr(ed, f), np.float32(v)))
            ed.pe_active = _append1(ed.pe_active, np.float32(1.0))
            ed.pe_noc = _append1(ed.pe_noc, ed.noc_slot[delta.attached[b.name]])
            touched_pe_slots.append(ed.pe_slot[b.name])
        elif b.kind == BlockKind.MEM:
            own("mem_slot")
            ed.mem_slot[b.name] = ed.mem_bw.shape[0]
            cols = _mem_coeffs(b, db)
            for f, v in zip(
                ("mem_bw", "mem_pj", "mem_leak", "mem_area_fixed", "mem_area_per_mb"), cols
            ):
                setattr(ed, f, _append1(getattr(ed, f), np.float32(v)))
            ed.mem_active = _append1(ed.mem_active, np.float32(1.0))
            ed.mem_noc = _append1(ed.mem_noc, ed.noc_slot[delta.attached[b.name]])

    # 3) knob edits (swap): refresh the touched slot's rate + coefficients
    for name, snap in delta.touched.items():
        if snap.kind == BlockKind.NOC:
            s = ed.noc_slot[name]
            own(*_NOC_ARRAY_FIELDS)
            for f, v in zip(_NOC_ARRAY_FIELDS, _noc_cols(snap, db)):
                getattr(ed, f)[s] = v
        elif name in ed.pe_slot:
            s = ed.pe_slot[name]
            own("pe_peak", "pe_pj", "pe_leak", "pe_area")
            for f, v in zip(("pe_peak", "pe_pj", "pe_leak", "pe_area"), _pe_coeffs(snap, db)):
                getattr(ed, f)[s] = np.float32(v)
            touched_pe_slots.append(s)
        elif name in ed.mem_slot:
            s = ed.mem_slot[name]
            own("mem_bw", "mem_pj", "mem_leak", "mem_area_fixed", "mem_area_per_mb")
            for f, v in zip(
                ("mem_bw", "mem_pj", "mem_leak", "mem_area_fixed", "mem_area_per_mb"),
                _mem_coeffs(snap, db),
            ):
                getattr(ed, f)[s] = np.float32(v)

    # 4) mapping edits (migrate / fork / join reassignments)
    moved: List[int] = []
    if delta.task_pe:
        own("task_pe")
        for t, pe in delta.task_pe.items():
            k = enc.index[t]
            ed.task_pe[k] = ed.pe_slot[pe]
            moved.append(k)
    if delta.task_mem:
        own("task_mem")
        for t, mem in delta.task_mem.items():
            ed.task_mem[enc.index[t]] = ed.mem_slot[mem]

    # 4b) NoC re-attachments (NoC fork/join re-home attached blocks; newly
    # added slots were already born attached — re-setting is idempotent)
    for bname, nocname in delta.attached.items():
        p = ed.noc_slot[nocname]
        if bname in ed.pe_slot:
            own("pe_noc")
            ed.pe_noc[ed.pe_slot[bname]] = p
        elif bname in ed.mem_slot:
            own("mem_noc")
            ed.mem_noc[ed.mem_slot[bname]] = p

    # 5) acceleration refresh for every task whose PE (or its knobs) changed
    if touched_pe_slots or moved:
        slot_name = {s: n for n, s in ed.pe_slot.items()}
        affected = set(moved)
        for s in set(touched_pe_slots):
            affected.update(np.nonzero(ed.task_pe == s)[0].tolist())
        block_of: Dict[str, Block] = {b.name: b for b in delta.added}
        block_of.update(delta.touched)
        own("pe_accel")
        for k in affected:
            name = slot_name[int(ed.task_pe[k])]
            b = block_of.get(name) or design.blocks[name]
            tname = enc.names[k]
            ed.pe_accel[k] = _accel_of(b, tname, g.tasks[tname].llp, db)
    return ed


# per-design row keys, in the order buffers are allocated/filled
ROW_KEYS = (
    "task_pe", "task_mem", "pe_accel",
    "pe_peak", "pe_pj", "pe_leak", "pe_area", "pe_noc", "pe_active",
    "mem_bw", "mem_pj", "mem_leak", "mem_area_fixed", "mem_area_per_mb",
    "mem_noc", "mem_active",
    "noc_bw", "noc_links", "noc_leak", "noc_area", "noc_active", "noc_pj",
    "wl_budget", "power_budget", "area_budget", "alpha",
)


def alloc_rows(
    b: int, t: int, n_pe: int, n_mem: int, n_wl: int, n_noc: int = 1
) -> Dict[str, np.ndarray]:
    """Preallocate one batch of padded per-design rows (host buffers the
    backend reuses across dispatches of the same shape bucket). Pad values:
    rates 1.0 (div-by-zero-free, never hosting tasks), coefficients 0.0
    (they are summed), budgets BIG / alpha 0 (neutral scoring). Padded NoC
    slots (chain indices ≥ the design's real chain length) carry no attached
    blocks, so no route ever crosses them."""
    rows = {
        "task_pe": np.zeros((b, t), np.int32),
        "task_mem": np.zeros((b, t), np.int32),
        "pe_accel": np.ones((b, t), np.float32),
        "pe_peak": np.ones((b, n_pe), np.float32),
        "pe_pj": np.zeros((b, n_pe), np.float32),
        "pe_leak": np.zeros((b, n_pe), np.float32),
        "pe_area": np.zeros((b, n_pe), np.float32),
        "pe_noc": np.zeros((b, n_pe), np.int32),
        "pe_active": np.zeros((b, n_pe), np.float32),
        "mem_bw": np.ones((b, n_mem), np.float32),
        "mem_pj": np.zeros((b, n_mem), np.float32),
        "mem_leak": np.zeros((b, n_mem), np.float32),
        "mem_area_fixed": np.zeros((b, n_mem), np.float32),
        "mem_area_per_mb": np.zeros((b, n_mem), np.float32),
        "mem_noc": np.zeros((b, n_mem), np.int32),
        "mem_active": np.zeros((b, n_mem), np.float32),
        "noc_bw": np.ones((b, n_noc), np.float32),
        "noc_links": np.ones((b, n_noc), np.int32),
        "noc_leak": np.zeros((b, n_noc), np.float32),
        "noc_area": np.zeros((b, n_noc), np.float32),
        "noc_active": np.zeros((b, n_noc), np.float32),
        "noc_pj": np.zeros((b,), np.float32),
        "wl_budget": np.full((b, n_wl), BIG, np.float32),
        "power_budget": np.full((b,), BIG, np.float32),
        "area_budget": np.full((b,), BIG, np.float32),
        "alpha": np.zeros((b,), np.float32),
    }
    return rows


_TASK_FIELDS = ("task_pe", "task_mem", "pe_accel")
_PE_FIELDS = ("pe_peak", "pe_pj", "pe_leak", "pe_area", "pe_noc", "pe_active")
_MEM_FIELDS = (
    "mem_bw", "mem_pj", "mem_leak", "mem_area_fixed", "mem_area_per_mb",
    "mem_noc", "mem_active",
)
ENCODED_FIELDS = _TASK_FIELDS + _PE_FIELDS + _MEM_FIELDS + _NOC_ARRAY_FIELDS


def fill_row_fields(
    rows: Dict[str, np.ndarray], j: int, ed: EncodedDesign, fields
) -> None:
    """Write a subset of one design's encoding into row ``j`` — the backend
    pairs this with the copy-on-write :func:`apply_delta` to rewrite only the
    buffer fields a candidate's move actually changed (``ed.f is not
    base.f``); everything else keeps the broadcast base-row content."""
    for f in fields:
        if f in _TASK_FIELDS:
            rows[f][j] = getattr(ed, f)
        elif f in _PE_FIELDS:
            s = ed.pe_peak.shape[0]
            rows[f][j, :s] = getattr(ed, f)
            rows[f][j, s:] = 1.0 if f == "pe_peak" else 0.0
        elif f in _MEM_FIELDS:
            m = ed.mem_bw.shape[0]
            rows[f][j, :m] = getattr(ed, f)
            rows[f][j, m:] = 1.0 if f == "mem_bw" else 0.0
        else:  # per-NoC chain arrays
            n = ed.noc_bw.shape[0]
            rows[f][j, :n] = getattr(ed, f)
            rows[f][j, n:] = 1.0 if f in ("noc_bw", "noc_links") else 0.0


def fill_row(rows: Dict[str, np.ndarray], j: int, ed: EncodedDesign) -> None:
    """Write one design's full encoding into row ``j`` of the padded buffers."""
    fill_row_fields(rows, j, ed, ENCODED_FIELDS)
    rows["noc_pj"][j] = ed.noc_pj


def fill_budget(
    rows: Dict[str, np.ndarray], j: int, enc: EncodedWorkload,
    latency_s: Dict[str, float], power_w: float, area_mm2: float, alpha: float,
) -> None:
    """Write one design's Eq.-7 budget row (device-side fitness inputs).
    Workloads the budget does not name score BIG (distance ≈ −1, never the
    binding term)."""
    rows["wl_budget"][j] = [latency_s.get(w, BIG) for w in enc.wl_names]
    rows["power_budget"][j] = power_w
    rows["area_budget"][j] = area_mm2
    rows["alpha"][j] = alpha


def simulate_one(enc: EncodedWorkload, row: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:  # repro: traced
    """Phase simulation + device-side scoring of ONE candidate row.

    This is the single-candidate oracle shared by :func:`simulate_batch`
    (``vmap`` over the row axis — the XLA reference path) and by
    ``repro.kernels.phase_sim`` (the fused Pallas kernel reimplements this
    math per grid program; parity ≤ 1e-5 is asserted in
    tests/test_phase_sim_kernel.py). See :func:`simulate_batch` for the
    contract and the co-residency-matvec formulation notes.
    """
    t = enc.work_ops.shape[0]
    n_wl = len(enc.wl_names)
    idx3 = jnp.arange(3)

    task_pe, task_mem = row["task_pe"], row["task_mem"]
    n_pe = row["pe_peak"].shape[-1]
    n_mem = row["mem_bw"].shape[-1]
    n_noc = row["noc_bw"].shape[-1]
    noc_bw = row["noc_bw"]
    # the loop-invariant hoists and the phase loop are device scopes of
    # their own (repro.runtime.spans), so a trace splits the two
    with jax.named_scope("phase_sim.setup"):
        # loop-invariant hoists: effective peak rates per task and the
        # same-slot co-residency masks behind Eq. 1/2 (PE share) and Eq. 4
        # (burst-proportional memory share)
        peak_eff = row["pe_peak"][task_pe] * row["pe_accel"]
        mem_peak = row["mem_bw"][task_mem]
        same_pe = (task_pe[:, None] == task_pe[None, :]).astype(jnp.float32)
        same_mem = (task_mem[:, None] == task_mem[None, :]).astype(jnp.float32)
        # one-hot task→slot maps: cap rollup and the per-slot bottleneck
        # telemetry accumulate through these instead of segment_sum scatters
        onehot_pe = (task_pe[:, None] == jnp.arange(n_pe)[None, :]).astype(jnp.float32)
        onehot_mem = (task_mem[:, None] == jnp.arange(n_mem)[None, :]).astype(jnp.float32)
        links = jnp.maximum(row["noc_links"], 1)  # (N,)
        # multi-NoC chain routing: a task's route is the chain-index interval
        # between its PE's and its MEM's NoC; hop count scales the NoC energy
        pe_pos = row["pe_noc"][task_pe]
        mem_pos = row["mem_noc"][task_mem]
        lo = jnp.minimum(pe_pos, mem_pos)
        hi = jnp.maximum(pe_pos, mem_pos)
        hops = (hi - lo + 1).astype(jnp.float32)
        nidx = jnp.arange(n_noc, dtype=jnp.int32)
        on_route = (
            (nidx[None, :] >= lo[:, None]) & (nidx[None, :] <= hi[:, None])
        ).astype(jnp.float32)  # (T, N)

    def noc_share(runf):
        """Eq. 3 per NoC: round-robin link striping (same link ⟺ running
        ranks congruent mod n_links), burst arbitration within the link;
        a task's end-to-end NoC bandwidth is the min over its route, and
        the argmin (first, in chain order — matching the Python
        reference's strict-< scan) is the binding NoC instance for the
        telemetry. The ``n_noc == 1`` branch is bit-for-bit the historic
        single-NoC formulation — the dominant regime compiles to exactly
        the math it always had."""
        if n_noc == 1:
            order = jnp.cumsum(runf)
            same_link = (runf[:, None] * runf[None, :]) * jnp.where(
                (order[:, None] - order[None, :]) % links[0] == 0, 1.0, 0.0
            )
            link_t = same_link @ enc.burst
            n_bw = noc_bw[0] * enc.burst / jnp.maximum(link_t, 1e-30)
            return n_bw, jnp.zeros((t,), jnp.int32)
        # multi-NoC: the same rank-residue striping, but through a (T, 8)
        # link one-hot (the link ladder tops out at 8 channels) instead of a
        # (T, T) co-residency mask per NoC — user u's link is
        # (rank_u − 1) mod n_links, link loads are one (8,) segment sum, so
        # the per-NoC cost is O(T·8), not O(T²)
        lidx = jnp.arange(8, dtype=jnp.float32)
        best = jnp.full((t,), BIG, jnp.float32)
        arg = jnp.zeros((t,), jnp.int32)
        for k in range(n_noc):  # N is a static padded bucket: unrolled
            use_k = on_route[:, k] * runf
            order = jnp.cumsum(use_k)
            link = jnp.where(use_k > 0, (order - 1.0) % links[k], -1.0)
            oh = (link[:, None] == lidx[None, :]).astype(jnp.float32)
            link_load = (enc.burst * use_k) @ oh  # (8,) burst per link
            link_t = oh @ link_load
            bw_k = jnp.where(
                use_k > 0,
                noc_bw[k] * enc.burst / jnp.maximum(link_t, 1e-30),
                BIG,
            )
            better = bw_k < best
            arg = jnp.where(better, k, arg)
            best = jnp.where(better, bw_k, best)
        return best, arg

    def phase(_, state):
        (rem_ops, rem_rd, rem_wr, completed, now, finish, bneck, bneck_noc,
         kind_s, pe_bt, mem_bt, noc_bt, alp_t, traffic, nph) = state
        running = (~completed) & jnp.all(~enc.parent_mask | completed[None, :], axis=1)
        runf = jnp.where(running, 1.0, 0.0)
        burst_run = enc.burst * runf

        # Eq. 1/2: preemptive equal share per PE slot
        load_t = same_pe @ runf  # running tasks sharing my PE (incl. me)
        compute = peak_eff / jnp.maximum(load_t, 1.0)

        # Eq. 4: burst-proportional memory share (read/write channels
        # split, but they see identical shares — one bandwidth suffices)
        mem_t = same_mem @ burst_run
        m_bw = mem_peak * enc.burst / jnp.maximum(mem_t, 1e-30)

        # Eq. 3: per-NoC link striping, end-to-end min over the route
        n_bw, noc_arg = noc_share(runf)

        bw = jnp.minimum(m_bw, n_bw)
        comp_t = rem_ops / compute
        comm_t = jnp.maximum(rem_rd, rem_wr) / bw
        c_t = jnp.where(running, jnp.maximum(comp_t, comm_t), BIG)
        phi_raw = jnp.min(c_t)  # Eq. 6
        any_run = phi_raw < BIG * 0.5
        phi = jnp.where(any_run, phi_raw, 0.0)
        phi_run = jnp.where(running, phi, 0.0)

        # binding resource per running task (gables.bottleneck_of — note:
        # attribution uses the task's *total* work over current rates, not
        # the remaining work; compute wins ties, then mem vs noc by the
        # tighter pipe)
        tot_comp_t = enc.work_ops / compute
        tot_comm_t = jnp.maximum(enc.read_bytes, enc.write_bytes) / bw
        code = jnp.where(tot_comp_t >= tot_comm_t, 0, jnp.where(m_bw <= n_bw, 1, 2))
        kind_s = kind_s + jnp.sum(
            jnp.where(code[:, None] == idx3[None, :], phi_run[:, None], 0.0), axis=0
        )
        # per-TASK bottleneck-time accumulators for the block telemetry:
        # task→slot maps are phase-invariant, so the slot resolution (one
        # (T,S) matvec each) happens once AFTER the loop — in-loop this is
        # just two (T,) masked adds, keeping the phase critical path flat
        pe_bt = pe_bt + jnp.where(code == 0, phi_run, 0.0)
        mem_bt = mem_bt + jnp.where(code == 1, phi_run, 0.0)
        # per-NoC binding seconds: the binding NoC varies per phase (it is
        # contention-dependent), so unlike the task→slot maps it cannot be
        # resolved after the loop. One NoC: it is just kind_s[2], resolved
        # post-loop; multi-NoC: one (T,N) masked matvec per phase.
        if n_noc > 1:
            noc_bt = noc_bt + jnp.where(code == 2, phi_run, 0.0) @ (
                noc_arg[:, None] == nidx[None, :]
            ).astype(jnp.float32)

        # mask rates BEFORE the phi multiply: slots hosting no running
        # task price as inf bandwidth, and inf · 0 would poison the
        # remain columns with NaN
        d_ops = jnp.where(running, compute, 0.0) * phi
        d_bw = jnp.where(running, bw, 0.0) * phi
        dr_ops = jnp.maximum(rem_ops - d_ops, 0.0)  # post-drain, pre-retire
        dr_rd = jnp.maximum(rem_rd - d_bw, 0.0)
        dr_wr = jnp.maximum(rem_wr - d_bw, 0.0)
        newly_done = running & (c_t <= phi * (1 + 1e-9))
        keep = ~newly_done
        now = now + phi
        finish = jnp.where(newly_done, now, finish)
        bneck = jnp.where(newly_done, code, bneck)
        if n_noc > 1:  # binding NoC instance at completion (chain index)
            bneck_noc = jnp.where(newly_done, noc_arg, bneck_noc)
        # busy-PE count: each PE with k running tasks contributes k · 1/k
        alp_t = alp_t + phi * jnp.sum(runf / jnp.maximum(load_t, 1.0))
        # phase_sim accumulates min(post-drain bytes, bw·phi) per running
        # task — mirror it exactly so the backends agree on this field too
        traffic = traffic + jnp.sum(
            jnp.where(running, jnp.minimum(dr_rd + dr_wr, d_bw + d_bw), 0.0)
        )
        nph = nph + jnp.where(any_run, 1, 0)
        return (
            jnp.where(keep, dr_ops, 0.0), jnp.where(keep, dr_rd, 0.0),
            jnp.where(keep, dr_wr, 0.0), completed | newly_done, now, finish,
            bneck, bneck_noc, kind_s, pe_bt, mem_bt, noc_bt, alp_t, traffic,
            nph,
        )

    state = (
        enc.work_ops,
        enc.read_bytes,
        enc.write_bytes,
        jnp.zeros((t,), bool),
        jnp.float32(0.0),
        jnp.zeros((t,), jnp.float32),
        jnp.zeros((t,), jnp.int32),
        jnp.zeros((t,), jnp.int32),
        jnp.zeros((3,), jnp.float32),
        jnp.zeros((t,), jnp.float32),
        jnp.zeros((t,), jnp.float32),
        jnp.zeros((n_noc,), jnp.float32),
        jnp.float32(0.0),
        jnp.float32(0.0),
        jnp.int32(0),
    )
    with jax.named_scope("phase_sim.phases"):
        (rem_ops, rem_rd, rem_wr, completed, now, finish, bneck, bneck_noc,
         kind_s, pe_bt, mem_bt, noc_bt, alp_t, traffic, nph) = jax.lax.fori_loop(
            0, t, phase, state)
    # per-BLOCK bottleneck telemetry: phi attribution resolved to the
    # binding slot (task_pe for compute-bound, task_mem for memory-bound;
    # single-NoC chains resolve their one NoC column from kind_s[2])
    pe_b = pe_bt @ onehot_pe
    mem_b = mem_bt @ onehot_mem
    noc_b = kind_s[2:3] if n_noc == 1 else noc_bt

    # ---- device-side PPA rollup + Eq.-7 fitness ----------------------
    # dynamic energy is rate-independent (every task drains its totals;
    # the NoC term scales with the task's route hop count), so it is a
    # coefficient dot
    wl_lat = jax.ops.segment_max(finish, enc.wl_id, num_segments=n_wl)
    dyn_pj = jnp.sum(
        row["pe_pj"][task_pe] * enc.work_ops
        + (row["mem_pj"][task_mem] + row["noc_pj"] * hops)
        * (enc.read_bytes + enc.write_bytes)
    )
    # active-slot masked rollups: inactive slots (device-side joins over the
    # capacity-padded inventory — host rows are all-active with 0.0 pads, so
    # the mask multiply is bit-exact there) price as absent hardware
    leak_w = (
        jnp.sum(row["pe_leak"] * row["pe_active"])
        + jnp.sum(row["mem_leak"] * row["mem_active"])
        + jnp.sum(row["noc_leak"] * row["noc_active"])
    )
    energy = dyn_pj * 1e-12 + leak_w * now
    power = jnp.where(now > 0, energy / jnp.maximum(now, 1e-30), 0.0)
    cap = enc.write_bytes @ onehot_mem
    area = (
        jnp.sum(row["pe_area"] * row["pe_active"])
        + jnp.sum(
            (
                row["mem_area_fixed"]
                + row["mem_area_per_mb"] * jnp.maximum(cap, 1.0) / 1e6
            )
            * row["mem_active"]
        )
        + jnp.sum(row["noc_area"] * row["noc_active"])
    )
    dists = jnp.stack(
        [
            jnp.max((wl_lat - row["wl_budget"]) / row["wl_budget"]),
            (power - row["power_budget"]) / row["power_budget"],
            (area - row["area_budget"]) / row["area_budget"],
        ]
    )
    fitness = jnp.sum(jnp.where(dists > 0, dists, row["alpha"] * dists))
    return {
        "latency_s": now,
        "finish_s": finish,
        "all_done": jnp.all(completed),
        # packed per-task binding code: 0 = pe, 1 = mem, 2 + 3·k = NoC at
        # chain index k (single-NoC packs to the historic {0, 1, 2} values)
        "bneck_code": jnp.where(bneck == 2, 2 + 3 * bneck_noc, bneck),
        "bneck_kind_s": kind_s,
        # per-block bottleneck telemetry (slot order = encoding slot order):
        # seconds each PE/MEM slot was the binding bottleneck, plus the
        # argmax slot per class — the columns the telemetry-driven policies
        # select their next focus from without any host-side decode
        "pe_bneck_s": pe_b,
        "mem_bneck_s": mem_b,
        "noc_bneck_s": noc_b,
        "top_bneck_pe": jnp.argmax(pe_b).astype(jnp.int32),
        "top_bneck_mem": jnp.argmax(mem_b).astype(jnp.int32),
        "alp_time_s": alp_t,
        "traffic_bytes": traffic,
        "n_phases": nph,
        "wl_latency_s": wl_lat,
        "energy_j": energy,
        "power_w": power,
        "area_mm2": area,
        "fitness": fitness,
    }


def simulate_batch(  # repro: traced
    enc: EncodedWorkload,
    rows: Dict[str, jnp.ndarray],
) -> Dict[str, jnp.ndarray]:
    """vmap'd phase simulation + device-side scoring.

    ``rows`` is a dict of per-design arrays (batch axis leading; see
    ``ROW_KEYS``/:func:`alloc_rows`). Returns latency (B,), task finish
    times (B, T), the per-task / per-phase attribution a
    :class:`~repro.core.backend.JaxBatchedBackend` needs to lazily
    reconstruct a full ``SimResult`` (binding-resource code per task,
    time-weighted bottleneck seconds, ALP time, traffic, phase count) —
    plus the scalar PPA columns (energy/power/area, per-workload latency)
    and the Eq.-7 ``fitness`` vector the explorer ranks with, so accepting
    or rejecting a whole neighbour batch transfers O(B) floats, not B
    decoded dicts.

    Contention sums are (T, T) co-residency matvecs, not ``segment_sum``
    scatters: ``task_pe``/``task_mem`` are phase-invariant so the same-slot
    masks hoist out of the loop, and vmapped scatter/gather pairs are the
    dominant cost of the phase loop on CPU XLA (~4x kernel time). NoC
    round-robin striping (Eq. 3) is expressed the same way through rank
    residues — two running tasks share a link iff their running-order ranks
    are congruent mod ``n_links`` — which is exact for *any* link count
    (the old segment-bucketed formulation silently dropped the bandwidth
    attribution of links ≥ its hardcoded segment count).

    This is the XLA *reference* path; ``repro.kernels.phase_sim`` provides
    the fused Pallas formulation of the same math (one launch over the
    (B, T) grid, Mosaic on TPU / interpret on CPU) selected via
    ``JaxBatchedBackend(use_kernel=True)``.
    """
    return jax.vmap(lambda row: simulate_one(enc, row))(rows)


def encode_batch(
    designs: List[Design],
    g: TaskGraph,
    db: HardwareDatabase,
    enc: EncodedWorkload,
    n_pe: int = 0,
    n_mem: int = 0,
    n_noc: int = 0,
) -> Dict[str, np.ndarray]:
    """Pad a list of designs to common slot/chain counts and stack into a
    :func:`simulate_batch` rows dict (neutral budget rows — callers that
    want device-side fitness fill them via :func:`fill_budget`).

    ``n_pe``/``n_mem``/``n_noc`` optionally force the padded counts —
    backends pad to shape buckets so the jit cache is keyed on a handful of
    shapes instead of recompiling every time a move adds a block or forks a
    NoC. Returns host (numpy) arrays; `jax.jit` transfers them on dispatch.
    """
    encs = [EncodedDesign.of(d, g, db, enc) for d in designs]
    b, t = len(encs), len(enc.names)
    n_pe = max(n_pe, max(e.pe_peak.shape[0] for e in encs))
    n_mem = max(n_mem, max(e.mem_bw.shape[0] for e in encs))
    n_noc = max(n_noc, max(e.noc_bw.shape[0] for e in encs))
    rows = alloc_rows(b, t, n_pe, n_mem, len(enc.wl_names), n_noc)
    for i, e in enumerate(encs):
        fill_row(rows, i, e)
    return rows
