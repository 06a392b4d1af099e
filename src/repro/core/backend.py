"""Pluggable simulation backends: one batched ``evaluate`` API.

The paper's headline claim is an *agile* simulator (8,400X vs Platform
Architect at 98.5% accuracy) driving the DSE, and its own profile (Fig. 8)
puts 79.9% of exploration time in design evaluation overhead. This module
makes the evaluator a pluggable component behind a single batched interface
so the search loop never cares how a design is priced:

  ``PythonBackend``     — the reference phase-driven simulator
                          (`phase_sim.simulate`), one design at a time.
  ``JaxBatchedBackend`` — flat-array encodings evaluated under `vmap` in one
                          XLA dispatch per batch (`phase_sim_jax`), with a
                          jit cache keyed on power-of-two padded
                          slot/batch/NoC-chain shapes. Multi-NoC chains are
                          encoded natively (NoC fork/join moves are ordinary
                          deltas); the transparent per-design fallback to the
                          Python path remains only for shapes the encoding
                          cannot host (``UnsupportedDesignError`` — chains
                          beyond ``phase_sim_jax.MAX_NOC``).

The DSE hot path is :meth:`evaluate_candidates`: the explorer submits
lightweight :class:`Candidate` records (base design + recorded move delta —
no cloned object graphs), the backend applies each delta onto the cached
encoding of the base (`phase_sim_jax.apply_delta`) inside persistent
preallocated shape-bucket buffers, and one non-blocking dispatch returns
:class:`SimHandle` objects. A handle's Eq.-7 ``fitness`` (computed on
device) and scalar PPA columns are one small host transfer for the whole
batch; the full ``SimResult`` (per-task finish/bottleneck/energy dicts) is
reconstructed lazily on first ``result()`` — only the candidate the explorer
accepts ever pays the decode.

``evaluate(designs)`` stays as the eager compatibility wrapper (it decodes
everything). Both backends must agree on latency/finish times (asserted in
tests/test_backend_campaign.py); simulation-count and wall-clock accounting
live here, in ``BackendStats``.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator, List, Optional, Protocol, Sequence, runtime_checkable

import numpy as np

from ..runtime.spans import span
from .blocks import BlockKind
from .budgets import Budget, Distance, distance
from .database import HardwareDatabase
from .design import Design
from .moves import MoveDelta, MoveSpec, apply_spec
from .phase_sim import SimResult, simulate
from .ppa import total_leakage_w
from .tdg import TaskGraph, workload_of

_BNECK_KINDS = ("pe", "mem", "noc")


@dataclasses.dataclass
class BackendStats:
    """Evaluation accounting — the backend owns n_sims and sim wall-clock.

    ``wall_s`` covers time inside ``evaluate``/``evaluate_candidates``
    (and ``run_chains``); the encode/dispatch/fetch/decode breakdown splits
    the JAX hot path: host-side delta encoding into the batch buffers, XLA
    dispatch submission (async — device time is hidden behind it), the
    first fetch of a batch's outputs (the wait for the device, then the
    transfer), and the host decode of fetched outputs into scalars,
    telemetry and lazy ``SimResult`` s (paid per *accessed* handle, possibly
    after the dispatch returns, so neither ``fetch_wait_s`` nor
    ``decode_s`` is a subset of ``wall_s``). Each time field is summed by a
    host span (``repro.runtime.spans``), named beside the field."""

    n_sims: int = 0  # designs evaluated (cache-served candidates included)
    n_dispatches: int = 0  # evaluate() calls
    n_batched: int = 0  # designs through the vectorized path
    n_fallback: int = 0  # designs through the scalar Python path
    n_compiles: int = 0  # distinct padded shapes seen by the jit cache
    # content-addressed evaluation cache (serve.DesignStore, when attached):
    # hits never dispatch a device row — they are served from a memoized row
    # of an earlier identical (encoding, workload, budget) evaluation or
    # alias a duplicate row inside the same dispatch; bypasses are scalar-
    # fallback candidates the cache cannot host. All zero with no store.
    n_cache_hits: int = 0
    n_cache_misses: int = 0  # rows dispatched and registered in the store
    n_cache_bypass: int = 0
    # rows whose device fitness came back NaN/Inf at the host scal pull —
    # the serve layer's non-finite guard rejects these; a nonzero count on a
    # healthy backend means a numerical escape worth investigating
    n_nonfinite_rows: int = 0
    # total time inside the entries (spans backend.designs, backend.candidates,
    # backend.run_chains)
    wall_s: float = 0.0
    encode_s: float = 0.0  # incremental encoding into batch buffers (backend.encode)
    dispatch_s: float = 0.0  # XLA dispatch submission (backend.dispatch)
    fetch_wait_s: float = 0.0  # first fetch of each batch's outputs (backend.fetch_wait)
    decode_s: float = 0.0  # host decode of fetched outputs (backend.decode)


@dataclasses.dataclass
class Candidate:
    """One design to price: a shared *base* design plus an optional recorded
    move. The move is replayed (``apply_spec``) only when a full ``Design``
    is needed — python fallback, lazy decode, or explorer acceptance; the
    vectorized path prices the candidate straight from ``delta`` without
    ever materializing the object graph."""

    base: Design
    spec: Optional[MoveSpec] = None
    delta: Optional[MoveDelta] = None
    budget: Optional[Budget] = None  # enables device-side Eq.-7 fitness
    alpha: float = 0.05

    @staticmethod
    def of_design(design: Design, budget: Optional[Budget] = None,
                  alpha: float = 0.05) -> "Candidate":
        return Candidate(base=design, budget=budget, alpha=alpha)

    def vectorizable(self) -> bool:
        """True when the *resulting* design stays inside the encodable
        regime (a chain of at most ``phase_sim_jax.MAX_NOC`` NoCs) and (for
        moved candidates) the delta path can encode it — topology moves
        included, since NoC fork/join record chain/attachment edits."""
        from .phase_sim_jax import MAX_NOC

        n = len(self.base.noc_chain)
        if self.spec is not None:
            if self.delta is None or self.delta.topology:
                return False
            blocks = self.base.blocks
            for b in self.delta.added:
                n += b.kind == BlockKind.NOC
            for name in self.delta.removed:
                blk = blocks.get(name)
                n -= blk is not None and blk.kind == BlockKind.NOC
        return 1 <= n <= MAX_NOC

    def _replay(self, tdg: TaskGraph) -> None:
        """Replay the recorded move, then rename any block the replay minted
        back to the name recorded in the delta: every materialization of
        this candidate — pricing fallback, lazy decode, and the final
        ``accept`` — must agree on block names, or the decoded
        ``SimResult``'s per-task block references would dangle in the
        accepted design."""
        before = None
        if self.delta is not None and self.delta.added:
            before = set(self.base.blocks)
        ok = apply_spec(self.base, tdg, self.spec)
        assert ok, f"recorded move failed to replay: {self.spec}"
        if before is not None:
            minted = [n for n in self.base.blocks if n not in before]
            for fresh, rec in zip(minted, self.delta.added):
                if fresh != rec.name:
                    self.base.rename_block(fresh, rec.name)

    @contextlib.contextmanager
    def materialized(self, tdg: TaskGraph) -> Iterator[Design]:
        """Temporarily turn the candidate into a real ``Design`` (apply the
        recorded move in place, roll back on exit). The base must be in the
        state it had when the move was recorded — the explorer guarantees
        that by materializing/accepting before mutating ``cur``."""
        if self.spec is None:
            yield self.base
            return
        ck = self.base.checkpoint()
        self._replay(tdg)
        try:
            yield self.base
        finally:
            self.base.restore(ck)

    def accept(self, tdg: TaskGraph) -> None:
        """Apply the recorded move to the base permanently (the one full
        materialization the whole batch pays)."""
        if self.spec is not None:
            self._replay(tdg)


@runtime_checkable
class SimHandle(Protocol):
    """Lazy result of pricing one candidate."""

    @property
    def fitness(self) -> float:
        """Eq.-7 distance-to-budget fitness (requires Candidate.budget)."""
        ...

    def scalars(self) -> Dict[str, float]:
        """Cheap PPA columns: latency_s / power_w / area_mm2 (no decode)."""
        ...

    def result(self) -> SimResult:
        """Full SimResult; reconstructed on first access."""
        ...

    def telemetry(self) -> "SimTelemetry":
        """Selection-grade view (device bottleneck columns + Eq.-7
        distance) — what the heuristic-policy layer reasons over instead of
        a full decode. Same validity contract as ``result()``: the
        candidate's base design must be in its priced (pre-accept) state."""
        ...

    def result_for(self, design: Design) -> SimResult:
        """Decode against an explicitly provided materialized design — for
        consumers (the explorer's final best-design decode) that read a
        handle long after the candidate's base has mutated past it."""
        ...


@runtime_checkable
class SimulatorBackend(Protocol):
    """Anything that prices a batch of designs for one task graph."""

    name: str
    tdg: TaskGraph
    db: HardwareDatabase

    def evaluate(self, designs: Sequence[Design]) -> List[SimResult]:
        """Simulate every design eagerly; results align with the input order."""
        ...

    def evaluate_candidates(self, cands: Sequence[Candidate]) -> List[SimHandle]:
        """Price a batch of candidates, returning lazy handles — the DSE hot
        path. The call is NON-BLOCKING on asynchronous backends (it returns
        once the dispatch is submitted; nothing crosses the device boundary
        until a handle is read), so several batches may be in flight at
        once. ``flush()`` is the only way to wait without consuming."""
        ...

    def flush(self) -> None:
        """Block until every in-flight dispatch has finished scoring.
        Synchronous backends are already drained — no-op. Call it before
        tearing a backend down or timing device work; reading any handle of
        a batch also implicitly completes that batch."""
        ...

    def supports(self, design: Design) -> bool:
        """True if ``design`` takes the backend's fast path (capability hook;
        unsupported designs must still evaluate correctly via fallback)."""
        ...

    def stats(self) -> BackendStats:
        ...


class _ReadyHandle:
    """Handle over an already-decoded SimResult (python path / fallbacks).

    Carries its candidate so ``adopt_encoding`` can tell WHOSE cached base
    encoding to invalidate when a fallback-priced move gets accepted."""

    __slots__ = ("_res", "_fitness", "_cand", "_tdg")

    def __init__(self, res: SimResult, fitness: float,
                 cand: Optional[Candidate] = None,
                 tdg: Optional[TaskGraph] = None) -> None:
        self._res = res
        self._fitness = fitness
        self._cand = cand
        self._tdg = tdg

    @property
    def fitness(self) -> float:
        return self._fitness

    def scalars(self) -> Dict[str, float]:
        return {
            "latency_s": self._res.latency_s,
            "power_w": self._res.power_w,
            "area_mm2": self._res.area_mm2,
        }

    def result(self) -> SimResult:
        return self._res

    def result_for(self, design: Design) -> SimResult:
        return self._res  # already decoded; the design played no further part

    def telemetry(self) -> "SimTelemetry":
        assert self._tdg is not None, "handle was built without its TaskGraph"
        design = self._cand.base if self._cand is not None else None
        return SimTelemetry.of_result(self._res, self._tdg, design)


def _host_fitness(res: SimResult, cand: Candidate) -> float:
    if cand.budget is None:
        return float("nan")
    return distance(res, cand.budget).fitness(cand.alpha)


class _PPAView:
    """Duck-typed stand-in for the three SimResult fields `budgets.distance`
    reads — lets a telemetry view reuse the one true Eq.-7 distance code."""

    __slots__ = ("workload_latency_s", "power_w", "area_mm2")

    def __init__(self, wl: Dict[str, float], power: float, area: float) -> None:
        self.workload_latency_s = wl
        self.power_w = power
        self.area_mm2 = area


class SimTelemetry:
    """Selection-grade view of one priced candidate — the input the
    heuristic-policy layer (`repro.core.policy`) reasons over.

    It exposes (a) the device-side bottleneck telemetry columns — per-block
    binding-bottleneck seconds, the argmax ("top bottleneck") PE/MEM block,
    and the comp-vs-comm attribution split — and (b) the per-task /
    per-metric accessors FARSI's selection reasoning needs (task durations,
    per-task dynamic energy, memory residency, per-task binding resource),
    plus the Eq.-7 ``Distance``. What it does NOT do is materialize the full
    ``SimResult`` dict set: on the JAX backend a view is a handful of
    zero-copy column reads plus an O(T) host scalar rollup, which is what
    makes the winner's full ``_decode`` policy-optional.

    Built either over an already-decoded ``SimResult`` (`of_result` — the
    Python backend and fallback-priced candidates; every accessor proxies
    the result, so policies see bit-identical floats on either backend) or
    over one row of a JAX batch's host columns (`of_row`). Row-backed
    construction snapshots the task→block maps and recomputes the
    design-dependent scalars (energy, power, area, capacities) exactly as
    the lazy decode would — shared backend helpers — so telemetry-driven
    searches take the same decisions as decode-driven ones (asserted by the
    golden-sequence policy-equivalence tests). Construction has the same
    contract as ``SimHandle.result()``: the candidate's base design must
    still be in its priced state."""

    __slots__ = (
        "_tdg", "_res", "_design",
        "latency_s", "power_w", "area_mm2",
        "_wl_lat", "_tep", "_cap",
        "_fin", "_index", "_codes", "_task_pe", "_task_mem", "_nocs",
        "_pe_names", "_mem_names", "_pe_busy", "_mem_busy", "_noc_busy",
        "_kind", "_top_pe", "_top_mem",
    )

    # ---- births ----------------------------------------------------------
    @staticmethod
    def of_result(res: SimResult, tdg: TaskGraph,
                  design: Optional[Design] = None) -> "SimTelemetry":
        t = SimTelemetry()
        t._tdg, t._res, t._design = tdg, res, design
        t.latency_s = res.latency_s
        t.power_w = res.power_w
        t.area_mm2 = res.area_mm2
        t._top_pe = t._top_mem = None  # resolved lazily through the design
        return t

    @staticmethod
    def of_row(batch: "_JaxBatch", j: int, cand: Candidate,
               backend: "JaxBatchedBackend") -> "SimTelemetry":
        out = batch.host()  # forces the batch, like any first handle read
        t = SimTelemetry()
        t._tdg, t._res, t._design = backend.tdg, None, cand.base
        t._index = backend._enc.index
        t._fin = out["finish_s"][j].tolist()
        t._codes = out["bneck_code"][j]
        t._kind = out["bneck_kind_s"][j]
        t._pe_busy = out["pe_bneck_s"][j]
        t._mem_busy = out["mem_bneck_s"][j]
        t._noc_busy = out["noc_bneck_s"][j]
        t.latency_s = float(out["latency_s"][j])
        # design-dependent snapshot: the base design is only guaranteed to be
        # in the priced state NOW, so task→block maps and the host-exact
        # scalar rollup (the same floats the lazy decode would produce) are
        # captured at construction; everything else indexes device columns
        with cand.materialized(backend.tdg) as design:
            t._tep = backend._task_energy_pj(design)
            t._cap = backend._mem_caps(design)
            t.area_mm2 = backend._area_mm2(design, t._cap)
            energy = sum(t._tep.values()) * 1e-12 + total_leakage_w(
                design, backend.db
            ) * t.latency_s
            t.power_w = energy / t.latency_s if t.latency_s > 0 else 0.0
            t._task_pe = dict(design.task_pe)
            t._task_mem = dict(design.task_mem)
            t._nocs = list(design.noc_chain)
            t._pe_names = [n for n, b in design.blocks.items()
                           if b.kind == BlockKind.PE]
            t._mem_names = [n for n, b in design.blocks.items()
                            if b.kind == BlockKind.MEM]
        t._wl_lat = backend._wl_latency(t._fin)
        t._top_pe = t._pe_names[
            min(int(out["top_bneck_pe"][j]), len(t._pe_names) - 1)]
        t._top_mem = t._mem_names[
            min(int(out["top_bneck_mem"][j]), len(t._mem_names) - 1)]
        return t

    # ---- Eq.-7 distance --------------------------------------------------
    def dist(self, budget: Budget) -> Distance:
        if self._res is not None:
            return distance(self._res, budget)
        return distance(_PPAView(self._wl_lat, self.power_w, self.area_mm2),
                        budget)

    # ---- per-task selection accessors ------------------------------------
    def task_finish_s(self, t: str) -> float:
        if self._res is not None:
            return self._res.task_finish_s.get(t, 0.0)
        return self._fin[self._index[t]]

    def task_duration(self, t: str) -> float:
        """Critical-path duration contribution: finish − latest parent
        finish (what `_task_duration` computed from a decoded result)."""
        start = max(
            (self.task_finish_s(p) for p in self._tdg.parents[t]), default=0.0
        )
        return self.task_finish_s(t) - start

    def task_energy_j(self, t: str) -> float:
        if self._res is not None:
            return self._res.task_energy_j.get(t, 0.0)
        return self._tep.get(t, 0.0) * 1e-12

    def mem_capacity(self, m: str) -> float:
        if self._res is not None:
            return self._res.mem_capacity_bytes.get(m, 0.0)
        return self._cap.get(m, 0.0)

    def task_bneck(self, t: str) -> str:
        if self._res is not None:
            return self._res.task_bottleneck.get(t, "pe")
        # codes are packed: 0/1 = pe/mem, 2 + 3·k = NoC at chain index k
        return _BNECK_KINDS[min(int(self._codes[self._index[t]]), 2)]

    def task_bneck_block(self, t: str) -> Optional[str]:
        if self._res is not None:
            return self._res.task_bottleneck_block.get(t)
        c = int(self._codes[self._index[t]])
        return self._task_pe[t] if c == 0 else (
            self._task_mem[t] if c == 1 else self._nocs[(c - 2) // 3]
        )

    # ---- device bottleneck telemetry -------------------------------------
    @property
    def comp_s(self) -> float:
        """Seconds some running task was compute-bound (kind column 'pe')."""
        if self._res is not None:
            return self._res.bottleneck_s.get("pe", 0.0)
        return float(self._kind[0])

    @property
    def comm_s(self) -> float:
        """Seconds some running task was communication-bound (mem + noc)."""
        if self._res is not None:
            b = self._res.bottleneck_s
            return b.get("mem", 0.0) + b.get("noc", 0.0)
        return float(self._kind[1] + self._kind[2])

    def _top_of_kind(self, kind: BlockKind) -> Optional[str]:
        if self._design is None:
            return None
        best, best_s = None, -1.0
        for n, b in self._design.blocks.items():
            if b.kind == kind:
                s = self._res.block_bottleneck_s.get(n, 0.0)
                if s > best_s:
                    best, best_s = n, s
        return best

    def top_bneck_pe(self) -> Optional[str]:
        """The PE accumulating the most binding-bottleneck seconds — the
        device argmax column on JAX, the host attribution otherwise."""
        if self._top_pe is None and self._res is not None:
            self._top_pe = self._top_of_kind(BlockKind.PE)
        return self._top_pe

    def top_bneck_mem(self) -> Optional[str]:
        if self._top_mem is None and self._res is not None:
            self._top_mem = self._top_of_kind(BlockKind.MEM)
        return self._top_mem

    def block_bneck_s(self) -> Dict[str, float]:
        """Per-block binding-bottleneck seconds (name-resolved)."""
        if self._res is not None:
            return dict(self._res.block_bottleneck_s)
        out = {n: float(self._pe_busy[i]) for i, n in enumerate(self._pe_names)}
        out.update(
            (n, float(self._mem_busy[i])) for i, n in enumerate(self._mem_names)
        )
        out.update(
            (n, float(self._noc_busy[i])) for i, n in enumerate(self._nocs)
        )
        return out


class PythonBackend:
    """Scalar reference path: `phase_sim.simulate` per design."""

    name = "python"
    async_dispatch = False  # evaluates inline: nothing to pipeline behind

    def __init__(self, tdg: TaskGraph, db: HardwareDatabase) -> None:
        self.tdg = tdg
        self.db = db
        self._stats = BackendStats()

    def supports(self, design: Design) -> bool:
        return True

    def flush(self) -> None:
        """Synchronous backend: every evaluate() already returned results."""

    def evaluate(self, designs: Sequence[Design]) -> List[SimResult]:
        with span("backend.designs", stats=self._stats, field="wall_s"):
            out = [simulate(d, self.tdg, self.db) for d in designs]
        self._stats.n_sims += len(out)
        self._stats.n_dispatches += 1
        return out

    def evaluate_candidates(self, cands: Sequence[Candidate]) -> List[SimHandle]:
        with span("backend.candidates", stats=self._stats, field="wall_s"):
            out: List[SimHandle] = []
            for c in cands:
                with c.materialized(self.tdg) as d:
                    res = simulate(d, self.tdg, self.db)
                out.append(_ReadyHandle(res, _host_fitness(res, c), c, self.tdg))
        self._stats.n_sims += len(out)
        self._stats.n_dispatches += 1
        return out

    def stats(self) -> BackendStats:
        return self._stats


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _bucket(n: int) -> int:
    """Padded-size bucket: power of two, floored at 4. Compile time per shape
    dwarfs the padded FLOPs on these tiny kernels, so we buy a near-constant
    shape space (slots and batch rarely leave {4, 8, 16, 32, 64}) with
    padding — but the floor matters on the batch axis: the explorer's
    neighbour batches are ≤ 4 candidates, and padding them to 8 doubled the
    device time the serial loop stalls on."""
    return max(4, _pow2(n))


# layout of the device-packed scalar column block: the jit wrapper stacks
# every per-design scalar into ONE (B, N_SCAL + 2·S + N) matrix, so a batch
# crosses the device boundary as 3 leaves (scal, finish_s, bneck_code) —
# per-leaf transfer + pytree overhead was a measurable slice of the
# explorer's serial iteration. Fixed columns first, in SCAL_COLS order; the
# per-block bottleneck-seconds telemetry (pe_bneck_s, mem_bneck_s — S
# padded slots each — then noc_bneck_s over the N padded chain positions)
# rides in the variable-width tail, split on host via the batch's recorded
# (S, N) dims and never named here.

# the named host-unpack scalars
SCAL_PREFIX = (
    "latency_s", "energy_j", "power_w", "area_mm2", "fitness",
    "alp_time_s", "traffic_bytes", "n_phases", "all_done",
)
# comp-vs-comm attribution split, unpacked as one ``bneck_kind_s`` (B, 3)
# column block
BNECK_KIND_COLS = ("kind_pe_s", "kind_mem_s", "kind_noc_s")
# argmax slots of the per-block bottleneck-seconds telemetry — the block a
# bottleneck-relaxation policy should target next, computed on device
TOP_BNECK_COLS = ("top_bneck_pe", "top_bneck_mem")
SCAL_COLS = SCAL_PREFIX + BNECK_KIND_COLS + TOP_BNECK_COLS
N_SCAL = len(SCAL_COLS)
# host-unpack indices, derived from the tuples above
KIND_START = len(SCAL_PREFIX)
KIND_STOP = KIND_START + len(BNECK_KIND_COLS)
TOP_PE_COL = KIND_STOP
TOP_MEM_COL = TOP_PE_COL + 1


class _JaxBatch:
    """Shared state of one dispatch: device outputs + one memoized host pull.

    The dispatch is non-blocking — nothing transfers until a handle asks.
    The first consumer (any handle's ``fitness``) triggers exactly ONE
    stacked ``device_get`` of the packed output dict: one host↔device sync
    per batch, total. Padded-bucket batches are a few tens of KB, so the
    stacked transfer costs less than a single per-column ``np.asarray``
    used to (each of those paid jit-slicing overhead plus its own sync);
    per-task *dicts* are still only materialized by ``result()``, per
    accessed handle. ``consumed`` flips on the pull — the backend uses it
    to retire the batch from its in-flight pipeline accounting (a completed
    transfer implies the dispatch finished computing)."""

    __slots__ = ("out", "stats", "eds", "dims", "_host", "consumed")

    def __init__(self, out, stats: BackendStats, eds, dims) -> None:
        self.out = out
        self.stats = stats
        self.eds = eds  # per-row EncodedDesign (for adopt_encoding)
        self.dims = dims  # (padded slot count S, padded NoC count N)
        self._host: Optional[Dict[str, np.ndarray]] = None
        self.consumed = False

    def host(self) -> Dict[str, np.ndarray]:
        """The whole batch output on host: one stacked device_get, unpacked
        into the standard output keys as zero-copy column views."""
        if self._host is None:
            import jax

            with span("backend.fetch_wait", stats=self.stats, field="fetch_wait_s"):
                raw = jax.device_get(self.out)
            self.consumed = True
            with span("backend.decode", stats=self.stats, field="decode_s"):
                self._host = self._unpack(raw)
        return self._host

    def _unpack(self, raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """The fetched outputs as the standard output keys (zero-copy
        column views), with the batch's non-finite rows counted."""
        scal = raw["scal"]
        host = {name: scal[:, i] for i, name in enumerate(SCAL_PREFIX)}
        host["bneck_kind_s"] = scal[:, KIND_START:KIND_STOP]
        host["top_bneck_pe"] = scal[:, TOP_PE_COL]
        host["top_bneck_mem"] = scal[:, TOP_MEM_COL]
        s_busy, n_noc = self.dims
        f = N_SCAL
        host["pe_bneck_s"] = scal[:, f:f + s_busy]
        host["mem_bneck_s"] = scal[:, f + s_busy:f + 2 * s_busy]
        host["noc_bneck_s"] = scal[:, f + 2 * s_busy:f + 2 * s_busy + n_noc]
        host["finish_s"] = raw["finish_s"]
        host["bneck_code"] = raw["bneck_code"]
        # non-finite guard accounting: a NaN/Inf fitness row is the
        # device-side symptom the serve layer must never accept (real
        # rows only — the pow2 pad rows replicate row 0)
        fit = host["fitness"][: len(self.eds)]
        bad = int(np.size(fit) - np.count_nonzero(np.isfinite(fit)))
        if bad:
            self.stats.n_nonfinite_rows += bad
        return host

    def fitness(self) -> np.ndarray:
        return self.host()["fitness"]


class _CachedBatch:
    """Duck-typed one-row ``_JaxBatch`` over a memoized store row.

    A `serve.DesignStore` hit serves a candidate from the host columns of an
    earlier identical evaluation. Wrapping that row (leading axis 1) behind
    the ``host()/fitness()`` batch interface lets the ordinary ``_JaxHandle``
    machinery — fitness, scalars, telemetry, lazy decode, ``adopt_encoding``
    — read it through the exact same code path as a fresh dispatch, so a
    cache hit is bit-identical to the dispatch it memoized. ``eds`` carries
    the *consumer's* encoding (computed anyway to derive the cache key): the
    producer's encoding may map different block names onto the same arrays,
    and adoption must stay keyed to the consumer's own design."""

    __slots__ = ("_row", "stats", "eds", "dims", "consumed")

    def __init__(self, row: Dict[str, np.ndarray], stats: BackendStats, ed) -> None:
        self._row = row
        self.stats = stats
        self.eds = [ed]
        self.dims = None  # host() is pre-unpacked; dims only split raw scal
        self.consumed = True  # nothing in flight: the row is already host-side

    def host(self) -> Dict[str, np.ndarray]:
        return self._row

    def fitness(self) -> np.ndarray:
        return self._row["fitness"]


class _JaxHandle:
    """Lazy handle into one row of a `_JaxBatch`."""

    __slots__ = ("_batch", "_j", "_cand", "_backend", "_res", "_ed")

    def __init__(
        self, batch: _JaxBatch, j: int, cand: Candidate, backend, ed=None
    ) -> None:
        self._batch = batch
        self._j = j
        self._cand = cand
        self._backend = backend
        self._res: Optional[SimResult] = None
        # adoption override: a row shared across candidates (same-dispatch
        # cache alias) carries THIS consumer's encoding here — the row
        # owner's `eds[j]` may map different block names to the same arrays
        self._ed = ed

    @property
    def fitness(self) -> float:
        return float(self._batch.fitness()[self._j])

    def scalars(self) -> Dict[str, float]:
        s = self._batch.host()
        return {k: float(s[k][self._j]) for k in ("latency_s", "power_w", "area_mm2")}

    def result(self) -> SimResult:
        if self._res is None:
            self._batch.host()  # a first fetch counts as fetch_wait_s
            with span("backend.decode", stats=self._batch.stats, field="decode_s"):
                with self._cand.materialized(self._backend.tdg) as design:
                    self._res = self._decode_against(design)
        return self._res

    def result_for(self, design: Design) -> SimResult:
        """Decode against a caller-provided materialized design (e.g. the
        explorer's best-design snapshot, long after the candidate's base
        moved on). Bypasses — and does not populate — the memoized
        ``result()``."""
        self._batch.host()
        with span("backend.decode", stats=self._batch.stats, field="decode_s"):
            return self._decode_against(design)

    def _decode_against(self, design: Design) -> SimResult:
        out, j = self._batch.host(), self._j
        return self._backend._decode(
            design,
            float(out["latency_s"][j]),
            out["finish_s"][j],
            out["bneck_code"][j],
            out["bneck_kind_s"][j],
            out["pe_bneck_s"][j],
            out["mem_bneck_s"][j],
            out["noc_bneck_s"][j],
            float(out["alp_time_s"][j]),
            float(out["traffic_bytes"][j]),
            int(out["n_phases"][j]),
        )

    def telemetry(self) -> SimTelemetry:
        self._batch.host()
        with span("backend.decode", stats=self._batch.stats, field="decode_s"):
            return SimTelemetry.of_row(self._batch, self._j, self._cand, self._backend)


class JaxBatchedBackend:
    """One batched dispatch per batch of candidates (multi-NoC included).

    Latency/finish times and the Eq.-7 fitness come from the vectorized
    phase+scoring kernel; the rest of ``SimResult`` is reconstructed exactly
    on the host, lazily: PPA rollups are O(blocks) closed forms, and per-task
    dynamic energy depends only on total drained work (every task runs to
    completion) and its route hop count, not on phase rates. Chain
    topologies are encoded natively up to ``phase_sim_jax.MAX_NOC`` NoCs —
    topology moves (NoC fork/join) price on device like any other move;
    only shapes the encoding cannot host (``UnsupportedDesignError``) fall
    back to the Python simulator per design, inside the same
    ``evaluate_candidates`` call.

    The device prices every batch through `phase_sim_jax.simulate_batch`,
    the `vmap`-of-`fori_loop` XLA path.

    Dispatch is asynchronous and multi-dispatch-capable:
    ``evaluate_candidates`` returns after submission, host batch buffers are
    double-buffered per shape bucket (on CPU, XLA may alias the numpy input
    rather than copy — the *next* encode must not scribble over a buffer an
    in-flight dispatch is still reading), and ``flush()`` drains whatever is
    outstanding. For the device-resident explorer, :meth:`run_chains`
    prices a whole fused (R, K) chain block per dispatch
    (`repro.core.device_explore`)."""

    name = "jax"
    async_dispatch = True  # dispatch returns before the device scores it

    def __init__(self, tdg: TaskGraph, db: HardwareDatabase) -> None:
        from .phase_sim_jax import EncodedWorkload

        self.tdg = tdg
        self.db = db
        self._enc = EncodedWorkload.of(tdg)
        self._jit = None  # single kernel: shapes vary only via padded buckets
        # shape bucket -> two alternating host rows buffers (double-buffered
        # so a fresh encode never mutates what the device may still read)
        self._buffers: Dict[tuple, List[Optional[Dict[str, np.ndarray]]]] = {}
        self._bufsel: Dict[tuple, int] = {}
        # (bucket, buffer-slot) -> (base_ed, budget, dirty cells) enabling the
        # steady-state restore-only refill (see _evaluate_batch)
        self._buf_state: Dict[tuple, tuple] = {}
        # (bucket, buffer-slot) -> the _JaxBatch that last read the slot
        # (reuse guard against >2-deep callers overwriting aliased inputs)
        self._buf_owner: Dict[tuple, _JaxBatch] = {}
        self._inflight: List[_JaxBatch] = []
        # content-addressed evaluation cache (serve.DesignStore) — opt-in
        # via attach_store(); None keeps the historic uncached behaviour
        self._store = None
        self._wl_digest: Optional[bytes] = None
        # id(design) -> (design, EncodedDesign) adopted via adopt_encoding;
        # the design ref doubles as an identity guard against id() reuse
        self._adopted: Dict[int, tuple] = {}
        self._shapes: set = set()
        self._stats = BackendStats()
        # device-resident chain runner (device_explore) — built lazily so
        # host-loop users never pay for it; shares the workload encoding
        self._chains = None
        # static per-task tables for host-side SimResult reconstruction:
        # totals are design-independent; only the block subtype scales energy
        names = self._enc.names
        self._ops = [float(tdg.tasks[n].work_ops) for n in names]
        self._rw = [float(tdg.tasks[n].read_bytes + tdg.tasks[n].write_bytes) for n in names]
        self._wbytes = [float(tdg.tasks[n].write_bytes) for n in names]
        self._wl_of = [workload_of(n) if "." in n else tdg.name for n in names]
        e = db.energy
        self._pe_pj = {"acc": e.acc_pj_per_op, "gpp": e.gpp_pj_per_op}
        self._mem_pj = {"sram": e.sram_pj_per_byte, "dram": e.dram_pj_per_byte}
        self._noc_pj = e.noc_pj_per_byte_hop

    def supports(self, design: Design) -> bool:
        from .phase_sim_jax import MAX_NOC

        return 1 <= len(design.noc_chain) <= MAX_NOC

    def stats(self) -> BackendStats:
        return self._stats

    def attach_store(self, store) -> None:
        """Attach a content-addressed evaluation cache (`serve.DesignStore`).
        Every subsequent vectorizable candidate is keyed on
        ``hash(EncodedDesign leaves, workload, budget)``: key hits are served
        from the memoized row of an earlier identical evaluation (no device
        row dispatched — bit-identical scalars, see ``_CachedBatch``),
        duplicate keys *within* one batch alias a single dispatched row, and
        every freshly dispatched row is registered for future sessions. The
        store may be shared across backends/workloads (the workload digest
        namespaces the keys)."""
        self._store = store
        self._wl_digest = store.workload_digest(self._enc) if store is not None else None

    def _note_bypass(self) -> None:
        """A candidate the cache cannot host (scalar fallback — no device
        row to memoize). Counted only while a store is attached."""
        if self._store is not None:
            self._stats.n_cache_bypass += 1
            self._store.note_bypass()

    def flush(self) -> None:
        """Drain the dispatch pipeline: block until every outstanding batch
        has been scored (e.g. batches a finished session never consumed)."""
        import jax

        for batch in self._inflight:
            if not batch.consumed:
                jax.block_until_ready(batch.out["scal"])
                batch.consumed = True
        self._inflight.clear()

    def chain_runner(self):
        """The lazily-built :class:`~repro.core.device_explore.
        DeviceChainRunner` this backend prices chain blocks with. Shares the
        workload encoding; owns its own jit cache and
        compile/fallback counters (the bench smoke guard asserts on them)."""
        if self._chains is None:
            from .device_explore import DeviceChainRunner

            self._chains = DeviceChainRunner(self.tdg, self.db, self._enc)
        return self._chains

    def run_chains(self, req):
        """Price one fused (R, K) exploration block
        (:class:`~repro.core.device_explore.ChainRequest` in,
        :class:`~repro.core.device_explore.ChainBlockResult` out) — the
        device-resident counterpart of ``evaluate_candidates``: one dispatch
        runs K accept/reject iterations for R chains. Counted in the backend
        stats as R·K simulated designs in one dispatch."""
        runner = self.chain_runner()
        with span("backend.run_chains", stats=self._stats, field="wall_s"):
            res = runner.run_chains(
                req.design, req.budget, r=req.r, k=req.k, seed=req.seed,
                it0=req.it0, menu=req.menu, alpha=req.alpha,
                temperature0=req.temperature0, temp_decay=req.temp_decay,
                taboo_ttl=req.taboo_ttl, carry=req.carry, alloc=req.alloc,
                cap_pe=req.cap_pe, cap_mem=req.cap_mem,
            )
        self._stats.n_sims += req.r * req.k
        self._stats.n_batched += req.r * req.k
        self._stats.n_dispatches += 1
        return res

    def adopt_encoding(self, handle: SimHandle) -> None:
        """Promote ``handle``'s row encoding to be its base design's cached
        encoding for future dispatches. The explorer calls this right after
        accepting a move (`Candidate.accept` has just mutated the base to
        exactly the state the row's delta-encoding describes —
        ``apply_delta`` is bit-identical to a from-scratch encode), so the
        per-dispatch ``EncodedDesign.of`` walk disappears from the steady
        state: rejected iterations reuse the adopted base, accepted ones
        adopt the winner. Only the caller may mutate the design afterwards,
        and only through another accept+adopt.

        A winner priced through the Python FALLBACK (e.g. a topology move)
        has no row encoding — accepting it still mutates the base, so the
        call must *invalidate* any previously adopted encoding for that
        design instead of silently keeping a stale one (that exact staleness
        produced phantom missing-block KeyErrors in multi-hundred-iteration
        campaigns before the invalidation existed)."""
        cand = getattr(handle, "_cand", None)
        if cand is None:
            return  # foreign handle: no candidate, nothing to (in)validate
        if not isinstance(handle, _JaxHandle) or handle._batch.eds is None:
            self._adopted.pop(id(cand.base), None)
            return
        if len(self._adopted) > 512:  # bound design refs kept alive
            self._adopted.clear()
        ed = handle._ed if handle._ed is not None else handle._batch.eds[handle._j]
        self._adopted[id(cand.base)] = (cand.base, ed)

    def _track_inflight(self, batch: _JaxBatch) -> None:
        # in-flight = dispatched, not yet consumed by the host (the list
        # flush() drains); readiness does not retire a batch while the list
        # stays short. Abandoned
        # batches (a failed session's) are never consumed; to bound the
        # list WITHOUT voiding the flush() drain guarantee, overflow first
        # sheds batches whose compute already finished (nothing left to
        # drain) and only then applies backpressure (blocks) on the oldest
        # stragglers.
        alive = [b for b in self._inflight if not b.consumed]
        if len(alive) > 7:
            import jax

            still = []
            for b in alive:
                ready = getattr(b.out["scal"], "is_ready", None)
                if ready is not None and ready():
                    continue  # finished: safe to untrack, flush owes it nothing
                still.append(b)
            for b in still[:-7]:
                jax.block_until_ready(b.out["scal"])
            alive = still[-7:]
        self._inflight = alive
        self._inflight.append(batch)

    def _fn(self):
        if self._jit is None:
            import jax
            import jax.numpy as jnp

            from .phase_sim_jax import simulate_batch

            def packed(rows):
                # pack the per-design scalars into one (B, 14 + 2·S) matrix
                # on device (SCAL_PREFIX + bneck_kind_s + top-bottleneck slot
                # pair + the per-slot bottleneck telemetry): 3 output leaves
                # per dispatch (wl_latency_s is dropped — the lazy decode
                # recomputes per-workload latency from finish times on
                # host). Free under jit: XLA fuses the stack.
                out = simulate_batch(self._enc, rows)
                scal = jnp.stack(
                    [
                        out[k] if out[k].dtype == jnp.float32
                        else out[k].astype(jnp.float32)
                        for k in SCAL_PREFIX
                    ],
                    axis=1,
                )
                tops = jnp.stack(
                    [
                        out["top_bneck_pe"].astype(jnp.float32),
                        out["top_bneck_mem"].astype(jnp.float32),
                    ],
                    axis=1,
                )
                scal = jnp.concatenate(
                    [scal, out["bneck_kind_s"], tops,
                     out["pe_bneck_s"], out["mem_bneck_s"],
                     out["noc_bneck_s"]],
                    axis=1,
                )
                return {
                    "scal": scal,
                    "finish_s": out["finish_s"],
                    "bneck_code": out["bneck_code"],
                }

            self._jit = jax.jit(packed)
        return self._jit

    # ------------------------------------------------------------------
    def evaluate(self, designs: Sequence[Design]) -> List[SimResult]:
        """Eager compatibility path: price + decode everything."""
        handles = self.evaluate_candidates([Candidate.of_design(d) for d in designs])
        return [h.result() for h in handles]

    def evaluate_candidates(self, cands: Sequence[Candidate]) -> List[SimHandle]:
        with span("backend.candidates", stats=self._stats, field="wall_s"):
            results: List[Optional[SimHandle]] = [None] * len(cands)
            fast = [i for i, c in enumerate(cands) if c.vectorizable()]
            fast_set = set(fast)
            for i, c in enumerate(cands):
                if i not in fast_set:
                    with c.materialized(self.tdg) as d:
                        res = simulate(d, self.tdg, self.db)
                    results[i] = _ReadyHandle(res, _host_fitness(res, c), c, self.tdg)
                    self._stats.n_fallback += 1
                    self._note_bypass()
            if fast:
                self._evaluate_batch([cands[i] for i in fast], fast, results)
        self._stats.n_sims += len(cands)
        self._stats.n_dispatches += 1
        return results  # type: ignore[return-value]

    def _evaluate_batch(
        self, batch: List[Candidate], idx: List[int], results: List[Optional[SimHandle]]
    ) -> None:
        from .phase_sim_jax import (
            ENCODED_FIELDS, EncodedDesign, UnsupportedDesignError, alloc_rows,
            apply_delta, fill_budget, fill_row, fill_row_fields,
        )

        with span("backend.encode", stats=self._stats, field="encode_s"):
            # incremental encoding: each distinct base design is encoded once per
            # dispatch (candidates of one explorer iteration share their base),
            # then every candidate is the base row plus its recorded move delta.
            # apply_delta is copy-on-write, so `ed.f is base.f` marks untouched
            # fields — the buffer fill below broadcasts the base row per group
            # and rewrites only what each move changed.
            base_encs: Dict[int, EncodedDesign] = {}
            eds: List[EncodedDesign] = []
            keep: List[int] = []
            # content-addressed cache bookkeeping (store attached): per-row cache
            # keys to register after dispatch, same-dispatch alias rows, and the
            # batch-local key → row map that dedupes identical candidates two
            # co-batched sessions submit in one scheduler tick
            store = self._store
            row_keys: List[bytes] = []
            aliases: List[tuple] = []  # (results index, dispatched row, Candidate, ed)
            batch_rows: Dict[bytes, int] = {}
            bud_digests: Dict[tuple, bytes] = {}
            for pos, c in enumerate(batch):
                key = id(c.base)
                try:
                    ed = base_encs.get(key)
                    if ed is None:
                        # adopted encodings first: the explorer promotes the
                        # accepted winner's delta-encoding (bit-identical to a
                        # from-scratch encode of the mutated design), so steady-
                        # state dispatches never re-walk the base design's
                        # object graph at all
                        adopted = self._adopted.get(key)
                        if adopted is not None and adopted[0] is c.base:
                            ed = adopted[1]
                        else:
                            ed = EncodedDesign.of(c.base, self.tdg, self.db, self._enc)
                        base_encs[key] = ed
                    if c.spec is not None:
                        ed = apply_delta(ed, c.delta, c.base, self.tdg, self.db, self._enc)
                except UnsupportedDesignError:
                    # the typed capability check: shapes the encoding cannot
                    # host route to the exact scalar path, mid-batch
                    with c.materialized(self.tdg) as d:
                        res = simulate(d, self.tdg, self.db)
                    results[idx[pos]] = _ReadyHandle(
                        res, _host_fitness(res, c), c, self.tdg
                    )
                    self._stats.n_fallback += 1
                    self._note_bypass()
                    continue
                if store is not None:
                    bkey = (id(c.budget), c.alpha)
                    bud_dig = bud_digests.get(bkey)
                    if bud_dig is None:
                        bud_dig = bud_digests[bkey] = store.budget_digest(
                            c.budget, c.alpha
                        )
                    ckey = store.key_of(ed, self._wl_digest, bud_dig)
                    row = store.lookup(ckey)
                    if row is not None:
                        # store hit: serve from the memoized row of an earlier
                        # identical evaluation — no device row dispatched. The
                        # consumer's own encoding rides along for adoption.
                        results[idx[pos]] = _JaxHandle(
                            _CachedBatch(row, self._stats, ed), 0, c, self
                        )
                        self._stats.n_cache_hits += 1
                        continue
                    dup = batch_rows.get(ckey)
                    if dup is not None:
                        # same-dispatch alias: an identical candidate is already
                        # in this batch — share its row instead of paying one
                        # (the consumer's own ed rides along for adoption)
                        aliases.append((idx[pos], dup, c, ed))
                        self._stats.n_cache_hits += 1
                        store.note_alias_hit()
                        continue
                    batch_rows[ckey] = len(eds)
                    row_keys.append(ckey)
                keep.append(pos)
                eds.append(ed)
            if len(keep) != len(batch):
                batch = [batch[p] for p in keep]
                idx = [idx[p] for p in keep]
                if not batch:
                    return

            # pad slots and batch to power-of-two buckets: the jit cache then sees
            # a handful of shapes over a whole exploration instead of one per
            # block-count the moves walk through. Slot counts are bounded by the
            # task count (moves allocate at most ~one block per task), so pinning
            # the shared PE/MEM slot bucket at pow2(T) collapses that shape axis
            # to one entry per workload; only the batch axis still varies. The
            # NoC-chain axis buckets to pow2 WITHOUT a floor: the dominant
            # single-NoC regime stays at N = 1 (compiling to exactly the
            # historic kernel), and topology-heavy searches add at most
            # log2(MAX_NOC) shapes.
            # bucket over the candidate encodings AND their bases: the group
            # fill broadcasts each base row before applying diffs, so a batch of
            # all-join candidates (one slot/NoC fewer than base) must still
            # host the base's shape
            all_encs = list(base_encs.values())
            all_encs.extend(eds)
            need = max(max(e.pe_peak.shape[0], e.mem_bw.shape[0]) for e in all_encs)
            slots = _bucket(max(need, len(self._enc.names)))
            n_noc = max(1, _pow2(max(e.noc_bw.shape[0] for e in all_encs)))
            b = len(batch)
            b_pad = _bucket(b)
            key = (b_pad, slots, n_noc)
            # double-buffered per bucket: the previous dispatch of this shape may
            # still be reading its (possibly zero-copy-aliased) host buffer, so a
            # fresh encode flips to the other one. Two in-flight batches per
            # bucket suffice; anything deeper would flush first.
            pair = self._buffers.get(key)
            if pair is None:
                pair = self._buffers[key] = [None, None]
            sel = self._bufsel.get(key, 0)
            self._bufsel[key] = 1 - sel
            rows = pair[sel]
            if rows is None:
                rows = pair[sel] = alloc_rows(
                    b_pad, len(self._enc.names), slots, slots,
                    len(self._enc.wl_names), n_noc,
                )
            # reuse guard: two buffers cover two un-consumed dispatches per
            # bucket, but the protocol lets callers keep MORE un-consumed. If
            # the dispatch that last encoded into this slot might still be
            # reading it (CPU XLA may alias the numpy buffer zero-copy), wait
            # for its compute to finish before scribbling over its inputs.
            owner = self._buf_owner.get((key, sel))
            if owner is not None and not owner.consumed:
                ready = getattr(owner.out["scal"], "is_ready", None)
                if ready is None or not ready():
                    import jax

                    jax.block_until_ready(owner.out["scal"])

            # steady-state fast path (the explorer regime: one adopted base, one
            # budget, full bucket): the buffer already holds base-row content
            # everywhere except the cells last dispatch's diffs touched — restore
            # just those from the base instead of refilling every row
            bufkey = (key, sel)
            prev = self._buf_state.get(bufkey)
            c0 = batch[0]
            uniform = all(
                c.budget is c0.budget and c.alpha == c0.alpha for c in batch[1:]
            )
            state0 = len(base_encs) == 1 and b == b_pad and uniform
            fast = (
                state0 and prev is not None
                and prev[0] is base_encs[id(c0.base)]
                and prev[1] is c0.budget
                and prev[2] == c0.alpha
            )
            dirty: List[tuple] = []
            if fast:
                base_ed = prev[0]
                for k, f in prev[3]:
                    fill_row_fields(rows, k, base_ed, (f,))
                for k in range(b):
                    ed = eds[k]
                    if ed is not base_ed:
                        changed = [
                            f for f in ENCODED_FIELDS
                            if getattr(ed, f) is not getattr(base_ed, f)
                        ]
                        fill_row_fields(rows, k, ed, changed)
                        dirty.extend((k, f) for f in changed)
                self._buf_state[bufkey] = (base_ed, c0.budget, c0.alpha, dirty)
            else:
                # fill per base-group: write the base encoding + budget once,
                # broadcast across the group's rows, then apply per-candidate diffs
                j = 0
                while j < b:
                    cg = batch[j]
                    base_ed = base_encs[id(cg.base)]
                    end = j + 1
                    while end < b and batch[end].base is cg.base:
                        end += 1
                    fill_row(rows, j, base_ed)
                    bud = cg.budget
                    if bud is not None:
                        fill_budget(rows, j, self._enc, bud.latency_s, bud.power_w,
                                    bud.area_mm2, cg.alpha)
                    else:  # neutral scoring row (buffers are reused across dispatches)
                        fill_budget(rows, j, self._enc, {}, 1e30, 1e30, 0.0)
                    if end - j > 1:
                        for arr in rows.values():
                            arr[j + 1:end] = arr[j]
                    for k in range(j, end):
                        ed, c = eds[k], batch[k]
                        if ed is not base_ed:
                            changed = [
                                f for f in ENCODED_FIELDS
                                if getattr(ed, f) is not getattr(base_ed, f)
                            ]
                            fill_row_fields(rows, k, ed, changed)
                            dirty.extend((k, f) for f in changed)
                        if k > j and c.budget is not bud:
                            if c.budget is not None:
                                fill_budget(rows, k, self._enc, c.budget.latency_s,
                                            c.budget.power_w, c.budget.area_mm2, c.alpha)
                            else:
                                fill_budget(rows, k, self._enc, {}, 1e30, 1e30, 0.0)
                    j = end
                if b < b_pad:  # pad the batch axis with copies of row 0
                    for arr in rows.values():
                        arr[b:b_pad] = arr[0]
                # the invariant the fast path needs: every row holds base+budget
                # content except `dirty` — only true for single-group, uniform-
                # budget, full-bucket dispatches
                if state0:
                    self._buf_state[bufkey] = (
                        base_encs[id(c0.base)], c0.budget, c0.alpha, dirty
                    )
                else:
                    self._buf_state.pop(bufkey, None)
            if key not in self._shapes:
                self._shapes.add(key)
                self._stats.n_compiles += 1

        with span("backend.dispatch", stats=self._stats, field="dispatch_s"):
            out = self._fn()(rows)  # non-blocking: no host transfer here
        shared = _JaxBatch(out, self._stats, eds, (slots, n_noc))
        self._buf_owner[(key, sel)] = shared
        self._track_inflight(shared)
        for j, i in enumerate(idx):
            results[i] = _JaxHandle(shared, j, batch[j], self)
            self._stats.n_batched += 1
        if store is not None:
            # register every dispatched row for future sessions (lazy: the
            # entry holds (batch, row) until a hit materializes it) and wire
            # same-dispatch aliases onto the rows they dedupe against
            for j, ckey in enumerate(row_keys):
                store.insert(ckey, shared, j)
                self._stats.n_cache_misses += 1
            for i, j, c, ed in aliases:
                results[i] = _JaxHandle(shared, j, c, self, ed)

    # ------------------------------------------------------------------
    # host-exact scalar rollups, shared between the lazy ``_decode`` and the
    # policy-layer ``SimTelemetry`` so both produce bit-identical floats
    def _task_energy_pj(self, design: Design) -> Dict[str, float]:
        """Per-task dynamic energy: rate-independent (every task drains its
        full (ops, read, write) totals); the NoC term scales with the task's
        route hop count on multi-NoC chains."""
        blocks, d_pe, d_mem = design.blocks, design.task_pe, design.task_mem
        pe_pj, mem_pj, noc_pj = self._pe_pj, self._mem_pj, self._noc_pj
        if len(design.noc_chain) == 1:  # hops == 1 everywhere: skip routing
            return {
                n: pe_pj[blocks[d_pe[n]].subtype] * self._ops[k]
                + (mem_pj[blocks[d_mem[n]].subtype] + noc_pj) * self._rw[k]
                for k, n in enumerate(self._enc.names)
            }
        pos = {m: i for i, m in enumerate(design.noc_chain)}
        att = design.attached_noc
        return {
            n: pe_pj[blocks[d_pe[n]].subtype] * self._ops[k]
            + (
                mem_pj[blocks[d_mem[n]].subtype]
                + noc_pj * (abs(pos[att[d_pe[n]]] - pos[att[d_mem[n]]]) + 1)
            ) * self._rw[k]
            for k, n in enumerate(self._enc.names)
        }

    def _mem_caps(self, design: Design) -> Dict[str, float]:
        cap: Dict[str, float] = {m: 0.0 for m in design.mems()}
        d_mem = design.task_mem
        for k, n in enumerate(self._enc.names):
            cap[d_mem[n]] += self._wbytes[k]
        return cap

    def _area_mm2(self, design: Design, cap: Dict[str, float]) -> float:
        db = self.db
        area = 0.0
        for bname, blk in design.blocks.items():
            if blk.kind == BlockKind.MEM and blk.subtype == "sram":
                area += db.area.sram_mm2_per_mb * max(cap[bname], 1.0) / 1e6
            else:
                area += db.block_area_mm2(blk)
        return area

    def _wl_latency(self, fin: List[float]) -> Dict[str, float]:
        wl_latency: Dict[str, float] = {}
        for w, f in zip(self._wl_of, fin):
            if f > wl_latency.get(w, 0.0):
                wl_latency[w] = f
        return wl_latency

    def _decode(
        self,
        design: Design,
        latency: float,
        finish: np.ndarray,
        bneck: np.ndarray,
        kind_s: np.ndarray,
        pe_busy: np.ndarray,
        mem_busy: np.ndarray,
        noc_busy: np.ndarray,
        alp_time: float,
        traffic: float,
        n_phases: int,
    ) -> SimResult:
        db = self.db
        names = self._enc.names
        blocks, d_pe, d_mem = design.blocks, design.task_pe, design.task_mem
        chain = design.noc_chain
        fin = finish.tolist()
        codes = bneck.tolist()
        finish_s = dict(zip(names, fin))
        # codes are packed: 0/1 = pe/mem, 2 + 3·k = NoC at chain index k
        task_bneck = {n: _BNECK_KINDS[min(c, 2)] for n, c in zip(names, codes)}
        task_bneck_block = {
            n: d_pe[n] if c == 0 else (
                d_mem[n] if c == 1 else chain[(c - 2) // 3]
            )
            for n, c in zip(names, codes)
        }
        task_energy_pj = self._task_energy_pj(design)
        energy_j = sum(task_energy_pj.values()) * 1e-12 + total_leakage_w(
            design, db
        ) * latency
        wl_latency = self._wl_latency(fin)
        # fused mem-capacity + area rollup (ppa.mem_capacities/total_area_mm2
        # recomputed here with the precomputed write-bytes table)
        cap = self._mem_caps(design)
        area = self._area_mm2(design, cap)
        # per-block bottleneck seconds: device telemetry columns resolved to
        # block names via the encoding slot order (= block insertion order;
        # NoC columns are in chain order)
        block_bneck_s: Dict[str, float] = {}
        ipe = imem = 0
        for bname, blk in blocks.items():
            if blk.kind == BlockKind.PE:
                block_bneck_s[bname] = float(pe_busy[ipe])
                ipe += 1
            elif blk.kind == BlockKind.MEM:
                block_bneck_s[bname] = float(mem_busy[imem])
                imem += 1
        for i, bname in enumerate(chain):
            block_bneck_s[bname] = float(noc_busy[i])
        return SimResult(
            latency_s=latency,
            workload_latency_s=wl_latency,
            energy_j=energy_j,
            power_w=energy_j / latency if latency > 0 else 0.0,
            area_mm2=area,
            n_phases=n_phases,
            bottleneck_s={k: float(kind_s[i]) for i, k in enumerate(_BNECK_KINDS)},
            task_bottleneck=task_bneck,
            task_finish_s=finish_s,
            mem_capacity_bytes=cap,
            task_bottleneck_block=task_bneck_block,
            task_energy_j={n: e * 1e-12 for n, e in task_energy_pj.items()},
            block_bottleneck_s=block_bneck_s,
            avg_accel_parallelism=alp_time / latency if latency > 0 else 1.0,
            total_traffic_bytes=traffic,
        )


BACKENDS = {
    "python": PythonBackend,
    "jax": JaxBatchedBackend,
}


def make_backend(name: str, tdg: TaskGraph, db: HardwareDatabase) -> SimulatorBackend:
    """Instantiate a registered backend by name (`ExplorerConfig.backend`)."""
    try:
        cls = BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; known: {sorted(BACKENDS)}") from None
    return cls(tdg, db)
