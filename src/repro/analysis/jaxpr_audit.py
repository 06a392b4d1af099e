"""Jaxpr auditor: trace the hot jitted entry points with abstract inputs
and assert what the lint pass can only infer lexically.

Where ``lint.py`` reads source, this pass reads the *traced program*: it
builds each entry point's jaxpr (no device execution — ``jax.make_jaxpr``
with the same abstract shapes production dispatches) and walks every
equation, recursing through sub-jaxprs (``pjit``, ``scan`` bodies,
``cond`` branches), to check:

  * **no forbidden primitives** — callbacks (``pure_callback`` /
    ``io_callback`` / debug callbacks) and host transfers
    (``infeed``/``outfeed``/``outside_call``) would turn the fused block
    into a per-step host round-trip while still "working";
  * the Pallas wrapper really lowers through ``pallas_call`` (a silent
    fallback to the vmap reference would pass every numeric test at 10×
    the dispatch cost);
  * the **jit-cache key bound**: the backend buckets batch/slot shapes to
    pow2 (floor 4) exactly so the compile-cache key set stays small. The
    audit enumerates the documented production grid (batch and slots up
    to 64, NoC counts up to 8) through the real ``_bucket`` and fails if
    the distinct-key count exceeds :data:`BUCKET_GRID_BOUND` — someone
    widening the bucket function pays for every extra compile here, not
    in a prod flamegraph.

Entry points audited: ``phase_sim_jax.simulate_batch`` (the vmap'd
scoring core), the fused chain block (``DeviceChainRunner._build_block``
on the alloc menu — scan over K steps), and the Pallas wrapper
``ops.phase_sim`` (``interpret=True`` so the audit runs on CPU-only
hosts; the jaxpr is the same either way).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set

from .findings import Finding

__all__ = [
    "FORBIDDEN_SUBSTRINGS",
    "BUCKET_GRID_BOUND",
    "collect_primitives",
    "audit_jaxpr",
    "run_jaxpr_audit",
]

# primitive-name substrings that mean "this traced program talks to the
# host per call"
FORBIDDEN_SUBSTRINGS = (
    "callback", "infeed", "outfeed", "outside_call", "host_local",
)

# distinct (batch-bucket, slot-bucket, noc) jit keys allowed for the
# standard production grid: batch 1..64, slots 1..64, noc ∈ {1, 2, 4, 8}.
# _bucket's pow2-floor-4 gives 5 batch × 5 slot × 4 noc = 100 exactly;
# the bound leaves zero headroom on purpose — widening the bucket set is
# a deliberate decision that must touch docs/ANALYSIS.md too.
BUCKET_GRID_BOUND = 100


def _sub_jaxprs(params: Dict) -> List:
    """Sub-jaxprs hiding in an equation's params (pjit/scan `jaxpr`,
    cond `branches` tuples, closed-call bodies)."""
    out = []
    for v in params.values():
        for cand in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(cand, "jaxpr") or hasattr(cand, "eqns"):
                out.append(cand)
    return out


def collect_primitives(jaxpr) -> Set[str]:
    """Every primitive name reachable from a (Closed)Jaxpr, recursively."""
    core = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    prims: Set[str] = set()
    for eqn in core.eqns:
        prims.add(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn.params):
            prims.update(collect_primitives(sub))
    return prims


def audit_jaxpr(
    name: str,
    jaxpr,
    path: str,
    *,
    require: Sequence[str] = (),
    forbidden: Sequence[str] = FORBIDDEN_SUBSTRINGS,
) -> List[Finding]:
    """Findings for one traced entry point: forbidden primitives present,
    or required ones (``pallas_call``) missing."""
    prims = collect_primitives(jaxpr)
    out: List[Finding] = []
    for p in sorted(prims):
        for bad in forbidden:
            if bad in p:
                out.append(Finding(
                    pass_name="jaxpr", rule="forbidden-primitive",
                    message=f"`{name}` lowers through `{p}` — a per-call "
                    "host round-trip inside the hot path",
                    path=path,
                ))
                break
    for want in require:
        if want not in prims:
            out.append(Finding(
                pass_name="jaxpr", rule="missing-primitive",
                message=f"`{name}` no longer lowers through `{want}` "
                "(primitives seen: "
                f"{', '.join(sorted(prims)[:12])}…) — the kernel path "
                "silently fell back",
                path=path,
            ))
    return out


def _abstract_rows(enc, ed, budget, alpha: float, b: int):
    """A (b,)-batched abstract rows dict shaped exactly like production
    dispatch (reuses the runner's host staging, then broadcasts)."""
    import jax
    import jax.numpy as jnp

    from repro.core.phase_sim_jax import (
        alloc_rows, fill_budget, fill_row,
    )

    t = len(enc.names)
    rows = alloc_rows(
        b, t, int(ed.pe_peak.shape[0]), int(ed.mem_bw.shape[0]),
        len(enc.wl_names), int(ed.noc_bw.shape[0]),
    )
    for j in range(b):
        fill_row(rows, j, ed)
        fill_budget(
            rows, j, enc, budget.latency_s, budget.power_w,
            budget.area_mm2, alpha,
        )
    return {k: jnp.asarray(v) for k, v in rows.items()}


def _fixture():
    from repro.core import (
        DeviceChainRunner, HardwareDatabase, audio, calibrated_budget,
        random_single_noc_designs,
    )
    from repro.core.phase_sim_jax import EncodedDesign

    db = HardwareDatabase()
    g = audio()
    bud = calibrated_budget(db)
    d = random_single_noc_designs(g, 1, seed=7)[0]
    runner = DeviceChainRunner(g, db)
    ed = EncodedDesign.of(d, g, db, runner.enc)
    return runner, d, ed, bud


def _audit_simulate_batch(runner, ed, bud) -> List[Finding]:
    import jax

    from repro.core.phase_sim_jax import simulate_batch

    rows = _abstract_rows(runner.enc, ed, bud, 0.05, b=4)
    jx = jax.make_jaxpr(lambda r: simulate_batch(runner.enc, r))(rows)
    return audit_jaxpr(
        "simulate_batch", jx, "src/repro/core/phase_sim_jax.py"
    )


def _audit_chain_block(runner, d, ed, bud) -> List[Finding]:
    import jax
    import jax.numpy as jnp

    from repro.core.device_explore import MoveTable

    cap_pe = int(ed.pe_peak.shape[0]) + 3
    cap_mem = int(ed.mem_bw.shape[0]) + 2
    table = MoveTable.of(
        ed, runner.enc, alloc=True, cap_pe=cap_pe, cap_mem=cap_mem
    )
    carry = runner.fresh_carry(
        d, ed, r=2, seed=0, cap_pe=cap_pe, cap_mem=cap_mem, alloc=True
    )
    row0 = runner._row0(ed, bud, 0.05)
    fn = runner._build_block(
        2, 3, "farsi", 0.05, 0.997, 5, cap_pe, cap_mem,
        int(ed.noc_bw.shape[0]), True,
    )
    jx = jax.make_jaxpr(fn)(
        carry, jnp.int32(0), row0, table.kind, table.task, table.dest
    )
    return audit_jaxpr(
        "DeviceChainRunner._build_block(menu='farsi', alloc)", jx,
        "src/repro/core/device_explore.py",
    )


def _audit_pallas_wrapper(runner, ed, bud) -> List[Finding]:
    import jax

    from repro.kernels.phase_sim.ops import phase_sim

    rows = _abstract_rows(runner.enc, ed, bud, 0.05, b=4)
    jx = jax.make_jaxpr(lambda r: phase_sim(runner.enc, r, interpret=True))(
        rows
    )
    return audit_jaxpr(
        "ops.phase_sim", jx, "src/repro/kernels/phase_sim/ops.py",
        require=("pallas_call",),
    )


def _audit_bucket_grid() -> List[Finding]:
    from repro.core.backend import _bucket

    keys = {
        (_bucket(b), _bucket(s), n)
        for b in range(1, 65)
        for s in range(1, 65)
        for n in (1, 2, 4, 8)
    }
    if len(keys) > BUCKET_GRID_BOUND:
        return [Finding(
            pass_name="jaxpr", rule="jit-cache-bound",
            message=f"the standard bucket grid yields {len(keys)} distinct "
            f"jit keys (> documented bound {BUCKET_GRID_BOUND}) — every "
            "extra key is a full XLA compile at serve time; see "
            "docs/ANALYSIS.md before widening `_bucket`",
            path="src/repro/core/backend.py",
            related=("docs/ANALYSIS.md",),
        )]
    return []


def run_jaxpr_audit(entries: Optional[Sequence[str]] = None) -> List[Finding]:
    """Trace and audit all entry points (or a named subset of
    ``{"simulate_batch", "chain_block", "pallas", "buckets"}``)."""
    want = set(entries) if entries is not None else None
    out: List[Finding] = []

    def on(name: str) -> bool:
        return want is None or name in want

    if on("buckets"):
        out.extend(_audit_bucket_grid())
    if on("simulate_batch") or on("chain_block") or on("pallas") \
            or want is None:
        runner, d, ed, bud = _fixture()
        if on("simulate_batch"):
            out.extend(_audit_simulate_batch(runner, ed, bud))
        if on("chain_block"):
            out.extend(_audit_chain_block(runner, d, ed, bud))
        if on("pallas"):
            out.extend(_audit_pallas_wrapper(runner, ed, bud))
    return out
