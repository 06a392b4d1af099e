"""Pallas kernel for the FARSI phase-driven simulator (fused batch pricing).

Grid: ``(B,)`` — one program per candidate design, each owning one ``(1, T)``
tile row of every per-design input and running the full phase loop for its
candidate. The per-phase work is the same co-residency formulation as the
XLA reference (`phase_sim_jax.simulate_one`): same-slot (T, T) matvecs for
the PE/MEM shares (Eq. 1/2/4), rank-residue link striping for the NoC
(Eq. 3), Eq.-6 phase length, then the Eq.-7 fitness/energy/area rollup —
fused into ONE launch instead of a `vmap` of `fori_loop`, so every
per-phase intermediate lives on-chip for the whole candidate instead of
round-tripping through XLA's loop-carried HLO buffers.

VMEM scratch holds the loop-invariant stage: the one-hot task→slot maps
(T, S) and the same-PE / same-MEM co-residency masks (T, T), computed once
per program and re-read every phase. Working set at (T=128, S=64):
4·(T·S + T·T) ≈ 0.3 MB — far under the ~16 MB VMEM budget; T is padded to
the lane width by ``ops.phase_sim``, with padded tasks born *completed* so
they never run, never join a share, and contribute zero to every rollup.

Gathers are expressed as one-hot matmuls (``onehot_pe @ pe_coeffs``) rather
than vector-indexed loads — MXU-shaped on TPU and exact in f32 for the
0/1 masks involved. Every value is 2-D, in the forms Mosaic lowers
(docs/KERNELS.md, "Mosaic form"). Interpret mode (CPU) is bit-compatible
with Mosaic compilation up to f32 reassociation; parity ≤ 1e-5 against the
oracle is asserted in tests/test_phase_sim_kernel.py, and the Mosaic
compile in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scal output column layout (see _phase_sim_kernel rollup): the shared
# ``core.scal_layout`` tuple — backend._SCAL_COLS is its prefix, so the
# backend's device-side repack of the ops-layer dict folds to a no-op.
# The layout module is the single source of truth (dependency-free, safe
# mid-package-init); the contract checker (`python -m repro.analysis`)
# guards that both sides keep deriving from it and that the rollup write
# below stays the same width.
from ...core.scal_layout import N_SCAL, SCAL_COLS  # re-exported for ops.py

BIG = 1e30

# nocs input column layout (packed per-candidate scalars; the per-NoC chain
# arrays — bw/links/leak/area — ride as their own (1, N) tiles now that the
# chain is encoded natively)
NOCS_COLS = ("noc_pj", "power_budget", "area_budget", "alpha")
N_NOCS = len(NOCS_COLS)


def _dot(a, b):
    """``a @ b`` on 2-D f32 operands at full f32 precision: the default may
    round an operand to bf16 on TPU, and next to the 0/1 masks the other
    operand holds bytes, seconds and rates."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _dot_t(a, b):
    """``a @ b.T`` — for a ``(1, n)`` row ``a`` the row form of ``b @ a``."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _mod(x, n):
    """``x % n`` for integer-valued f32 ``x`` and integer ``n ≥ 1`` (Python
    sign convention). The +0.5 keeps the quotient at least 1/(2n) from an
    integer, so an approximate divide cannot flip its floor."""
    return x - n * jnp.floor((x + 0.5) / n)


def _first_argmax(row):
    """Index of the first maximum of a ``(1, n)`` row: ``jnp.argmax``'s tie
    rule, which Mosaic's argmax does not keep (it returns the last)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.min(jnp.where(row == jnp.max(row), idx, row.shape[1]))


def _phase_sim_kernel(
    # --- static workload tensors (shared by every program) ---------------
    work_ref,   # (1, T) f32  total ops per task
    rd_ref,     # (1, T) f32  read bytes
    wr_ref,     # (1, T) f32  write bytes
    burst_ref,  # (1, T) f32  burst bytes
    pmask_ref,  # (T, T) f32  [i, j] = 1 iff j is a parent of i
    wlhot_ref,  # (T, NW) f32 one-hot of the task's workload id
    # --- per-candidate rows (one (1, X) tile per program) ----------------
    task_pe_ref,   # (1, T) i32
    task_mem_ref,  # (1, T) i32
    accel_ref,     # (1, T) f32
    pe_peak_ref,   # (1, S) f32
    pe_pj_ref,     # (1, S) f32
    pe_leak_ref,   # (1, S) f32
    pe_area_ref,   # (1, S) f32
    pe_noc_ref,    # (1, S) i32  chain index each PE slot attaches to
    pe_active_ref,  # (1, S) f32 active-slot mask (0 ⇒ priced as absent)
    mem_bw_ref,    # (1, S) f32
    mem_pj_ref,    # (1, S) f32
    mem_leak_ref,  # (1, S) f32
    mem_af_ref,    # (1, S) f32  fixed area
    mem_amb_ref,   # (1, S) f32  area per MB
    mem_noc_ref,   # (1, S) i32  chain index each MEM slot attaches to
    mem_active_ref,  # (1, S) f32 active-slot mask
    noc_bw_ref,    # (1, N) f32  per-NoC per-link bandwidth (chain order)
    noc_links_ref,  # (1, N) i32 per-NoC channel count
    noc_leak_ref,  # (1, N) f32
    noc_area_ref,  # (1, N) f32
    noc_active_ref,  # (1, N) f32 active-slot mask
    nocs_ref,      # (1, N_NOCS) f32 packed scalars (NOCS_COLS order)
    wlbud_ref,     # (1, NW) f32 per-workload latency budget
    # --- outputs ----------------------------------------------------------
    finish_ref,  # (1, T) f32
    bneck_ref,   # (1, T) i32 packed: 0/1 = pe/mem, 2 + 3·k = NoC chain idx k
    wllat_ref,   # (1, NW) f32
    scal_ref,    # (1, N_SCAL) f32 (SCAL_COLS order)
    pe_bneck_ref,   # (1, S) f32 per-PE-slot binding-bottleneck seconds
    mem_bneck_ref,  # (1, S) f32 per-MEM-slot binding-bottleneck seconds
    noc_bneck_ref,  # (1, N) f32 per-NoC binding-bottleneck seconds
    # --- VMEM scratch (loop-invariant stage, reused across phases) -------
    ohp_ref,       # (T, S) f32 one-hot task→PE-slot
    ohm_ref,       # (T, S) f32 one-hot task→MEM-slot
    same_pe_ref,   # (T, T) f32 co-residency on the same PE slot
    same_mem_ref,  # (T, T) f32 co-residency on the same MEM slot
    *,
    t_real: int,
):
    # Mosaic lowers 2-D vectors only: per-task vectors are (1, T) rows and
    # every matvec is a row @ matrix dot (`_dot`/`_dot_t`); scalars are 0-d.
    t = work_ref.shape[1]
    s_pe = pe_peak_ref.shape[1]
    s_mem = mem_bw_ref.shape[1]  # PE/MEM slot axes pad independently
    n_noc = noc_bw_ref.shape[1]
    f32 = jnp.float32
    iota = jax.lax.broadcasted_iota

    work = work_ref[...]
    rd_b = rd_ref[...]
    wr_b = wr_ref[...]
    burst = burst_ref[...]
    pmask = pmask_ref[...]

    # ---- loop-invariant stage into VMEM scratch -------------------------
    ohp_ref[...] = (
        task_pe_ref[...].T == iota(jnp.int32, (t, s_pe), 1)
    ).astype(f32)
    ohm_ref[...] = (
        task_mem_ref[...].T == iota(jnp.int32, (t, s_mem), 1)
    ).astype(f32)
    ohp = ohp_ref[...]
    ohm = ohm_ref[...]
    same_pe_ref[...] = _dot_t(ohp, ohp)
    same_mem_ref[...] = _dot_t(ohm, ohm)

    peak_eff = _dot_t(pe_peak_ref[...], ohp) * accel_ref[...]
    mem_peak = _dot_t(mem_bw_ref[...], ohm)
    links = jnp.maximum(noc_links_ref[...].astype(f32), 1.0)  # (1, N)
    noc_bw = noc_bw_ref[...]  # (1, N)
    # chain routing: gather the chain positions through the one-hot maps
    # (positions are small ints — exact in f32), then the route mask
    pe_pos = _dot_t(pe_noc_ref[...].astype(f32), ohp)
    mem_pos = _dot_t(mem_noc_ref[...].astype(f32), ohm)
    lo = jnp.minimum(pe_pos, mem_pos)
    hi = jnp.maximum(pe_pos, mem_pos)
    hops = hi - lo + 1.0
    nidx_f = iota(jnp.int32, (n_noc, t), 0).astype(f32)
    on_route = jnp.where((nidx_f >= lo) & (nidx_f <= hi), 1.0, 0.0)  # (N, T)

    def noc_share(runf):
        """Eq. 3 per NoC: rank-residue link striping within each NoC's
        users, end-to-end bandwidth = min over the route, binding NoC =
        first argmin in chain order. ``n_noc == 1`` is the historic
        single-NoC formulation, bit-for-bit."""
        # [j, i] = 1 iff j ≤ i: row @ prefix is the inclusive prefix sum,
        # exact for the 0/1 run masks it is applied to
        prefix = (iota(jnp.int32, (t, t), 0) <= iota(jnp.int32, (t, t), 1)
                  ).astype(f32)
        if n_noc == 1:
            order = _dot(runf, prefix)
            same_link = (runf.T * runf) * jnp.where(
                _mod(order.T - order, links[:, 0:1]) == 0, 1.0, 0.0
            )
            link_t = _dot_t(burst, same_link)
            return (noc_bw[:, 0:1] * burst / jnp.maximum(link_t, 1e-30),
                    jnp.zeros((1, t), f32))
        # multi-NoC: rank-residue striping through an (8, T) link one-hot
        # (ladder max 8 channels) — O(T·8) per NoC instead of a (T, T)
        # co-residency mask; user u's link is (rank_u − 1) mod n_links
        lidx = iota(jnp.int32, (8, t), 0).astype(f32)
        best = jnp.full((1, t), BIG, f32)
        arg = jnp.zeros((1, t), f32)
        for k in range(n_noc):  # static unroll over the padded chain bucket
            use_k = on_route[k:k + 1, :] * runf
            order = _dot(use_k, prefix)
            link = jnp.where(use_k > 0, _mod(order - 1.0, links[:, k:k + 1]), -1.0)
            oh = jnp.where(link == lidx, 1.0, 0.0)  # (8, T)
            link_load = _dot_t(burst * use_k, oh)  # (1, 8) burst per link
            link_t = _dot(link_load, oh)
            bw_k = jnp.where(
                use_k > 0,
                noc_bw[:, k:k + 1] * burst / jnp.maximum(link_t, 1e-30), BIG,
            )
            better = bw_k < best
            arg = jnp.where(better, f32(k), arg)
            best = jnp.where(better, bw_k, best)
        return best, arg

    # padded tasks (index ≥ t_real) are born completed: they never run,
    # never enter a share, and their zero work/bytes vanish in every sum.
    # `completed` is a 0/1 f32 row: Mosaic cannot carry a bool vector
    # through the phase loop.
    completed0 = jnp.where(iota(jnp.int32, (1, t), 1) >= t_real, 1.0, 0.0)

    def phase(_, state):
        (rem_ops, rem_rd, rem_wr, completed, now, finish, bneck, bneck_noc,
         kind_s, pe_bt, mem_bt, noc_bt, alp_t, traffic, nph) = state
        same_pe = same_pe_ref[...]
        same_mem = same_mem_ref[...]
        # ready ⟺ zero incomplete parents (counts are exact small ints)
        pending = _dot_t(1.0 - completed, pmask)
        running = (completed < 0.5) & (pending < 0.5)
        runf = jnp.where(running, 1.0, 0.0)
        burst_run = burst * runf

        # Eq. 1/2: preemptive equal share per PE slot
        load_t = _dot_t(runf, same_pe)
        compute = peak_eff / jnp.maximum(load_t, 1.0)

        # Eq. 4: burst-proportional memory share
        mem_t = _dot_t(burst_run, same_mem)
        m_bw = mem_peak * burst / jnp.maximum(mem_t, 1e-30)

        # Eq. 3: per-NoC rank-residue link striping, min over the route
        n_bw, noc_arg = noc_share(runf)

        bw = jnp.minimum(m_bw, n_bw)
        comp_t = rem_ops / compute
        comm_t = jnp.maximum(rem_rd, rem_wr) / bw
        c_t = jnp.where(running, jnp.maximum(comp_t, comm_t), BIG)
        phi_raw = jnp.min(c_t)  # Eq. 6
        any_run = phi_raw < BIG * 0.5
        phi = jnp.where(any_run, phi_raw, 0.0)
        phi_run = jnp.where(running, phi, 0.0)

        # binding resource per running task (total work over current rates;
        # compute wins ties, then mem vs noc by the tighter pipe)
        tot_comp_t = work / compute
        tot_comm_t = jnp.maximum(rd_b, wr_b) / bw
        code = jnp.where(tot_comp_t >= tot_comm_t, 0, jnp.where(m_bw <= n_bw, 1, 2))
        kind_s = tuple(
            kind_s[c] + jnp.sum(jnp.where(code == c, phi_run, 0.0))
            for c in range(3)
        )
        # per-TASK bottleneck-time accumulators: the task→slot resolution
        # (one VMEM one-hot matvec each) is hoisted to after the loop —
        # in-loop the telemetry costs two (1, T) masked adds
        pe_bt = pe_bt + jnp.where(code == 0, phi_run, 0.0)
        mem_bt = mem_bt + jnp.where(code == 1, phi_run, 0.0)
        # per-NoC binding seconds: the binding NoC is contention-dependent
        # per phase, so multi-NoC chains accumulate in-loop (single-NoC
        # resolves from kind_s[2] after the loop)
        if n_noc > 1:
            noc_bt = noc_bt + _dot_t(
                jnp.where(code == 2, phi_run, 0.0),
                jnp.where(noc_arg == nidx_f, 1.0, 0.0),
            )

        # mask rates BEFORE the phi multiply (inf · 0 would poison remains)
        d_ops = jnp.where(running, compute, 0.0) * phi
        d_bw = jnp.where(running, bw, 0.0) * phi
        dr_ops = jnp.maximum(rem_ops - d_ops, 0.0)
        dr_rd = jnp.maximum(rem_rd - d_bw, 0.0)
        dr_wr = jnp.maximum(rem_wr - d_bw, 0.0)
        newly_done = running & (c_t <= phi * (1 + 1e-9))
        keep = ~newly_done
        now = now + phi
        finish = jnp.where(newly_done, now, finish)
        bneck = jnp.where(newly_done, code, bneck)
        if n_noc > 1:
            bneck_noc = jnp.where(newly_done, noc_arg, bneck_noc)
        alp_t = alp_t + phi * jnp.sum(runf / jnp.maximum(load_t, 1.0))
        traffic = traffic + jnp.sum(
            jnp.where(running, jnp.minimum(dr_rd + dr_wr, d_bw + d_bw), 0.0)
        )
        nph = nph + jnp.where(any_run, 1.0, 0.0)
        return (
            jnp.where(keep, dr_ops, 0.0), jnp.where(keep, dr_rd, 0.0),
            jnp.where(keep, dr_wr, 0.0), jnp.where(newly_done, 1.0, completed),
            now, finish, bneck, bneck_noc, kind_s, pe_bt, mem_bt, noc_bt,
            alp_t, traffic, nph,
        )

    row0 = jnp.zeros((1, t), f32)
    state = (
        work, rd_b, wr_b, completed0,
        f32(0.0), row0, jnp.zeros((1, t), jnp.int32), row0,
        (f32(0.0),) * 3, row0, row0, jnp.zeros((1, n_noc), f32),
        f32(0.0), f32(0.0), f32(0.0),
    )
    # every phase retires ≥ 1 of the t_real live tasks, so t_real iterations
    # suffice; once all are done, phases are zero-length no-ops
    (_, _, _, completed, now, finish, bneck, bneck_noc, kind_s, pe_bt,
     mem_bt, noc_bt, alp_t, traffic, nph) = jax.lax.fori_loop(
        0, t_real, phase, state)
    # slot-resolve the per-task bottleneck time once (phase-invariant maps)
    pe_b = _dot(pe_bt, ohp)
    mem_b = _dot(mem_bt, ohm)
    noc_b = jnp.full((1, 1), kind_s[2], f32) if n_noc == 1 else noc_bt

    # ---- device-side PPA rollup + Eq.-7 fitness -------------------------
    wlhot = wlhot_ref[...]
    wl_lat = jnp.max(
        jnp.where(wlhot > 0.5, finish.T, 0.0), axis=0, keepdims=True
    )  # (1, NW)
    dyn_pj = jnp.sum(
        _dot_t(pe_pj_ref[...], ohp) * work
        + (_dot_t(mem_pj_ref[...], ohm) + nocs_ref[0, 0] * hops)
        * (rd_b + wr_b)
    )
    # active-slot masked rollups (inactive slots price as absent hardware;
    # host rows are all-active so the ×1.0 multiply is bit-exact)
    leak_w = (
        jnp.sum(pe_leak_ref[...] * pe_active_ref[...])
        + jnp.sum(mem_leak_ref[...] * mem_active_ref[...])
        + jnp.sum(noc_leak_ref[...] * noc_active_ref[...])
    )
    energy = dyn_pj * 1e-12 + leak_w * now
    power = jnp.where(now > 0, energy / jnp.maximum(now, 1e-30), 0.0)
    cap = _dot(wr_b, ohm)  # per-MEM-slot resident bytes
    area = (
        jnp.sum(pe_area_ref[...] * pe_active_ref[...])
        + jnp.sum(
            (mem_af_ref[...] + mem_amb_ref[...] * jnp.maximum(cap, 1.0) / 1e6)
            * mem_active_ref[...]
        )
        + jnp.sum(noc_area_ref[...] * noc_active_ref[...])
    )
    wlbud = wlbud_ref[...]
    alpha = nocs_ref[0, 3]
    dists = jnp.stack([
        jnp.max((wl_lat - wlbud) / wlbud),
        (power - nocs_ref[0, 1]) / nocs_ref[0, 1],
        (area - nocs_ref[0, 2]) / nocs_ref[0, 2],
    ])
    fitness = jnp.sum(jnp.where(dists > 0, dists, alpha * dists))

    finish_ref[...] = finish
    # packed binding code: 0/1 = pe/mem, NoC-bound = 2 + 3·(chain index)
    bneck_ref[...] = jnp.where(
        bneck == 2, 2 + 3 * bneck_noc.astype(jnp.int32), bneck
    )
    wllat_ref[...] = wl_lat
    pe_bneck_ref[...] = pe_b
    mem_bneck_ref[...] = mem_b
    noc_bneck_ref[...] = noc_b
    scal_ref[0] = jnp.stack([
        now, energy, power, area, fitness, alp_t, traffic, nph,
        jnp.where(jnp.min(completed) > 0.5, 1.0, 0.0),
        kind_s[0], kind_s[1], kind_s[2],
        _first_argmax(pe_b).astype(f32), _first_argmax(mem_b).astype(f32),
    ])


def phase_sim_batch(
    work: jax.Array,      # (1, T) f32, T padded
    rd: jax.Array,        # (1, T)
    wr: jax.Array,        # (1, T)
    burst: jax.Array,     # (1, T)
    pmask: jax.Array,     # (T, T)
    wlhot: jax.Array,     # (T, NW)
    task_pe: jax.Array,   # (B, T) i32
    task_mem: jax.Array,  # (B, T) i32
    accel: jax.Array,     # (B, T)
    pe_coeffs: Dict[str, jax.Array],   # 5 × (B, S) f32 + (B, S) i32 pe_noc
    mem_coeffs: Dict[str, jax.Array],  # 6 × (B, S) f32 + (B, S) i32 mem_noc
    noc_arrays: Dict[str, jax.Array],  # 5 × (B, N) per-NoC chain columns
    nocs: jax.Array,      # (B, N_NOCS) packed scalars
    wlbud: jax.Array,     # (B, NW)
    *,
    t_real: int,
    interpret: bool = False,
):
    """One fused launch over the (B, T) grid; returns (finish, bneck,
    wl_latency, scal, pe_bneck, mem_bneck, noc_bneck) with the scal columns
    laid out as ``SCAL_COLS`` and the per-slot bottleneck-seconds telemetry
    in the trailing (B, S)/(B, N) blocks."""
    b, t = task_pe.shape
    s_pe = pe_coeffs["pe_peak"].shape[1]
    s_mem = mem_coeffs["mem_bw"].shape[1]
    n_noc = noc_arrays["noc_bw"].shape[1]
    n_wl = wlhot.shape[1]

    shared = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    # one (1, w) row per program: per-candidate arrays travel as (B, 1, w)
    # with the batch axis squeezed, so a block's last two dims equal the
    # array's — the only way Mosaic takes a one-row block
    perb = lambda w: pl.BlockSpec((pl.squeezed, 1, w), lambda i: (i, 0, 0))
    row = lambda a: a[:, None, :]

    kernel = functools.partial(_phase_sim_kernel, t_real=t_real)
    outs = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            shared((1, t)), shared((1, t)), shared((1, t)), shared((1, t)),
            shared((t, t)), shared((t, n_wl)),
            perb(t), perb(t), perb(t),
            perb(s_pe), perb(s_pe), perb(s_pe), perb(s_pe), perb(s_pe),
            perb(s_pe),
            perb(s_mem), perb(s_mem), perb(s_mem), perb(s_mem), perb(s_mem),
            perb(s_mem), perb(s_mem),
            perb(n_noc), perb(n_noc), perb(n_noc), perb(n_noc), perb(n_noc),
            perb(N_NOCS), perb(n_wl),
        ],
        out_specs=[perb(t), perb(t), perb(n_wl), perb(N_SCAL),
                   perb(s_pe), perb(s_mem), perb(n_noc)],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, t), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, t), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, n_wl), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, N_SCAL), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, s_pe), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, s_mem), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, n_noc), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, s_pe), jnp.float32),
            pltpu.VMEM((t, s_mem), jnp.float32),
            pltpu.VMEM((t, t), jnp.float32),
            pltpu.VMEM((t, t), jnp.float32),
        ],
        interpret=interpret,
    )(
        work, rd, wr, burst, pmask, wlhot,
        *map(row, (
            task_pe, task_mem, accel,
            pe_coeffs["pe_peak"], pe_coeffs["pe_pj"],
            pe_coeffs["pe_leak"], pe_coeffs["pe_area"], pe_coeffs["pe_noc"],
            pe_coeffs["pe_active"],
            mem_coeffs["mem_bw"], mem_coeffs["mem_pj"], mem_coeffs["mem_leak"],
            mem_coeffs["mem_area_fixed"], mem_coeffs["mem_area_per_mb"],
            mem_coeffs["mem_noc"], mem_coeffs["mem_active"],
            noc_arrays["noc_bw"], noc_arrays["noc_links"],
            noc_arrays["noc_leak"], noc_arrays["noc_area"],
            noc_arrays["noc_active"],
            nocs, wlbud,
        )),
    )
    return tuple(o[:, 0, :] for o in outs)
