"""Peaks of the chips the benchmark runs on, and the work one priced design
asks of the phase simulator, counted the same whatever implements it (the
Pallas kernel, the XLA path, or a later packed kernel).
"""
from __future__ import annotations

# device_kind as JAX reports it -> peaks. Source: Google Cloud documentation,
# "TPU v5e" (per chip: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "Google Cloud documentation, TPU v5e"},
}

F32 = 4  # bytes per value: every column of the encoding is 32-bit


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}")
    return PEAKS[device_kind]


def design_work(t: int, s_pe: int, s_mem: int, n_noc: int, n_wl: int) -> dict:
    """Bytes and operations the phase simulation of ONE design must move and
    compute, at real widths: ``t`` tasks, ``s_pe``/``s_mem`` PE and memory
    slots, ``n_noc`` NoCs and ``n_wl`` workloads, never the lane padding.

    Bytes read, per design:
      * per task: its PE slot, its memory slot and its acceleration (3·t);
      * per PE slot: peak rate, pJ/op, leakage, area, NoC position (5·s_pe);
      * per memory slot: bandwidth, pJ/byte, leakage, fixed area, area per
        MB, NoC position (6·s_mem);
      * per NoC: bandwidth, links, leakage, area (4·n_noc);
      * the budget: a latency per workload, power, area, alpha (n_wl + 3).
    The task graph itself (work, intensities, bursts, edges) is shared by a
    whole batch and not counted per design.
    Bytes written, per design: each task's finish time and bottleneck code
    (2·t), and the scalar results: latency, energy, power, area, fitness,
    phases, parallelism, traffic, the three bottleneck-class seconds and
    the per-slot and per-NoC bottleneck seconds (10 + s_pe + s_mem + n_noc).

    Operations: the phase recurrence runs ``t`` phases (each retires at
    least one task), and in each phase every task costs
      * rates: PE share (1 div, 1 mul for the acceleration), memory share
        (1 div, 1 mul), NoC share and the slowest NoC on its route (2 per
        NoC: a div and a min, bounded here by n_noc), the read/write minimum
        (1): 5 + 2·n_noc;
      * Eq. 5 time left: 3 divisions and 2 maxima (5);
      * Eq. 6 phase length: 1 min;
      * drain: 3 mul, 3 min, 3 sub (9);
      * energy: 3 mul, 3 add (6).
    That is 26 + 2·n_noc per task per phase, t·t times. The rollup (leakage,
    area, Eq. 7) is O(slots) once per design and left out.
    """
    read = F32 * (3 * t + 5 * s_pe + 6 * s_mem + 4 * n_noc + n_wl + 3)
    written = F32 * (2 * t + 10 + s_pe + s_mem + n_noc)
    ops = t * t * (26 + 2 * n_noc)
    return {"bytes": float(read + written), "ops": float(ops)}


def roofline_share(work_bytes: float, work_ops: float, busy_s: float,
                   device_kind: str) -> tuple:
    """The least time the work could take on this chip (the larger of bytes
    over HBM bandwidth and operations over peak rate) as a percentage of
    the device's busy time, and which of the two binds."""
    pk = peaks(device_kind)
    t_bytes = work_bytes / pk["hbm_bytes_per_s"]
    t_ops = work_ops / pk["flops_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_bytes, t_ops) / busy_s, bound
