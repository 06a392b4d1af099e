"""Plain reference of what the explorer prices: the phase-driven simulator,
the power/area rollup and the Eq.-7 fitness, written from the FARSI paper
(arXiv:2201.05232, §3.2 Eqs. 1-6 and §3.4 Eq. 7) over plain data.

It imports nothing of the program. A configuration is the dict of
``bench/configs/<name>.json`` (tasks, edges, database constants, budget); a
design is the plain dict that ``bench/designs.py`` reads off a design the
program produced:

    {"blocks": [{"name", "kind", "subtype", "freq_mhz", "width_bytes",
                 "n_links", "unroll", "hardened_for"}, ...],   # insertion order
     "noc_chain": [noc names in chain order],
     "attached": {pe/mem name: noc name},
     "task_pe": {task: pe name}, "task_mem": {task: mem name}}

The model, phase by phase: every task whose parents are done runs; a PE's
peak is shared equally by the tasks on it (Eq. 1), times the hardened
speed-up for the task its accelerator was built for (Eq. 2); a memory's
bandwidth is split by burst size among the tasks buffered on it, read and
write channels apart (Eq. 4); a NoC's tasks are striped round-robin (by task
name) over its links and split each link by burst size, and a route takes
the slowest NoC on it (Eq. 3). A task drains its ops, read bytes and write
bytes concurrently; the phase lasts until the first running task would
finish (Eqs. 5-6).

``rnd`` rounds every intermediate result: the identity computes in float64,
:func:`bf16` in bfloat16, the precision below the float32 the configuration
states. That second form is the control of the correctness check.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import ml_dtypes

EPS = 1e-12


def f64(x: float) -> float:
    return x


def bf16(x: float) -> float:
    return float(ml_dtypes.bfloat16(x))


def workload_of(graph_name: str, task: str) -> str:
    """Merged graphs namespace their tasks ``<workload>.<task>``; a
    single-workload graph's tasks roll up to the graph's name."""
    return task.split(".", 1)[0] if "." in task else graph_name


def _a_peak(task: dict, unroll: int) -> float:
    """Eq. 2's A_peak: the per-task speed-up at unroll 1 times the unroll
    factor, capped by the task's loop-level parallelism."""
    return task["a_peak_base"] * max(1.0, min(float(unroll), task["llp"]))


def _leakage_w(b: dict, db: dict) -> float:
    e = db["energy"]
    fs = b["freq_mhz"] / 400.0
    if b["kind"] == "pe":
        return (e["acc_leak_w"] if b["subtype"] == "acc" else e["gpp_leak_w"]) * fs
    if b["kind"] == "mem":
        cap = db["sram_capacity_mb"] if b["subtype"] == "sram" else 0.5
        return e["mem_leak_w_per_mb"] * cap * fs
    return e["noc_leak_w"] * b["n_links"] * fs


def _block_area_mm2(b: dict, db: dict, sram_bytes: float) -> float:
    a = db["area"]
    fs = 0.6 + 0.4 * (b["freq_mhz"] / 800.0)
    if b["kind"] == "pe":
        return (a["acc_mm2"] if b["subtype"] == "acc" else a["gpp_mm2"]) * fs
    if b["kind"] == "mem":
        if b["subtype"] == "sram":  # sized by the buffers it holds
            return a["sram_mm2_per_mb"] * max(sram_bytes, 1.0) / 1e6
        return a["dram_phy_mm2"]
    return a["noc_mm2_per_byte_width"] * b["width_bytes"] * b["n_links"] * fs


def simulate(cfg: dict, design: dict, rnd: Callable[[float], float] = f64) -> dict:
    """Price one design. Returns latency, per-workload latency, energy,
    power, area and per-task finish times."""
    q = rnd
    db = cfg["database"]
    e = db["energy"]
    gname = cfg["graph"]["name"]
    tasks = {t["name"]: t for t in cfg["graph"]["tasks"]}
    order = [t["name"] for t in cfg["graph"]["tasks"]]
    parents: Dict[str, List[str]] = {n: [] for n in order}
    for src, dst, _ in cfg["graph"]["edges"]:
        parents[dst].append(src)
    blocks = {b["name"]: b for b in design["blocks"]}
    chain = design["noc_chain"]
    pos = {n: i for i, n in enumerate(chain)}
    att = design["attached"]
    t_pe, t_mem = design["task_pe"], design["task_mem"]

    route = {}
    for t in order:
        i, j = pos[att[t_pe[t]]], pos[att[t_mem[t]]]
        route[t] = chain[min(i, j):max(i, j) + 1]

    ops = {t: q(tasks[t]["work_ops"]) for t in order}
    rd = {t: q(tasks[t]["work_ops"] / tasks[t]["i_read"]) for t in order}
    wr = {t: q(tasks[t]["work_ops"] / tasks[t]["i_write"]) for t in order}
    write_total = dict(wr)
    gpp_ops = db["gpp_ops_per_cycle"]
    done: set = set()
    finish: Dict[str, float] = {}
    now = 0.0
    energy_pj = 0.0
    n_phases = 0
    while len(done) < len(order):
        n_phases += 1
        if n_phases > 10 * len(order) + 10:
            raise RuntimeError("reference simulation did not terminate")
        running = [t for t in order
                   if t not in done and all(p in done for p in parents[t])]
        if not running:
            raise RuntimeError("no ready task but the graph is incomplete")
        on_pe: Dict[str, int] = {}
        mem_burst: Dict[str, float] = {}
        for t in running:
            on_pe[t_pe[t]] = on_pe.get(t_pe[t], 0) + 1
            mem_burst[t_mem[t]] = mem_burst.get(t_mem[t], 0.0) + tasks[t]["burst_bytes"]
        # Eq. 3: stripe each NoC's users over its links by sorted task name
        link_of: Dict[tuple, int] = {}
        link_burst: Dict[tuple, float] = {}
        users: Dict[str, int] = {}
        for t in sorted(running):
            for n in route[t]:
                k = users.get(n, 0)
                users[n] = k + 1
                link = k % blocks[n]["n_links"]
                link_of[(t, n)] = link
                link_burst[(n, link)] = link_burst.get((n, link), 0.0) + tasks[t]["burst_bytes"]
        rate = {}
        remain = {}
        for t in running:
            task = tasks[t]
            pe, mem = blocks[t_pe[t]], blocks[t_mem[t]]
            peak = q(pe["freq_mhz"] * 1e6 * gpp_ops)
            accel = 1.0
            if pe["subtype"] == "acc" and pe["hardened_for"] == t:
                accel = q(_a_peak(task, pe["unroll"]))
            comp = q(q(accel * peak) / on_pe[pe["name"]])
            share_m = q(task["burst_bytes"] / mem_burst[mem["name"]])
            mem_bw = q(q(mem["freq_mhz"] * 1e6 * mem["width_bytes"]) * share_m)
            noc_bw = float("inf")
            for n in route[t]:
                nb = blocks[n]
                share = q(task["burst_bytes"] / link_burst[(n, link_of[(t, n)])])
                noc_bw = min(noc_bw, q(q(nb["freq_mhz"] * 1e6 * nb["width_bytes"]) * share))
            bw = min(mem_bw, noc_bw)
            rate[t] = (comp, bw)
            remain[t] = max(q(ops[t] / comp), q(rd[t] / bw), q(wr[t] / bw))
        phi = max(min(remain.values()), EPS)
        for t in running:
            comp, bw = rate[t]
            d_ops = min(ops[t], q(comp * phi))
            d_rd = min(rd[t], q(bw * phi))
            d_wr = min(wr[t], q(bw * phi))
            ops[t] = q(ops[t] - d_ops)
            rd[t] = q(rd[t] - d_rd)
            wr[t] = q(wr[t] - d_wr)
            pe, mem = blocks[t_pe[t]], blocks[t_mem[t]]
            pe_pj = e["acc_pj_per_op"] if pe["subtype"] == "acc" else e["gpp_pj_per_op"]
            mem_pj = e["sram_pj_per_byte"] if mem["subtype"] == "sram" else e["dram_pj_per_byte"]
            moved = q(d_rd + d_wr)
            energy_pj = q(energy_pj + q(q(pe_pj * d_ops) + q(mem_pj * moved)
                                        + q(e["noc_pj_per_byte_hop"] * q(moved * len(route[t])))))
        now = q(now + phi)
        for t in running:
            drained = ops[t] <= EPS and rd[t] <= EPS and wr[t] <= EPS
            if drained or remain[t] <= phi + EPS:
                done.add(t)
                finish[t] = now

    leak = 0.0
    area = 0.0
    sram_bytes: Dict[str, float] = {}
    for t in order:
        sram_bytes[t_mem[t]] = sram_bytes.get(t_mem[t], 0.0) + write_total[t]
    for b in design["blocks"]:
        leak = q(leak + q(_leakage_w(b, db)))
        area = q(area + q(_block_area_mm2(b, db, sram_bytes.get(b["name"], 0.0))))
    energy_j = q(q(energy_pj * 1e-12) + q(leak * now))
    wl_latency: Dict[str, float] = {}
    for t, f in finish.items():
        w = workload_of(gname, t)
        wl_latency[w] = max(wl_latency.get(w, 0.0), f)
    return {
        "latency_s": now,
        "workload_latency_s": wl_latency,
        "energy_j": energy_j,
        "power_w": q(energy_j / now),
        "area_mm2": area,
        "n_phases": n_phases,
        "task_finish_s": finish,
    }


def fitness(cfg: dict, res: dict, budget: dict, rnd: Callable[[float], float] = f64) -> float:
    """Eq. 7: the budget-normalised distance of latency (worst workload),
    power and area, with met metrics damped by ``alpha``."""
    q = rnd
    alpha = cfg["alpha"]
    per_wl = [q(q(res["workload_latency_s"].get(w, 0.0) - b) / b)
              for w, b in budget["latency_s"].items()]
    dists = (
        max(per_wl),
        q(q(res["power_w"] - budget["power_w"]) / budget["power_w"]),
        q(q(res["area_mm2"] - budget["area_mm2"]) / budget["area_mm2"]),
    )
    out = 0.0
    for d in dists:
        out = q(out + (d if d > 0 else q(alpha * d)))
    return out


def price(cfg: dict, design: dict, budget: dict, rnd: Callable[[float], float] = f64) -> dict:
    """Simulate and score one design; the result carries ``fitness``."""
    res = simulate(cfg, design, rnd)
    res["fitness"] = fitness(cfg, res, budget, rnd)
    return res


def scaled_budget(budget: dict, factor: float) -> dict:
    """The §6.1 budget relaxation (1X/2X/4X): every limit times ``factor``."""
    return {
        "latency_s": {w: v * factor for w, v in budget["latency_s"].items()},
        "power_w": budget["power_w"] * factor,
        "area_mm2": budget["area_mm2"] * factor,
    }


def rel_gap(ref: float, got: float, floor: float = 0.0) -> float:
    """|got - ref| over max(|ref|, floor): the relative gap, with a floor
    for quantities that may cross zero (a fitness at its budget)."""
    return abs(got - ref) / max(abs(ref), floor, 1e-300)
