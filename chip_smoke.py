"""Smoke run of the FARSI design-space explorer on one TPU chip.

    python3 chip_smoke.py

Drives the explorer's main path once, through the entry points a user
calls, at the paper's problem size: the AR-complex workload (Audio + CAVA +
Edge Detection, 28 tasks), the calibrated budget and the default hardware
database. Four phases run in one process; the first wrong answer raises and
ends the run with a non-zero exit:

  device      refuses any platform but TPU.
  candidates  prices 64 single-NoC and 64 multi-NoC designs through the
              default ``make_backend("jax")``. Its fitness agrees with the
              batched XLA simulator (``simulate_batch``) on every design,
              and its latency and fitness with the Python reference
              simulator on a sample.
  chains      a device-resident mixed mapping+allocation search (256 chains,
              64 fused steps per block) on the default backend. Its winner
              re-prices on the Python reference to the fitness the device
              reported, and the fused block at R=1 replays the host-driven
              loop bit for bit.
  serve       16 sessions over two workloads and three policies, one of them
              on device chains, six joining mid-flight, on the default
              backend. Every session ends DONE; none failed, degraded or
              priced on the scalar fallback.

The seconds, compile counts and parity maxima printed along the way are
readings of this one run, not metrics. The last line of standard output is
the JSON verdict: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    Candidate,
    Design,
    Explorer,
    ExplorerConfig,
    HardwareDatabase,
    ar_complex,
    audio,
    calibrated_budget,
    distance,
    make_accelerator,
    make_backend,
    make_mem,
    random_single_noc_designs,
    simulate,
)
from repro.core.moves import apply_fork  # noqa: E402
from repro.core.phase_sim_jax import (  # noqa: E402
    EncodedWorkload,
    encode_batch,
    fill_budget,
    simulate_batch,
)
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402
from repro.serve import DseService  # noqa: E402

N_DESIGNS = 64  # per NoC regime
N_SAMPLE = 16  # designs per regime re-priced on the Python reference
CHAIN_R, CHAIN_K = 256, 64
PARITY_K = 16  # fused steps replayed against the host-driven loop at R=1
N_SESSIONS, N_LATE = 16, 6  # sessions in all / joining mid-flight
SERVE_ITERS = 40
CHAIN_SESSION = ExplorerConfig(policy="farsi", seed=0, max_iterations=128,
                               backend="jax", chain_r=32, chain_k=32)
ALPHA = 0.05
PARITY_TOL = 1e-5  # backend vs XLA and vs the Python reference
# a search's winner: f32 device fitness after many accepted moves vs the
# f64 Python rollup of the reconciled design
FITNESS_TOL = 1e-4


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def rel_err(ref, got) -> float:
    a = np.asarray(ref, np.float64)
    b = np.asarray(got, np.float64)
    check(a.shape == b.shape, f"shape {b.shape} != reference {a.shape}")
    if a.size == 0:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-12)))


def expect_backend(backend, name: str) -> None:
    check(backend.name == name,
          f"backend resolved to {backend.name!r}, not {name!r}")


def multi_noc_designs(g, n: int, seed: int):
    """Random designs on 2- and 3-NoC chains, built the way the explorer builds
    them: accelerators and memories on the base design's NoC, real NoC
    forks, then a random remap so routes span the chain."""
    rng = random.Random(seed)
    tasks = sorted(g.tasks)
    out = []
    for i in range(n):
        d = Design.base(g)
        noc0 = d.noc_chain[0]
        for _ in range(rng.randint(2, 4)):
            if rng.random() < 0.5:
                t = rng.choice(tasks)
                b = d.add_block(make_accelerator(t, rng.choice((100, 400))),
                                attach_to=noc0)
                d.task_pe[t] = b.name
            else:
                d.add_block(make_mem(rng.choice(("dram", "sram")),
                                     rng.choice((100, 800)), 32),
                            attach_to=noc0)
        while len(d.noc_chain) < 2 + i % 2:
            forkable = [n for n in d.noc_chain if len(d.attached(n)) >= 2]
            check(apply_fork(d, g, rng.choice(forkable)), "NoC fork refused")
        pes, mems = d.pes(), d.mems()
        for t in tasks:
            d.task_pe[t] = rng.choice(pes)
            d.task_mem[t] = rng.choice(mems)
        out.append(d)
    return out


def phase_device() -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"device: {info}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform {dev.platform!r}"
        )
    return info


def phase_candidates(g, db, bud) -> None:
    enc = EncodedWorkload.of(g)
    xla = jax.jit(lambda r: simulate_batch(enc, r))
    for label, designs in (
        ("1 NoC", random_single_noc_designs(g, N_DESIGNS, seed=1)),
        ("2-3 NoCs", multi_noc_designs(g, N_DESIGNS, seed=2)),
    ):
        t0 = time.perf_counter()
        rows = encode_batch(designs, g, db, enc)
        for j in range(len(designs)):
            fill_budget(rows, j, enc, bud.latency_s, bud.power_w, bud.area_mm2, ALPHA)
        ref = xla(rows)
        backend = make_backend("jax", g, db)
        expect_backend(backend, "jax")
        handles = backend.evaluate_candidates(
            [Candidate.of_design(d, bud, ALPHA) for d in designs]
        )
        fit = np.array([h.fitness for h in handles])
        fit_err = rel_err(np.asarray(ref["fitness"]), fit)
        check(fit_err <= PARITY_TOL,
              f"{label}: backend vs XLA fitness {fit_err:.3g}")
        lat_err = py_fit_err = 0.0
        for j in range(0, len(designs), len(designs) // N_SAMPLE):
            py = simulate(designs[j], g, db)
            res = handles[j].result()
            lat_err = max(lat_err, rel_err(py.latency_s, res.latency_s),
                          rel_err([py.task_finish_s[t] for t in py.task_finish_s],
                                  [res.task_finish_s[t] for t in py.task_finish_s]))
            py_fit_err = max(py_fit_err, rel_err(
                distance(py, bud).fitness(ALPHA), handles[j].fitness))
        check(lat_err <= PARITY_TOL,
              f"{label}: latency vs Python {lat_err:.3g}")
        check(py_fit_err <= PARITY_TOL,
              f"{label}: fitness vs Python {py_fit_err:.3g}")
        st = backend.stats()
        check(st.n_fallback == 0, f"{label}: {st.n_fallback} scalar fallbacks")
        print(f"candidates[{label}]: {len(designs)} designs, vs XLA fitness "
              f"{fit_err:.3g}, vs Python latency {lat_err:.3g} fitness "
              f"{py_fit_err:.3g}, {time.perf_counter() - t0:.1f} s", flush=True)


def phase_chains(g, db, bud) -> None:
    t0 = time.perf_counter()
    ex = Explorer(g, db, bud, ExplorerConfig(
        policy="farsi", backend="jax", chain_r=CHAIN_R, chain_k=CHAIN_K,
        chain_alloc=True,
    ))
    expect_backend(ex.backend, "jax")
    res = ex.run_chains()
    dev_fit = res.history[-1]["fitness"]
    py_fit = distance(simulate(res.best_design, g, db), bud).fitness(ALPHA)
    err = rel_err(py_fit, dev_fit)
    check(err <= FITNESS_TOL,
          f"chains: winner fitness {dev_fit} on device, {py_fit} on Python")
    runner = ex.backend.chain_runner()
    print(f"chains: R={CHAIN_R} K={CHAIN_K}, {res.iterations} iterations, "
          f"winner fitness {dev_fit:.6g} (Python {py_fit:.6g}, rel {err:.3g}), "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    t0 = time.perf_counter()
    d = random_single_noc_designs(g, 1, seed=7)[0]
    kw = dict(r=1, seed=7, menu="farsi", alloc=True)
    fused = runner.run_chains(d, bud, k=PARITY_K, **kw)
    host = runner.run_chains_host(d, bud, n_steps=PARITY_K, **kw)
    check(fused.seq(0) == host.seq(0), "chains: R=1 move sequence differs")
    check(np.array_equal(fused.fit_trace, host.fit_trace),
          "chains: R=1 fitness trace differs")
    check(all(np.array_equal(a, b) for a, b in zip(fused.carry, host.carry)),
          "chains: R=1 carry differs")
    check(runner.n_fallback == 0, f"chains: {runner.n_fallback} fallbacks")
    print(f"chains: R=1 fused block replays {PARITY_K} host steps bit for bit; "
          f"n_compiles={runner.n_compiles}, {time.perf_counter() - t0:.1f} s",
          flush=True)


def phase_serve(db, bud) -> None:
    t0 = time.perf_counter()
    graphs = {"audio": audio(), "ar": ar_complex()}
    policies = ("farsi", "bottleneck", "naive_sa")
    svc = DseService(db, backend="jax")

    def submit(i):
        wl = "ar" if i % 2 == 0 else "audio"
        cfg = CHAIN_SESSION if i == 0 else ExplorerConfig(
            policy=policies[i % len(policies)], seed=i,
            max_iterations=SERVE_ITERS, backend="jax",
        )
        return svc.submit(f"{wl}.{cfg.policy}.{i}", graphs[wl], bud, cfg)

    n_head = N_SESSIONS - N_LATE
    handles = [submit(i) for i in range(n_head)]
    for _ in range(3):
        svc.step()
    handles += [submit(i) for i in range(n_head, N_SESSIONS)]
    stats = svc.run()
    for be in svc.scheduler.backends().values():
        expect_backend(be, "jax")
    not_done = [h.name for h in handles if not h.done]
    check(not not_done, f"serve: sessions not DONE: {not_done}")
    check(handles[0].result.chained, "serve: the chain session ran no chains")
    check(stats.n_failed == 0 and stats.n_degraded == 0 and stats.n_fallback == 0,
          f"serve: failed={stats.n_failed} degraded={stats.n_degraded} "
          f"fallback={stats.n_fallback}")
    print(f"serve: {stats.n_done}/{stats.n_sessions} sessions DONE in "
          f"{stats.n_ticks} ticks, failed=0 degraded=0 fallback=0, "
          f"{time.perf_counter() - t0:.1f} s", flush=True)


def main() -> None:
    device = phase_device()
    cache = use_compile_cache()
    db = HardwareDatabase()
    bud = calibrated_budget(db)
    g = ar_complex()
    phase_candidates(g, db, bud)
    phase_chains(g, db, bud)
    phase_serve(db, bud)
    print(f"compile cache: {cache}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
