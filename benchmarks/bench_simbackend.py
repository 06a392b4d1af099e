"""SimulatorBackend shoot-out: scalar-Python vs array-native JAX evaluation.

Measures the DSE hot path the perf work targets, and writes it to
``BENCH_simbackend.json`` (next to this file, mirrored to the repo root by
``benchmarks/run.py``) so future PRs can track the speedup trajectory:

  1. neighbour-evaluation throughput — the regime the explorer actually
     runs: one base design, a batch of move candidates (recorded deltas, no
     clones), priced by ``PythonBackend`` (simulate() per candidate) and by
     a warm ``JaxBatchedBackend`` (incremental encode → one batched dispatch
     → fitness column consumed, no decode), in candidates/second;
  2. the backend's encode/dispatch/decode wall-clock breakdown
     (``BackendStats``) over the measured dispatches, plus a kernel-vs-ref
     column: the same candidate batch dispatched through the fused Pallas
     phase-sim kernel (interpret mode on CPU — it exists for Mosaic/TPU, so
     on CPU this column measures the interpreter, not a win) with its
     fitness column asserted ≤ 1e-5 against the XLA reference path;
  3. end-to-end explorer iteration rate — fixed-seed exploration runs with
     each backend, in iterations/second, best-of-``reps`` to cut scheduler
     noise (jit warm-up excluded via a priming run);
  4. the device-resident explorer (``repro.core.device_explore``): fused
     (R, K) chain blocks vs the host-driven loop (the SAME compiled step
     dispatched one iteration at a time), in chain-iterations/second, plus
     the R×K sweep (R ∈ {1, 16, 256}, K ∈ {8, 64}) against the host
     explorer's e2e rate in the full run.

A policy-convergence comparison (paper §5.2 / Fig. 9b) rides along: every
policy of the comparison set (naive SA → telemetry-driven bottleneck /
locality → full FARSI) explores the workload under a reachable budget and
reports iterations-to-budget; the full run additionally sweeps the
generated synthetic-scenario family through ``Campaign.policy_sweep``.

A ``serve`` payload measures the continuous-batching service
(`repro.serve.DseService`): aggregate evals/s and p50/p95 session latency
at 1/8(/64 in the full run) concurrent sessions on one service with the
cache off (pure co-batching economics), plus the content-addressed
``DesignStore`` hit-rate on a repeated-scenario session mix (64 sessions in
the full run, which asserts hit-rate > 0.3 with ``n_fallback == 0``).

``run(smoke=True)`` is the CI guard (`python -m benchmarks.run --smoke`):
tiny iteration counts, and it *asserts* (a) JAX beats Python on
neighbour-eval throughput, (b) both backends agree on the winning
candidate's latency, (b') multi-NoC chain batches dispatch at ≥ 0.5x the
single-NoC throughput with ``n_fallback == 0`` (the array-native topology
regime), (c) kernel-vs-ref fitness parity ≤ 1e-5, (d) the device-loop
guard: the fused (R=16, K) chain block must sustain ≥ 2x the host-driven
loop's chain-iteration rate with ``n_compiles ≤ 4`` and ``n_fallback ==
0``, and at R=1 the fused block replays the host-driven loop's
(move, accepted) sequence bit-for-bit, (d') the speculative host pipeline
is retired: its counters must be ABSENT from ``ExplorationResult`` (the
tombstone), (e) the policy guard:
``FarsiPolicy`` reaches budget in no more iterations than ``NaiveSA`` on
the audio workload, the shared policy backend staying within the same
jit-cache footprint, (f) the serve guard: 8 co-batched sessions
sustain ≥ 0.7x the single-session *aggregate* throughput and the
repeated-scenario mix hits the cache, and (g) the degraded-mode guard: a
chaos run at a 5% injected dispatch-fault rate (seeded ``FaultInjector``)
must complete ALL sessions with zero failures and ≥ 0.5x the fault-free
aggregate throughput — retry/bisect/degrade overhead bounded, service
never down.
"""
from __future__ import annotations

import dataclasses
import filecmp
import json
import os
import random
import time
from typing import List

import numpy as np

from repro.core import (
    Campaign,
    Candidate,
    DeviceChainRunner,
    Explorer,
    ExplorerConfig,
    HardwareDatabase,
    JaxBatchedBackend,
    PythonBackend,
    ar_complex,
    audio,
    calibrated_budget,
    random_single_noc_designs,
    synthetic_family,
)
from repro.core.moves import MOVE_KINDS, MoveDelta, MoveSpec, apply_fork, apply_move
from repro.serve import DseService, FaultInjector, RetryPolicy

from .common import Row, timeit

# the §5.2 comparison set: naive SA baseline, the two telemetry-driven
# single-ingredient policies, and the full FARSI composition
POLICY_SET = ("naive_sa", "bottleneck", "locality", "farsi")

JSON_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_simbackend.json")
BATCH = 64  # campaign-scale cross-batch (explorer alone submits 4/iteration)
EXPLORE_ITERS = 120


def make_candidates(g, base, budget, n: int, seed: int = 7) -> List[Candidate]:
    """``n`` recorded-move candidates off one base design — the shape of an
    explorer/campaign neighbour batch (shared base, delta per candidate)."""
    rng = random.Random(seed)
    tasks = sorted(g.tasks)
    ck = base.checkpoint()
    out: List[Candidate] = []
    while len(out) < n:
        move = rng.choice(MOVE_KINDS)
        block = rng.choice(list(base.blocks))
        task = rng.choice(tasks)
        direction = rng.choice([-1, 1])
        delta = MoveDelta()
        ok = apply_move(base, g, move, block, task, direction, "pe", "latency",
                        random.Random(0), delta)
        base.restore(ck)
        if ok and not delta.topology:
            spec = MoveSpec(move, block, task, direction, "pe", "latency")
            out.append(Candidate(base=base, spec=spec, delta=delta, budget=budget))
    return out


def _consume(handles) -> int:
    """Rank the batch the way the explorer does: fitness column only."""
    fits = [h.fitness for h in handles]
    return min(range(len(fits)), key=fits.__getitem__)


def _serve_mix_config(i: int, iters: int) -> ExplorerConfig:
    """The repeated-scenario session mix: 16 distinct policy×seed configs,
    cycled — replica requests are what the content-addressed cache collapses."""
    return ExplorerConfig(
        policy=POLICY_SET[i % len(POLICY_SET)], seed=(i // len(POLICY_SET)) % 4,
        max_iterations=iters, backend="jax",
    )


def _serve_wave(svc: DseService, g, bud, wave: str, n: int, iters: int) -> dict:
    """Admit ``n`` mixed sessions onto ``svc``, drive to completion, and
    report the wave's aggregate throughput + per-session latency spread.
    Reusing one service across waves keeps the shared backends (and their
    jit caches) warm, so waves compare batching economics, not compiles."""
    handles = [
        svc.submit(f"{wave}.{i}", g, bud, _serve_mix_config(i, iters))
        for i in range(n)
    ]
    t0 = time.perf_counter()
    svc.run()
    wall = time.perf_counter() - t0
    lats = sorted(h.latency_s for h in handles)
    pct = lambda q: lats[min(len(lats) - 1, round(q * (len(lats) - 1)))]
    return {
        "n_sessions": n,
        "wall_s": wall,
        "iters_per_s_aggregate": n * iters / max(wall, 1e-9),
        "evals_per_s": sum(h.result.n_sims for h in handles) / max(wall, 1e-9),
        "latency_p50_s": pct(0.5),
        "latency_p95_s": pct(0.95),
    }


def run(smoke: bool = False) -> List[Row]:
    db = HardwareDatabase()
    batch = 16 if smoke else BATCH
    iters = 20 if smoke else EXPLORE_ITERS
    reps = 3 if smoke else 7
    payload = {"batch": batch, "explore_iterations": iters, "workloads": {}}
    rows: List[Row] = []

    # audio (15 tasks) and the full AR complex (28 tasks) — the two paper
    # workload scales where batching is the DSE's operating point
    graphs = (audio(),) if smoke else (audio(), ar_complex())
    for g in graphs:
        bud = calibrated_budget(db)
        base = random_single_noc_designs(g, 1, seed=7)[0]
        cands = make_candidates(g, base, bud, batch)
        py = PythonBackend(g, db)
        jx = JaxBatchedBackend(g, db)
        _consume(jx.evaluate_candidates(cands))  # compile once; steady state
        _consume(py.evaluate_candidates(cands))
        # interleave the samples so both backends see the same machine
        # conditions (scheduler noise on small graphs otherwise skews ratios)
        t_py = t_jx = float("inf")
        s0 = dataclasses.replace(jx.stats())
        for _ in range(reps):
            t_py = min(t_py, timeit(lambda: _consume(py.evaluate_candidates(cands)), n=1))
            t_jx = min(t_jx, timeit(lambda: _consume(jx.evaluate_candidates(cands)), n=1))
        s1 = jx.stats()
        evals_py = batch / (t_py * 1e-6)
        evals_jx = batch / (t_jx * 1e-6)
        n_disp = s1.n_dispatches - s0.n_dispatches
        breakdown = {
            "encode_s_per_dispatch": (s1.encode_s - s0.encode_s) / n_disp,
            "dispatch_s_per_dispatch": (s1.dispatch_s - s0.dispatch_s) / n_disp,
            "fetch_wait_s_per_dispatch": (s1.fetch_wait_s - s0.fetch_wait_s) / n_disp,
            "decode_s_per_dispatch": (s1.decode_s - s0.decode_s) / n_disp,
            "n_compiles": s1.n_compiles,
        }

        # kernel-vs-ref: the same batch through the fused Pallas kernel
        # (interpret on CPU) — parity asserted, dispatch wall recorded
        jk = JaxBatchedBackend(g, db, use_kernel=True)
        hk = jk.evaluate_candidates(cands)
        hr = jx.evaluate_candidates(cands)
        fit_k = [h.fitness for h in hk]
        fit_r = [h.fitness for h in hr]
        k_rel = max(
            abs(a - b) / max(abs(a), 1e-12) for a, b in zip(fit_k, fit_r)
        )
        assert k_rel <= 1e-5, f"pallas kernel vs ref fitness parity: {k_rel}"
        t_k = min(
            timeit(lambda: _consume(jk.evaluate_candidates(cands)), n=1)
            for _ in range(2)
        )
        breakdown["kernel_dispatch_wall_s"] = t_k * 1e-6
        breakdown["ref_dispatch_wall_s"] = t_jx * 1e-6
        breakdown["kernel_vs_ref_parity"] = k_rel

        # ---- multi-NoC vs single-NoC dispatch throughput -----------------
        # the array-native topology regime: chain designs (one NoC fork on
        # top of the same random single-NoC population) must price through
        # the batched path — n_fallback == 0 — at ≥ 0.5x the single-NoC
        # dispatch throughput (the padded-N striping loop is the only cost)
        singles = random_single_noc_designs(g, batch, seed=23)
        multis = random_single_noc_designs(g, batch, seed=23)
        for d in multis:
            apply_fork(d, g, d.noc_chain[0])
        c_single = [Candidate.of_design(d, bud) for d in singles]
        c_multi = [Candidate.of_design(d, bud) for d in multis]
        jm = JaxBatchedBackend(g, db)
        _consume(jm.evaluate_candidates(c_single))  # compile both buckets
        _consume(jm.evaluate_candidates(c_multi))
        t_s1 = t_m1 = float("inf")
        for _ in range(reps):
            t_s1 = min(t_s1, timeit(lambda: _consume(jm.evaluate_candidates(c_single)), n=1))
            t_m1 = min(t_m1, timeit(lambda: _consume(jm.evaluate_candidates(c_multi)), n=1))
        multi_ratio = t_s1 / max(t_m1, 1e-9)  # multi-NoC throughput / single
        assert jm.stats().n_fallback == 0, jm.stats()
        breakdown["multi_noc_vs_single_dispatch"] = multi_ratio
        rows.append(
            (
                f"simbackend.{g.name}.multi_noc",
                t_m1 / batch,
                f"multi={batch/(t_m1*1e-6):.0f}/s single={batch/(t_s1*1e-6):.0f}/s "
                f"ratio={multi_ratio:.2f}x n_fallback=0 batch={batch}",
            )
        )

        if smoke:
            assert evals_jx / max(evals_py, 1e-9) >= 1.0, (
                f"jax neighbour-eval slower than python: {evals_jx:.0f}/s vs {evals_py:.0f}/s"
            )
            assert multi_ratio >= 0.5, (
                f"multi-NoC dispatch regression: {multi_ratio:.2f}x of the "
                f"single-NoC path (floor 0.5x)"
            )
            hj = jx.evaluate_candidates(cands)
            hp = py.evaluate_candidates(cands)
            j = _consume(hj)
            a, b = hp[j].result(), hj[j].result()
            rel = abs(a.latency_s - b.latency_s) / a.latency_s
            assert rel < 1e-4, f"backend latency mismatch on winner: {rel}"

        # end-to-end: fixed-seed exploration per backend, best-of-reps (prime
        # the jit cache with a short run so shape-bucket compiles don't bill
        # the measure runs)
        Explorer(g, db, bud, ExplorerConfig(max_iterations=iters, seed=2),
                 backend=jx).run()
        e2e_reps = 1 if smoke else 3
        it_stats = {}
        last = None
        for name, backend in (("python", py), ("jax", jx)):
            best = None
            for _ in range(e2e_reps):
                res = Explorer(
                    g, db, bud,
                    ExplorerConfig(max_iterations=iters, seed=3),
                    backend=backend,
                ).run()
                if best is None or res.wall_s < best.wall_s:
                    best = res
            last = best
            it_stats[name] = {
                "iterations": best.iterations,
                "wall_s": best.wall_s,
                "sim_wall_s": best.sim_wall_s,
                "iters_per_s": best.iterations / max(best.wall_s, 1e-9),
                "converged": best.converged,
            }
        if smoke:
            # tombstone: the speculative host pipeline is retired — its
            # counters must not quietly reappear on ExplorationResult
            for gone in ("n_spec_hits", "n_sims_wasted", "spec_auto_disabled",
                         "pipelined"):
                assert not hasattr(last, gone), (
                    f"speculative-pipeline counter resurrected: {gone}"
                )
            assert jx.stats().n_compiles <= 4, jx.stats()

        # ---- device-resident explorer (smoke: hard assertions) -----------
        # the fused (R, K) chain block vs the host-driven loop: the SAME
        # compiled step dispatched K=1 per iteration with the carry pulled
        # back to host — the classic host-loop regime. Parity first (at R=1
        # the fused block must replay the host loop bit-for-bit), then
        # throughput at an R=16 population.
        runner = DeviceChainRunner(g, db)
        dev_k = 32
        par_f = runner.run_chains(base, bud, r=1, k=dev_k, seed=5)
        par_h = runner.run_chains_host(base, bud, r=1, n_steps=dev_k, seed=5)
        parity_ok = par_f.seq(0) == par_h.seq(0)
        assert parity_ok, "fused device block diverged from the host loop"
        dev_r = 16
        runner.run_chains(base, bud, r=dev_r, k=dev_k, seed=5)  # compile
        runner.run_chains(base, bud, r=dev_r, k=1, seed=5)  # warm k=1 block
        t_dev = t_hloop = float("inf")
        for _ in range(reps):
            t_dev = min(
                t_dev, runner.run_chains(base, bud, r=dev_r, k=dev_k, seed=5).wall_s
            )
        for _ in range(max(1, reps - 1)):
            t_hloop = min(
                t_hloop,
                runner.run_chains_host(
                    base, bud, r=dev_r, n_steps=dev_k, seed=5
                ).wall_s,
            )
        dev_its = dev_r * dev_k / max(t_dev, 1e-9)
        hloop_its = dev_r * dev_k / max(t_hloop, 1e-9)
        fused_vs_host_loop = dev_its / max(hloop_its, 1e-9)
        if smoke:
            assert fused_vs_host_loop >= 2.0, (
                f"device-loop regression: fused block at "
                f"{fused_vs_host_loop:.2f}x of the host-driven loop (floor 2x)"
            )
            assert runner.n_compiles <= 4, runner.n_compiles
            assert runner.n_fallback == 0, runner.n_fallback
        device_explore = {
            "r": dev_r,
            "k": dev_k,
            "device_iters_per_s": dev_its,
            "host_loop_iters_per_s": hloop_its,
            "fused_vs_host_loop": fused_vs_host_loop,
            "vs_host_explorer_jax": (
                dev_its / max(it_stats["jax"]["iters_per_s"], 1e-9)
            ),
            "vs_host_explorer_python": (
                dev_its / max(it_stats["python"]["iters_per_s"], 1e-9)
            ),
            "parity_r1": parity_ok,
            "n_compiles": runner.n_compiles,
            "n_fallback": runner.n_fallback,
        }
        if not smoke:
            # the R×K block sweep (R=256 is the slow, full-run-only point):
            # chain-iterations/second per fused shape, against the host
            # explorer's end-to-end rate
            sweep = {}
            for rr in (1, 16, 256):
                for kk in (8, 64):
                    runner.run_chains(base, bud, r=rr, k=kk, seed=5)  # compile
                    t_blk = min(
                        runner.run_chains(base, bud, r=rr, k=kk, seed=5).wall_s
                        for _ in range(3)
                    )
                    blk_its = rr * kk / max(t_blk, 1e-9)
                    sweep[f"r{rr}.k{kk}"] = {
                        "iters_per_s": blk_its,
                        "wall_s": t_blk,
                        "vs_host_explorer_jax": blk_its
                        / max(it_stats["jax"]["iters_per_s"], 1e-9),
                    }
            device_explore["sweep"] = sweep
        rows.append(
            (
                f"simbackend.{g.name}.device_explore",
                t_dev * 1e6,
                f"fused={dev_its:.0f}it/s host_loop={hloop_its:.0f}it/s "
                f"({fused_vs_host_loop:.1f}x) r={dev_r} k={dev_k} "
                f"vs_explorer={device_explore['vs_host_explorer_jax']:.1f}x "
                f"compiles={runner.n_compiles} fallback={runner.n_fallback}",
            )
        )

        # ---- mixed mapping+allocation chains (device_explore.alloc) ------
        # the widened move table: PE/MEM fork/join/frequency-swap + NoC
        # attach over capacity-padded slot inventories, sampled in the same
        # lax.scan block as the migrates. R∈{1,16}: parity first (the fused
        # mixed-move block must replay the host-driven loop bit-for-bit at
        # R=1 — same threefry draws, same f32 accept math, allocation
        # columns included), then fused-vs-host-loop throughput at R=16.
        # Fresh runner: the alloc jit cache is its own budget (≤ 6 entries).
        arunner = DeviceChainRunner(g, db)
        apar_f = arunner.run_chains(
            base, bud, r=1, k=dev_k, seed=5, menu="farsi", alloc=True
        )
        apar_h = arunner.run_chains_host(
            base, bud, r=1, n_steps=dev_k, seed=5, menu="farsi", alloc=True
        )
        alloc_parity = (
            apar_f.seq(0) == apar_h.seq(0)
            and all(
                np.array_equal(x, y)
                for x, y in zip(apar_f.carry, apar_h.carry)
            )
        )
        assert alloc_parity, (
            "fused mixed-move block diverged from the host loop"
        )
        arunner.run_chains(
            base, bud, r=dev_r, k=dev_k, seed=5, menu="farsi", alloc=True
        )  # compile
        arunner.run_chains(
            base, bud, r=dev_r, k=1, seed=5, menu="farsi", alloc=True
        )  # warm k=1 block
        t_adev = t_ahloop = float("inf")
        for _ in range(reps):
            t_adev = min(
                t_adev,
                arunner.run_chains(
                    base, bud, r=dev_r, k=dev_k, seed=5, menu="farsi",
                    alloc=True,
                ).wall_s,
            )
        for _ in range(max(1, reps - 1)):
            t_ahloop = min(
                t_ahloop,
                arunner.run_chains_host(
                    base, bud, r=dev_r, n_steps=dev_k, seed=5, menu="farsi",
                    alloc=True,
                ).wall_s,
            )
        adev_its = dev_r * dev_k / max(t_adev, 1e-9)
        ahloop_its = dev_r * dev_k / max(t_ahloop, 1e-9)
        alloc_vs_host_loop = adev_its / max(ahloop_its, 1e-9)
        if smoke:
            assert alloc_vs_host_loop >= 2.0, (
                f"mixed-move device-loop regression: fused block at "
                f"{alloc_vs_host_loop:.2f}x of the host-driven loop "
                f"(floor 2x)"
            )
            assert arunner.n_compiles <= 6, arunner.n_compiles
            assert arunner.n_fallback == 0, arunner.n_fallback
        device_explore["alloc"] = {
            "r": dev_r,
            "k": dev_k,
            "menu": "farsi",
            "n_moves": apar_f.n_moves,
            "device_iters_per_s": adev_its,
            "host_loop_iters_per_s": ahloop_its,
            "fused_vs_host_loop": alloc_vs_host_loop,
            "vs_host_explorer_jax": (
                adev_its / max(it_stats["jax"]["iters_per_s"], 1e-9)
            ),
            "parity_r1": alloc_parity,
            "n_compiles": arunner.n_compiles,
            "n_fallback": arunner.n_fallback,
        }
        rows.append(
            (
                f"simbackend.{g.name}.device_explore.alloc",
                t_adev * 1e6,
                f"fused={adev_its:.0f}it/s host_loop={ahloop_its:.0f}it/s "
                f"({alloc_vs_host_loop:.1f}x) r={dev_r} k={dev_k} "
                f"menu=farsi moves={apar_f.n_moves} "
                f"vs_explorer={device_explore['alloc']['vs_host_explorer_jax']:.1f}x "
                f"compiles={arunner.n_compiles} fallback={arunner.n_fallback}",
            )
        )

        # ---- policy-convergence comparison (§5.2 / Fig. 9b) --------------
        # iterations-to-budget per registered policy under a relaxed budget
        # the searches can actually reach within the iteration cap — the
        # guard is the paper's qualitative ORDERING (FarsiPolicy needs no
        # more iterations than NaiveSA), not endurance. One shared backend
        # across policies keeps the jit-cache footprint covered too.
        jpol = JaxBatchedBackend(g, db)
        pol_bud = bud.scaled(2.0)
        pol_cap = 150 if smoke else 400
        policy_conv = {}
        for pol in POLICY_SET:
            resp = Explorer(
                g, db, pol_bud,
                ExplorerConfig(policy=pol, max_iterations=pol_cap, seed=11),
                backend=jpol,
            ).run()
            policy_conv[pol] = {
                "iterations_to_budget": resp.iterations_to_budget(pol_cap),
                "converged": resp.converged,
                "best_distance": resp.best_distance.city_block(),
            }
        it_farsi = policy_conv["farsi"]["iterations_to_budget"]
        it_naive = policy_conv["naive_sa"]["iterations_to_budget"]
        policy_conv["naive_over_farsi"] = it_naive / max(it_farsi, 1.0)
        if smoke:
            assert it_farsi <= it_naive, (
                f"policy-convergence regression: farsi needed {it_farsi} "
                f"iterations vs naive_sa {it_naive}"
            )
            assert jpol.stats().n_compiles <= 4, jpol.stats()
        rows.append(
            (
                f"simbackend.{g.name}.policy_convergence",
                0.0,
                " ".join(
                    f"{p}={policy_conv[p]['iterations_to_budget']:.0f}"
                    + ("*" if policy_conv[p]["converged"] else "")
                    for p in POLICY_SET
                )
                + f" naive/farsi={policy_conv['naive_over_farsi']:.1f}x",
            )
        )

        payload["workloads"][g.name] = {
            "n_tasks": len(g.tasks),
            "python_evals_per_s": evals_py,
            "jax_evals_per_s": evals_jx,
            "eval_throughput_speedup": evals_jx / max(evals_py, 1e-9),
            "jax_breakdown": breakdown,
            "policy_convergence": policy_conv,
            "device_explore": device_explore,
            "explorer": it_stats,
            "explorer_iters_per_s_speedup": (
                it_stats["jax"]["iters_per_s"] / max(it_stats["python"]["iters_per_s"], 1e-9)
            ),
        }
        rows.append(
            (
                f"simbackend.{g.name}.eval_throughput",
                t_jx / batch,
                f"jax={evals_jx:.0f}/s python={evals_py:.0f}/s "
                f"speedup={evals_jx/max(evals_py,1e-9):.1f}x batch={batch}",
            )
        )
        rows.append(
            (
                f"simbackend.{g.name}.breakdown",
                0.0,
                "encode={encode_s_per_dispatch:.2e}s dispatch={dispatch_s_per_dispatch:.2e}s "
                "fetch={fetch_wait_s_per_dispatch:.2e}s "
                "decode={decode_s_per_dispatch:.2e}s compiles={n_compiles} "
                "kernel={kernel_dispatch_wall_s:.2e}s "
                "ref={ref_dispatch_wall_s:.2e}s".format(**breakdown),
            )
        )
        rows.append(
            (
                f"simbackend.{g.name}.explorer",
                it_stats["jax"]["wall_s"] * 1e6,
                f"jax={it_stats['jax']['iters_per_s']:.1f}it/s "
                f"python={it_stats['python']['iters_per_s']:.1f}it/s "
                f"speedup={payload['workloads'][g.name]['explorer_iters_per_s_speedup']:.1f}x "
                f"device={device_explore['device_iters_per_s']:.0f}it/s",
            )
        )

    # ---- continuous-batching serve economics -----------------------------
    # One DseService, repeated-scenario session mix. Throughput waves run
    # with the cache OFF (pure co-batching: does packing N sessions into
    # shared dispatches keep aggregate throughput?); the cache run measures
    # the repeated-scenario hit-rate the DesignStore exists for. Per-session
    # rate necessarily drops with N (each session still pays its own host-
    # side explorer step) — the economics claim is about the AGGREGATE.
    g_serve = audio()
    bud_serve = calibrated_budget(db)
    serve_iters = 12 if smoke else 30
    sizes = (1, 8) if smoke else (1, 8, 64)
    svc = DseService(db, backend="jax", cache=False)
    # prime at full length: the measure waves replay identical configs
    # (deterministic searches), so every shape bucket / jit entry they will
    # walk through is compiled before anything is timed
    for n in sizes:
        _serve_wave(svc, g_serve, bud_serve, f"prime{n}", n, serve_iters)
    thr = {str(n): _serve_wave(svc, g_serve, bud_serve, f"t{n}", n, serve_iters)
           for n in sizes}
    eff8 = (thr["8"]["iters_per_s_aggregate"]
            / max(thr["1"]["iters_per_s_aggregate"], 1e-9))

    cache_sessions = 16 if smoke else 64
    svc_c = DseService(db, backend="jax")  # cache on (fresh DesignStore)
    for i in range(cache_sessions):
        svc_c.submit(f"c{i}", g_serve, bud_serve,
                     _serve_mix_config(i, serve_iters))
    cstats = svc_c.run()
    assert cstats.n_fallback == 0, cstats
    if smoke:
        assert eff8 >= 0.7, (
            f"co-batching regression: 8-session aggregate throughput at "
            f"{eff8:.2f}x of single-session (floor 0.7x)"
        )
        assert cstats.cache_hit_rate > 0, cstats
    else:
        # the acceptance-criterion run: 64 repeated-scenario sessions
        assert cstats.cache_hit_rate > 0.3, cstats
    # ---- degraded-mode guard: chaos at 5% injected dispatch faults -------
    # a fresh service (own compile, primed by a warm wave) runs the same
    # 8-session mix with every shared dispatch vetoed at 5%: every fault
    # triggers the bisect → retry → (rarely) degrade ladder, and the guard
    # is that all sessions still complete with bounded throughput loss
    fault_rate = 0.05
    chaos_n = 8
    # seed pinned so faults land in BOTH waves: the warm wave must compile
    # the per-session bisect shape buckets (a fault-free warm wave would
    # leave the measured wave paying those compiles), and the measured wave
    # must actually exercise the bisect/retry ladder for the guard to mean
    # anything
    inj = FaultInjector(seed=1, dispatch_fault_rate=fault_rate)
    svc_f = DseService(db, backend="jax", cache=False, faults=inj,
                       retry=RetryPolicy(backoff_s=0.0))
    _serve_wave(svc_f, g_serve, bud_serve, "fwarm", chaos_n, serve_iters)
    chaos = _serve_wave(svc_f, g_serve, bud_serve, "fchaos", chaos_n, serve_iters)
    fstats = svc_f.stats()
    fault_ratio = (chaos["iters_per_s_aggregate"]
                   / max(thr["8"]["iters_per_s_aggregate"], 1e-9))
    assert fstats.n_failed == 0 and fstats.n_done == 2 * chaos_n, fstats
    if smoke:
        assert fault_ratio >= 0.5, (
            f"degraded-mode regression: chaos throughput at "
            f"{fault_ratio:.2f}x of fault-free (floor 0.5x) with "
            f"{fstats.n_dispatch_faults} injected dispatch faults"
        )
    payload["serve"] = {
        "workload": g_serve.name,
        "iterations_per_session": serve_iters,
        "throughput": thr,
        "batching_efficiency_8": eff8,
        "faults": {
            "dispatch_fault_rate": fault_rate,
            "n_sessions": chaos_n,
            "throughput_ratio_vs_fault_free": fault_ratio,
            "iters_per_s_aggregate": chaos["iters_per_s_aggregate"],
            "n_injected": len(inj.schedule),
            "n_dispatch_faults": fstats.n_dispatch_faults,
            "n_bisects": fstats.n_bisects,
            "n_retries": fstats.n_retries,
            "n_degraded": fstats.n_degraded,
            "n_failed": fstats.n_failed,
        },
        "cache": {
            "n_sessions": cache_sessions,
            "hit_rate": cstats.cache_hit_rate,
            "hits": cstats.cache_hits,
            "misses": cstats.cache_misses,
            "bypasses": cstats.cache_bypasses,
            "n_fallback": cstats.n_fallback,
            "evals_per_s": cstats.evals_per_s,
            "latency_p50_s": cstats.latency_percentile(50),
            "latency_p95_s": cstats.latency_percentile(95),
        },
    }
    rows.append(
        (
            "simbackend.serve.throughput",
            thr[str(sizes[-1])]["wall_s"] * 1e6,
            " ".join(
                f"agg{n}={thr[str(n)]['iters_per_s_aggregate']:.0f}it/s"
                for n in sizes
            )
            + f" eff8={eff8:.2f}x p95_8={thr['8']['latency_p95_s']:.2f}s",
        )
    )
    rows.append(
        (
            "simbackend.serve.cache",
            0.0,
            f"{cache_sessions} sessions hit-rate="
            f"{cstats.cache_hit_rate:.1%} ({cstats.cache_hits}h/"
            f"{cstats.cache_misses}m) fallback={cstats.n_fallback}",
        )
    )
    rows.append(
        (
            "simbackend.serve.faults",
            chaos["wall_s"] * 1e6,
            f"chaos@{fault_rate:.0%} dispatch faults: "
            f"{fault_ratio:.2f}x fault-free throughput, "
            f"{fstats.n_dispatch_faults} faults/"
            f"{fstats.n_retries} retries/{fstats.n_bisects} bisects/"
            f"{fstats.n_degraded} degraded, 0 failed",
        )
    )

    if not smoke:
        # ---- policy × synthetic-scenario sweep through Campaign ----------
        # the generative workload family: per-scenario iterations-to-budget
        # for the full policy set, cross-batched per scenario graph
        scens = synthetic_family(seed=0, n=6, db=db)
        camp = Campaign.policy_sweep(
            db, scens, policies=POLICY_SET, seeds=(0,),
            backend="jax", max_iterations=200,
        )
        cres = camp.run()
        scen_table = {
            s.name: {
                pol: cres.runs[f"{s.name}.{pol}.s0"].iterations_to_budget(200)
                for pol in POLICY_SET
            }
            for s in scens
        }
        farsi_wins = sum(
            1 for v in scen_table.values() if v["farsi"] <= v["naive_sa"]
        )
        payload["policy_scenarios"] = {
            "per_scenario": scen_table,
            "policy_iterations_mean": cres.policy_iterations(200),
            "farsi_beats_naive": farsi_wins,
            "n_scenarios": len(scens),
            "codesign": {
                k: v for k, v in cres.aggregate.items() if k.startswith("codesign")
            },
        }
        rows.append(
            (
                "simbackend.policy_scenarios",
                0.0,
                f"farsi<=naive on {farsi_wins}/{len(scens)} synthetic scenarios; "
                + " ".join(
                    f"{p}={cres.policy_iterations(200)[p]:.0f}" for p in POLICY_SET
                ),
            )
        )
        with open(JSON_PATH, "w") as f:
            json.dump(payload, f, indent=2)
        rows.append(("simbackend.json", 0.0, f"wrote {JSON_PATH}"))
    else:
        # stale-mirror guard: the repo-root copy of the trajectory JSON must
        # be byte-identical to the benchmarks/ source (a full run that died
        # mid-mirror would leave them diverged; run.py now renames the
        # mirror into place atomically, and this asserts the invariant)
        root_mirror = os.path.join(
            os.path.dirname(os.path.dirname(JSON_PATH)),
            os.path.basename(JSON_PATH),
        )
        if os.path.exists(JSON_PATH) and os.path.exists(root_mirror):
            assert filecmp.cmp(JSON_PATH, root_mirror, shallow=False), (
                f"stale root mirror: {root_mirror} != {JSON_PATH} — rerun "
                "the full bench so the tracker reads current numbers"
            )
        rows.append((
            "simbackend.smoke", 0.0,
            "speedup>=1, winner equivalence, kernel parity<=1e-5, "
            "multi-noc dispatch>=0.5x single-noc + n_fallback=0, "
            "device loop>=2x host loop @R=16 + compiles<=4 + fallback=0, "
            "R=1 device/host-loop parity, mixed-move alloc block: R=1 "
            "parity + >=2x host loop @R=16 + compiles<=6 + fallback=0, "
            "bench-json mirror==source, spec-pipeline tombstone, "
            "policy convergence farsi<=naive_sa, "
            "serve: 8-session aggregate>=0.7x single + cache hit-rate>0, "
            "chaos@5% dispatch faults: all sessions complete >=0.5x: OK",
        ))
    return rows
