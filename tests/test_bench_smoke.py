"""Perf-regression guard: `python -m benchmarks.run --smoke` must pass in
tier-1 CI. The smoke mode prices one neighbour-candidate batch through both
backends at tiny sizes and *asserts* (1) the JAX array-native path is at
least as fast as the scalar Python path, (2) both agree on the winning
candidate's latency, and (3) the device-loop guard: the fused (R=16, K)
chain block sustains ≥ 2x the host-driven loop's chain-iteration rate with
``n_compiles ≤ 4`` and ``n_fallback == 0``, replaying the host loop
bit-for-bit at R=1 — and (4) the same
contract for the mixed mapping+allocation block on the widened move table
(R=1 parity, ≥ 2x at R=16, ``n_compiles ≤ 6``, ``n_fallback == 0``) —
while the retired speculative-pipeline counters stay absent from
``ExplorationResult`` (the tombstone). A regression in the
incremental-encoding / lazy-decode / fused-chain hot path fails fast
instead of silently eroding the BENCH numbers. Also guards the bench-json
root mirror: it must be byte-identical to its benchmarks/ source (run.py
mirrors atomically via tmp + rename; a diverged pair means a torn or
stale mirror the perf tracker would misread)."""
import filecmp
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_benchmarks_smoke_cli():
    # the persistent compilation cache stays off under test
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=480,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "simbackend.smoke" in out.stdout, out.stdout
    # smoke must never touch the tracked trajectory file nor its root mirror
    assert "wrote" not in out.stdout
    assert "\nmirror," not in out.stdout


def test_bench_json_mirror_matches_source():
    """The repo-root BENCH_simbackend.json mirror must be byte-identical to
    the benchmarks/ source whenever both exist (atomic tmp+rename mirroring
    makes a torn copy impossible; this catches a *stale* one)."""
    src = os.path.join(REPO, "benchmarks", "BENCH_simbackend.json")
    dst = os.path.join(REPO, "BENCH_simbackend.json")
    if not (os.path.exists(src) and os.path.exists(dst)):
        return
    assert filecmp.cmp(src, dst, shallow=False), (
        "root BENCH_simbackend.json diverged from benchmarks/ source — "
        "rerun the full bench so the mirror is refreshed atomically"
    )
