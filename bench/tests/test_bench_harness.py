"""The harness finds a cell's configuration, traffic and metrics by name, so
a later cell needs only new files and entries; and the measured path
refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests import tiny


def test_a_cell_made_only_of_added_files_runs(tmp_path):
    root = tiny.make_root(tmp_path)
    cfg = json.loads((root / "bench/configs/audio.json").read_text())
    cfg["name"] = "audio_copy"
    (root / "bench/configs/audio_copy.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/added_mix.json").write_text(json.dumps({
        "mode": "searches", "generator": "back_to_back", "policy": "naive_sa",
        "chain_r": 4, "chain_k": 4, "chain_alloc": False, "max_iterations": 8,
        "initial": {"kind": "seeded_platform", "accelerators": 2, "memories": 1, "nocs": 1},
    }))
    (root / "bench/metrics/added_blocks.py").write_text(
        "def read(w):\n    return float(len(w.outcome.blocks))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "audio_copy", "source": "https://arxiv.org/abs/2201.05232",
                             "file": "bench/configs/audio_copy.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "audio_copy.added", "config": "audio_copy",
                               "traffic": "added_mix", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "added_blocks", "unit": "blocks", "better": "higher",
                                "bound": 0.25, "source": "host_clock",
                                "workloads": ["audio_copy.added"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = tiny.run(root, "audio_copy.added")
    assert res["correct"], res["checks"]
    assert res["metrics"]["added_blocks"]["value"] >= 1
    # metrics listed for other cells stay out of this one's line
    assert set(res["metrics"]) == {"setup_s", "added_blocks"}
    assert list(res)[list(res).index("checks") - 1] == "device"


BURSTY = '''
import random

def generate(params, seed, seconds, stream="window"):
    """Bursts of ``burst`` sessions at once, one burst per period."""
    rng = random.Random(f"{stream}:{seed}")
    n_bursts = max(1, int(seconds / params["period_s"]))
    return [{"due_s": i * params["period_s"] + 0.01 * j, "policy": "farsi",
             "budget_factor": 1.0, "explorer_seed": rng.randrange(2**31),
             "platform_seed": rng.randrange(2**31)}
            for i in range(n_bursts) for j in range(params["burst"])]
'''


def test_a_new_arrival_process_runs_from_added_files_only(tmp_path):
    root = tiny.make_root(tmp_path)
    (root / "bench/generators/bursty.py").write_text(BURSTY)
    (root / "bench/traffic/bursty_mix.json").write_text(json.dumps({
        "mode": "sessions", "generator": "bursty", "period_s": 0.5, "burst": 3,
        "max_iterations": 6, "initial": {"kind": "base"},
        "warm": {"batches": [4, 8, 16], "nocs": [1]},
    }))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "ar_complex.bursty", "config": "ar_complex",
                               "traffic": "bursty_mix", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "sessions_per_s":
            m["workloads"].append("ar_complex.bursty")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = tiny.run(root, "ar_complex.bursty", seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 6 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "sessions_per_s"}


def _run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ar_complex.chains", "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_measured_path_refuses_the_cpu():
    proc = _run_cli(tiny.ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_a_checkout_of_the_benchmark_alone_refuses_to_run(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tiny.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
