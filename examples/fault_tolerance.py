"""Fault-tolerance demo: inject a step failure mid-training; the supervisor
restores the last atomic checkpoint, rewinds the data pipeline, and the run
completes with the SAME final parameters as an uninterrupted run.

  PYTHONPATH=src python examples/fault_tolerance.py
"""
import shutil
import tempfile

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import reduced_config
from repro.data.pipeline import for_model
from repro.models.model import RunFlags
from repro.optim.adamw import AdamWConfig
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.health import Supervisor
from repro.train.step import init_train_state, make_train_step

STEPS, SAVE_EVERY, FAIL_AT = 24, 6, 15


def run(workdir: str, inject_failure: bool):
    cfg = reduced_config("qwen3-1.7b")
    data = for_model(cfg, seq_len=32, global_batch=4, seed=0)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, RunFlags(attn_impl="full"),
                                   AdamWConfig(peak_lr=1e-3, warmup_steps=2)))
    calls = {"n": 0}

    def maybe_flaky(s, b):
        calls["n"] += 1
        if inject_failure and calls["n"] == FAIL_AT:
            print("  !! injected device failure at call", calls["n"])
            raise RuntimeError("simulated ICI link failure")
        return step(s, b)

    ckpt = CheckpointManager(workdir, keep_n=3, async_save=False)
    sup = Supervisor(ckpt, data, save_every=SAVE_EVERY)
    out = sup.run(state, maybe_flaky, STEPS,
                  restore_fn=lambda: ckpt.restore(state),
                  on_metrics=lambda s, m: print(f"  step {s:3d} loss={float(m['loss']):.4f}")
                  if s % 6 == 0 else None)
    return out, sup.recoveries


def main() -> None:
    use_compile_cache()
    d1, d2 = tempfile.mkdtemp(), tempfile.mkdtemp()
    try:
        print("reference run (no failure):")
        ref, _ = run(d1, inject_failure=False)
        print("\nfaulty run (failure at call 15 → restore from step 12):")
        out, recoveries = run(d2, inject_failure=True)
        same = all(
            np.allclose(a, b, atol=1e-6)
            for a, b in zip(jax.tree.leaves(ref["params"]), jax.tree.leaves(out["params"]))
        )
        print(f"\nrecoveries={recoveries}; final params identical to uninterrupted run: {same}")
        assert same and recoveries == 1
    finally:
        shutil.rmtree(d1, ignore_errors=True)
        shutil.rmtree(d2, ignore_errors=True)


if __name__ == "__main__":
    main()
