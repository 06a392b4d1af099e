"""Checkpoint manager (atomicity, keep-N, async), data pipeline determinism,
fault-tolerant supervisor recovery, straggler detection, heartbeats."""
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import reduced_config
from repro.data.pipeline import DataConfig, SyntheticLM, for_model
from repro.models.model import RunFlags
from repro.optim.adamw import AdamWConfig
from repro.runtime.health import Heartbeat, StepTimeMonitor, Supervisor
from repro.train.step import init_train_state, make_train_step


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_data_deterministic_and_seekable():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=4, seed=7)
    a, b = SyntheticLM(cfg), SyntheticLM(cfg)
    for _ in range(3):
        np.testing.assert_array_equal(a.next_batch()["tokens"], b.next_batch()["tokens"])
    b.skip_to(0)
    a2 = SyntheticLM(cfg)
    np.testing.assert_array_equal(b.next_batch()["tokens"], a2.next_batch()["tokens"])


def test_data_host_sharding_partitions():
    full = SyntheticLM(DataConfig(vocab_size=64, seq_len=8, global_batch=8, seed=1))
    whole = full.next_batch()["tokens"]
    parts = []
    for h in range(4):
        s = SyntheticLM(
            DataConfig(vocab_size=64, seq_len=8, global_batch=8, seed=1, n_hosts=4, host_index=h)
        )
        parts.append(s.next_batch()["tokens"])
    np.testing.assert_array_equal(np.concatenate(parts, 0), whole)


def test_labels_are_next_tokens():
    d = SyntheticLM(DataConfig(vocab_size=64, seq_len=8, global_batch=2, seed=3))
    b = d.next_batch()
    assert b["tokens"].shape == b["labels"].shape == (2, 8)


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------
def _tiny_state(key):
    return {
        "params": {"w": jax.random.normal(key, (4, 4)), "b": jnp.zeros((4,))},
        "step": jnp.int32(7),
    }


def test_checkpoint_roundtrip(tmp_path, rng_key):
    m = CheckpointManager(str(tmp_path), async_save=False)
    state = _tiny_state(rng_key)
    m.save(7, state, extra={"data_step": 9})
    restored, meta = m.restore(state)
    assert meta["step"] == 7 and meta["extra"]["data_step"] == 9
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(a, b)


def test_checkpoint_atomic_ignores_partial(tmp_path, rng_key):
    m = CheckpointManager(str(tmp_path), async_save=False)
    state = _tiny_state(rng_key)
    m.save(1, state)
    # simulate a crash mid-save: stray tmp dir + a committed dir missing meta
    os.makedirs(tmp_path / ".tmp-step_00000002")
    os.makedirs(tmp_path / "step_00000003")
    assert m.latest_step() == 1


def test_checkpoint_keep_n(tmp_path, rng_key):
    m = CheckpointManager(str(tmp_path), keep_n=2, async_save=False)
    state = _tiny_state(rng_key)
    for s in (1, 2, 3, 4):
        m.save(s, state)
    assert m.all_steps() == [3, 4]


def test_checkpoint_async(tmp_path, rng_key):
    m = CheckpointManager(str(tmp_path), async_save=True)
    state = _tiny_state(rng_key)
    m.save(5, state)
    m.wait()
    assert m.latest_step() == 5


# ---------------------------------------------------------------------------
# health / supervisor
# ---------------------------------------------------------------------------
def test_step_monitor_flags_stragglers():
    mon = StepTimeMonitor(threshold=2.0, warmup=2)
    for i in range(6):
        mon.record(i, 0.1)
    s = mon.record(6, 0.5)
    assert s.is_straggler
    assert len(mon.flagged) == 1
    # outlier must not poison the EMA
    assert abs(mon.ema - 0.1) < 1e-6


def test_heartbeat_dead_detection(tmp_path):
    hb0 = Heartbeat(str(tmp_path), 0)
    hb1 = Heartbeat(str(tmp_path), 1)
    now = time.time()
    hb0.beat(1)
    hb1.beat(1)
    assert Heartbeat.dead_hosts(str(tmp_path), timeout_s=10) == []
    # host 1 goes silent: check at a future "now"
    assert Heartbeat.dead_hosts(str(tmp_path), timeout_s=10, now=now + 100) == [0, 1]


def test_compile_cache_dir_is_fixed_unless_set_from_outside(monkeypatch, tmp_path):
    """Without ``JAX_COMPILATION_CACHE_DIR`` the persistent cache goes to
    ``.jax_cache/`` at the checkout root; with it, the directory JAX read
    from the variable stands. Nothing compiles here, so the cache stays
    unused."""
    from repro.runtime import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.realpath(__file__)))
    assert compile_cache.CACHE_DIR == os.path.join(repo, ".jax_cache")
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.use_compile_cache() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
        outside = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        # what JAX reads from the variable when it starts
        jax.config.update("jax_compilation_cache_dir", outside)
        assert compile_cache.use_compile_cache() == outside
        assert jax.config.jax_compilation_cache_dir == outside
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_supervisor_recovers_and_matches_uninterrupted_run(tmp_path, rng_key):
    """Kill the step function mid-run; the supervisor restores the last
    checkpoint and the final state matches a run with no failure
    (determinism of the recovery path end-to-end)."""
    cfg = reduced_config("qwen3-1.7b")
    opt = AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=20)
    raw_step = jax.jit(make_train_step(cfg, RunFlags(attn_impl="full"), opt))

    def fresh(dirname):
        data = for_model(cfg, seq_len=16, global_batch=4, seed=0)
        ckpt = CheckpointManager(str(tmp_path / dirname), keep_n=3, async_save=False)
        state = init_train_state(cfg, rng_key)
        return data, ckpt, state

    # uninterrupted reference
    data, ckpt, state = fresh("ref")
    sup = Supervisor(ckpt, data, save_every=4)
    ref = sup.run(state, raw_step, 12, restore_fn=lambda: ckpt.restore(state))

    # faulty run: blow up at global call 7
    data, ckpt, state = fresh("faulty")
    calls = {"n": 0}

    def flaky(s, b):
        calls["n"] += 1
        if calls["n"] == 7:
            raise RuntimeError("injected device failure")
        return raw_step(s, b)

    sup2 = Supervisor(ckpt, data, save_every=4)
    out = sup2.run(state, flaky, 12, restore_fn=lambda: ckpt.restore(state))
    assert sup2.recoveries == 1
    for a, b in zip(jax.tree.leaves(ref["params"]), jax.tree.leaves(out["params"])):
        np.testing.assert_allclose(a, b, atol=1e-6)
