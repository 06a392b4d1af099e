"""Session latency covers every session due in the window, a session that
never finishes counts at the drain limit, and rates are taken over the
whole window."""
import types

import pytest

from bench import drive, stats, traffic

SESSIONS = traffic.load_module(traffic.Path(__file__).resolve().parents[2], "drivers", "sessions")


def test_percentile_takes_every_value_by_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([5.0, 1.0, 4.0, 2.0, 3.0], 95) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


class _Handle:
    def __init__(self, name, ok):
        self.name, self.done, self.degraded = name, ok, False
        self.result = None


class _Service:
    """Finishes every session on its first tick except ``stuck`` ones."""

    def __init__(self, stuck):
        self.stuck, self.live, self.handles = stuck, [], {}
        self.scheduler = types.SimpleNamespace(flush=lambda: None)

    def submit(self, name, g, budget, cfg, initial=None):
        h = _Handle(name, False)
        self.handles[name] = h
        self.live.append(h)
        return h

    @property
    def n_live(self):
        return len(self.live)

    def step(self):
        out = []
        for h in list(self.live):
            if h.name.split(".")[1] not in self.stuck:
                h.done = True
                self.live.remove(h)
                out.append(h)
        return out


def test_a_session_that_never_finishes_counts_at_the_drain_limit(monkeypatch):
    monkeypatch.setattr(drive, "keep_best", lambda out, res, f, a: out.finished.append({"iterations": 1, "converged": True}))
    monkeypatch.setattr(SESSIONS, "session_config", lambda p, r, a: None)
    cell = types.SimpleNamespace(
        cfg={"alpha": 0.05}, g=None, budget=types.SimpleNamespace(scaled=lambda f: object()),
        params={"budget_factors": [1.0], "initial": {"kind": "base"}},
    )
    reqs = [{"due_s": 0.01 * (i + 1), "budget_factor": 1.0, "platform_seed": 0,
             "explorer_seed": i, "policy": "farsi"} for i in range(20)]
    out = drive.Outcome()
    svc = _Service(stuck={"3"})
    SESSIONS.serve(svc, cell, reqs, 0.3, 0.2, drive.Recorder(), out, "w")
    assert out.attempted == 20 and len(out.latencies) == 20
    assert out.lost == 1 and out.failed == 1
    # the lost session sits at the limit (window + drain - its due time)
    assert max(out.latencies) == pytest.approx(0.3 + 0.2 - 0.04, abs=1e-9)
    assert stats.percentile(out.latencies, 100) == max(out.latencies)
    assert out.window_s == 0.3 and out.completed_in_window == 19


def test_rates_divide_by_the_whole_window():
    from bench import run

    w = types.SimpleNamespace(mode="sessions", outcome=drive.Outcome(
        window_s=30.0, completed_in_window=120, latencies=[1.0]))
    assert run.load_reader(run.ROOT, "sessions_per_s")(w) == 4.0
    w = types.SimpleNamespace(mode="searches", outcome=drive.Outcome(window_s=12.5, evals=16384 * 10))
    assert run.load_reader(run.ROOT, "chain_evals_per_s")(w) == 16384 * 10 / 12.5
    assert run.load_reader(run.ROOT, "sessions_per_s")(w) is None


def test_compiles_count_only_while_the_window_is_open():
    import jax
    import jax.numpy as jnp

    rec = drive.Recorder()
    jax.jit(lambda x: x * 7 - 2)(jnp.ones(5)).block_until_ready()
    assert rec.compiles == 0
    rec.open_window()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    rec.close_window()
    assert rec.compiles >= 1
