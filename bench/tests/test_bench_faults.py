"""A whole run on the CPU, the look for a chip skipped, with the timed path
broken underneath: ``correct`` has to come out false for each fault the
cell can have. (No cell runs across chips, so none can lose an exchange
between them.)"""
import pytest

from bench.tests import tiny


def _stuck_chain_state(monkeypatch):
    """Every chain block returns the state it was given."""
    from repro.core.device_explore import DeviceChainRunner

    build = DeviceChainRunner._block

    def block(self, *args, **kw):
        fn = build(self, *args, **kw)

        def stuck(carry, *rest):
            _, traces = fn(carry, *rest)
            return carry, traces
        return stuck

    monkeypatch.setattr(DeviceChainRunner, "_block", block)


def _simulator_patched(monkeypatch, change):
    import repro.core.phase_sim_jax as psj
    import repro.kernels.phase_sim.chain as chain

    orig = psj.simulate_batch

    def simulate_batch(enc, rows):
        return change(dict(orig(enc, rows)))

    monkeypatch.setattr(psj, "simulate_batch", simulate_batch)
    monkeypatch.setattr(chain, "simulate_batch", simulate_batch)


def _answer_altered(monkeypatch):
    """The simulator's answer is off by a thousandth where it is made."""
    def change(out):
        for k in ("fitness", "latency_s"):
            out[k] = out[k] * 1.001
        return out
    _simulator_patched(monkeypatch, change)


def _half_batch_left_out(monkeypatch):
    """The second half of every batch gets the first half's answers."""
    def change(out):
        for k, v in out.items():
            b = v.shape[0]
            if b > 1:
                out[k] = v.at[b - b // 2:].set(v[: b // 2])
        return out
    _simulator_patched(monkeypatch, change)


FAULTS = {"stuck_state": _stuck_chain_state, "answer_altered": _answer_altered,
          "half_batch": _half_batch_left_out}


@pytest.mark.parametrize("workload,fault", [
    ("ar_complex.chains", "stuck_state"),
    ("ar_complex.chains", "answer_altered"),
    ("audio.chains", "half_batch"),
    ("audio.serve_chains", "stuck_state"),
    ("ar_complex.serve", "answer_altered"),
    ("ar_complex.serve", "half_batch"),
])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    res = tiny.run(tiny.make_root(tmp_path), workload)
    assert not res["correct"], res["checks"]
