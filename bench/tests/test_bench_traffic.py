"""The traffic generator is a function of the seed: the same seed gives the
same requests, and seeds differ in order and search seeds, not in the
amount of work offered."""
import math
from collections import Counter
from pathlib import Path

import pytest

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
TRAFFIC = ROOT / "bench" / "traffic"
FILES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def _generate(params, seed, seconds, stream="window"):
    gen = traffic.load_module(ROOT, "generators", params["generator"])
    return gen.generate(params, seed, seconds, stream)


@pytest.mark.parametrize("name", FILES)
def test_generate_is_deterministic_in_seed(name):
    params = traffic.load(TRAFFIC / f"{name}.json")
    a = _generate(params, 2**31 + 11, 30.0)
    assert a == _generate(params, 2**31 + 11, 30.0)
    assert a != _generate(params, 2**31 + 12, 30.0)
    assert a != _generate(params, 2**31 + 11, 30.0, stream="warm")


@pytest.mark.parametrize("name", [n for n in FILES
                                  if traffic.load(TRAFFIC / f"{n}.json")["mode"] == "sessions"])
def test_sessions_offer_the_same_work_under_every_seed(name):
    params = traffic.load(TRAFFIC / f"{name}.json")
    runs = [_generate(params, s, 30.0) for s in (1, 2**31 + 5, 987654321)]
    n = round(params["rate_per_s"] * 30.0)
    for reqs in runs:
        assert len(reqs) == n
        assert reqs[-1]["due_s"] == pytest.approx(runs[0][-1]["due_s"])
        assert all(b["due_s"] > a["due_s"] for a, b in zip(reqs, reqs[1:]))
    for key in ("policy", "budget_factor"):
        counts = [Counter(r[key] for r in reqs) for reqs in runs]
        assert counts[0] == counts[1] == counts[2]
    gaps = [sorted(round(b["due_s"] - a["due_s"], 9) for a, b in zip([{"due_s": 0.0}] + reqs, reqs))
            for reqs in runs]
    assert gaps[0] == gaps[1] == gaps[2]
    # the gaps are the exponential distribution's quantiles at the rate
    assert math.isclose(sum(gaps[0]) / n, 1.0 / params["rate_per_s"], rel_tol=0.1)


def test_policy_shares_round_to_whole_counts():
    assert traffic.counts([0.7, 0.1, 0.1, 0.1], 120) == [84, 12, 12, 12]
    assert sum(traffic.counts([0.7, 0.1, 0.1, 0.1], 7)) == 7
