"""Open-loop sessions at ``rate_per_s`` for the window, with the gaps of a
Poisson process and the same work under every seed.

A window of ``seconds`` offers ``n = round(rate_per_s * seconds)``
sessions. The gaps are the ``n`` quantiles of the exponential distribution
at that rate, the policies are the mix's shares (``mix``) rounded to whole
counts, and the budget factors (``budget_factors``) come in equal counts:
every seed gets the same set of gaps, policies and budgets, shuffled into
another order, and its own explorer and platform seeds. ``stream``
separates the untimed warm-up traffic from the window's.
"""
from __future__ import annotations

import math
import random
from typing import List

from bench import traffic


def generate(params: dict, seed: int, seconds: float, stream: str = "window") -> List[dict]:
    rng = random.Random(f"{stream}:{seed}")
    rate = float(params["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    rng.shuffle(gaps)
    mix = params["mix"]
    policies = [m["policy"] for m, c in zip(mix, traffic.counts([m["share"] for m in mix], n))
                for _ in range(c)]
    rng.shuffle(policies)
    factors = params.get("budget_factors", [1.0])
    budget = [f for f, c in zip(factors, traffic.counts([1.0] * len(factors), n))
              for _ in range(c)]
    rng.shuffle(budget)
    out = []
    due = 0.0
    for i in range(n):
        due += gaps[i]
        out.append({
            "due_s": due,
            "policy": policies[i],
            "budget_factor": budget[i],
            "explorer_seed": rng.randrange(2**31),
            "platform_seed": rng.randrange(2**31),
        })
    return out
