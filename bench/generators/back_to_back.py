"""Back-to-back searches, closed loop: ``drive.MAX_SEARCHES`` requests,
each with an explorer seed and a platform seed drawn from the run's seed
(the driver starts the next when the last one ends)."""
from __future__ import annotations

import random
from typing import List

from bench import drive


def generate(params: dict, seed: int, seconds: float, stream: str = "window") -> List[dict]:
    rng = random.Random(f"{stream}:{seed}")
    return [{"explorer_seed": rng.randrange(2**31),
             "platform_seed": rng.randrange(2**31)} for _ in range(drive.MAX_SEARCHES)]
