"""A traffic mix is a data file of parameters (``bench/traffic/<name>.json``).
Its ``mode`` names the driver (``bench/drivers/<mode>.py``) that drives the
program with the requests, and its ``generator`` names the module
(``bench/generators/<generator>.py``) whose
``generate(params, seed, seconds, stream)`` turns the parameters and a seed
into the run's requests. A new mix of an existing kind is a data file; a
new arrival process or way of driving is one more module beside them.
"""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path
from typing import List

_MODULES: dict = {}


def load(path: Path) -> dict:
    params = json.loads(Path(path).read_text())
    for key in ("mode", "generator"):
        if not isinstance(params.get(key), str):
            raise ValueError(f"{path}: a traffic mix names its {key!r}")
    return params


def load_module(root: Path, kind: str, name: str):
    """``bench/<kind>/<name>.py`` of the checkout at ``root``, loaded once."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if path not in _MODULES:
        if not path.is_file():
            raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def counts(shares: List[float], n: int) -> List[int]:
    """Whole counts in proportion to ``shares`` summing to ``n``
    (largest remainder)."""
    total = sum(shares)
    exact = [s * n / total for s in shares]
    out = [int(math.floor(x)) for x in exact]
    rest = sorted(range(len(shares)), key=lambda i: exact[i] - out[i], reverse=True)
    for i in rest[: n - sum(out)]:
        out[i] += 1
    return out
