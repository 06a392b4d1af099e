"""In-loop re-simulation for the device-resident explorer.

The fused accept loop (``repro.core.device_explore``) prices its R mutated
chain encodings inside each ``lax.scan`` step through ``resimulate_chains``.
It reads ``simulate_batch`` as this module's own name so that a test can
patch the simulator the chain step prices through (the benchmark's fault
test does).

Mixed mapping+allocation chains price through the same call: allocation
moves are shape-preserving over capacity-padded slot inventories, so every
step hands this function an (R,)-rows dict, with ``pe_active``/
``mem_active`` masks pricing inactive slots as absent.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

from ...core.phase_sim_jax import EncodedWorkload, simulate_batch

__all__ = ["resimulate_chains"]


def resimulate_chains(  # repro: traced
    enc: EncodedWorkload, rows: Dict[str, jnp.ndarray]
) -> Dict[str, jnp.ndarray]:
    """Price the R mutated chain encodings of one accept-loop iteration
    (``rows`` has the chain axis leading)."""
    return simulate_batch(enc, rows)
