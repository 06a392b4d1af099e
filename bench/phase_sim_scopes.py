"""The phase simulator's own device scopes inside a chain step's pricing.

The XLA phase simulator (``phase_sim_jax.simulate_one``) opens
``phase_sim.setup`` (the loop-invariant hoists: co-residency masks,
one-hots, routes) and ``phase_sim.phases`` (the phase loop). In a chain
step they nest inside ``chain.price``, and since the simulator runs under
``vmap`` the path names them ``vmap(<scope>)``:
``jit(block)/while/body/closed_call/chain.price/vmap(phase_sim.phases)/...``.

``program_trace.of`` gives each operation the first of the program's
scopes its path holds, which is ``chain.price`` for both; so this module
loads the same trace once more through ``program_trace.load``, against a
scope list of its own that names the two under ``chain.price``. Candidates
priced outside a chain step carry no ``chain.price`` and are not counted.

Where the program opens neither scope (a checkout older than them, or the
Pallas kernel asked for by name), every reading is None.
"""
from __future__ import annotations

from typing import Optional

from bench import drive, program_trace, traces

# each scope as a chain step's path names it: under chain.price, inside a vmap
PATHS = {s: f"chain.price/vmap({s})" for s in ("phase_sim.setup", "phase_sim.phases")}


def scope_ms(w, scope: str) -> Optional[float]:
    """Leaf device time under ``chain.price`` and the phase simulator's
    ``scope``, per chain block of the traced window, in ms."""
    if w.mode != "searches" or w.trace is None or not drive._RECORDERS:
        return None
    names = program_trace.program_names()
    rec = drive._RECORDERS[-1]
    if names is None or rec.trace_dir is None or not program_trace.n_blocks(w):
        return None
    try:
        path = traces.find_xplane(rec.trace_dir)
    except FileNotFoundError:
        return None
    ev = program_trace.load(path, names[0], tuple(PATHS.values()))
    if not ev["devices"]:
        return None
    if "scope_time" not in ev:
        w0, w1 = program_trace.window(ev["host"])
        ev["scope_time"] = program_trace.scope_time(ev["devices"], w0, w1)
    if not any(k is not None for k in ev["scope_time"]):
        return None
    return 1e3 * ev["scope_time"].get(PATHS[scope], 0.0) / program_trace.n_blocks(w)
