"""phase_sim.setup_ms.chains: device time of the leaf operations that ran
under the phase simulator's ``phase_sim.setup`` scope inside a chain step's
``chain.price`` (the loop-invariant co-residency masks, one-hot maps and
routes of each step's candidates) inside the traced window, per chain
block, in ms (profiler trace)."""
from bench import phase_sim_scopes


def read(w):
    return phase_sim_scopes.scope_ms(w, "phase_sim.setup")
