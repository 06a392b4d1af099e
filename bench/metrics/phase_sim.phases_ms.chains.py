"""phase_sim.phases_ms.chains: device time of the leaf operations that ran
under the phase simulator's ``phase_sim.phases`` scope inside a chain
step's ``chain.price`` (the phase loop over every task of each step's
candidates) inside the traced window, per chain block, in ms (profiler
trace)."""
from bench import phase_sim_scopes


def read(w):
    return phase_sim_scopes.scope_ms(w, "phase_sim.phases")
