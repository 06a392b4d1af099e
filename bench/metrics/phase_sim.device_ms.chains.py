"""phase_sim.device_ms.chains: device time of the leaf operations that ran
under the program's ``chain.price`` scope (the phase simulation of each
step's candidates, Pallas kernel or XLA path) inside the traced window, per
chain block, in ms (profiler trace)."""
from bench import program_trace


def read(w):
    return program_trace.scope_ms(w, ("chain.price",))
