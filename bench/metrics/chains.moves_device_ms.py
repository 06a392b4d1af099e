"""chains.moves_device_ms: device time of the leaf operations that ran
under the program's ``chain.sample``, ``chain.apply`` and ``chain.accept``
scopes (move sampling, move application, SA accept) inside the traced
window, per chain block, in ms (profiler trace)."""
from bench import program_trace


def read(w):
    return program_trace.scope_ms(w, ("chain.sample", "chain.apply", "chain.accept"))
