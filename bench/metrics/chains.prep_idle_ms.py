"""chains.prep_idle_ms: device idle time while the program's
``chains.prep`` or ``chains.dispatch`` span was the innermost host span
open, inside the traced window, per chain block, in ms (idle split at the
host spans' edges; profiler trace)."""
from bench import program_trace


def read(w):
    return program_trace.idle_ms(w, ("chains.prep", "chains.dispatch"))
