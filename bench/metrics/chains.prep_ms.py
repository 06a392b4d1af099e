"""chains.prep_ms: host time in the program's ``chains.prep`` span (encode,
move table, budget row, jit lookup, fresh carry: everything before the
jitted block is called) inside the traced window, per chain block, in ms
(the span as the profiler's host plane holds it)."""
from bench import program_trace


def read(w):
    return program_trace.span_ms(w, ("chains.prep",))
