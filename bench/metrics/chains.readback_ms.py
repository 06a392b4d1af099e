"""chains.readback_ms: host time in the program's ``chains.readback`` span
(the copies of a block's outputs and carry to the host, after the device
has finished) inside the traced window, per chain block, in ms (the span as
the profiler's host plane holds it)."""
from bench import program_trace


def read(w):
    return program_trace.span_ms(w, ("chains.readback",))
