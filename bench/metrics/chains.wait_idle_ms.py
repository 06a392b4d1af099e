"""chains.wait_idle_ms: device idle time while the program's
``chains.wait`` span was the innermost host span open (the block is
dispatched and the host waits on its outputs, so idle here is a launch
stall on the device side), inside the traced window, per chain block, in
ms (idle split at the host spans' edges; profiler trace)."""
from bench import program_trace


def read(w):
    return program_trace.idle_ms(w, ("chains.wait",))
