"""setup_s: process start to the opening of the measured window (host
clock): JAX and TPU start-up, building the graph and budget, compile-cache
loads or compiles, and the warm-up of every shape the cell reaches."""


def read(w):
    return w.setup_s
