"""chains.block_ms: mean host span around backend.run_chains in a chain
search (one (R, K) block; the span ends when the block's output is on the
host), in ms."""


def read(w):
    if w.mode != "searches":
        return None
    d = [t1 - t0 for name, t0, t1, _ in w.spans if name == "chains.block"]
    return 1e3 * sum(d) / len(d) if d else None
