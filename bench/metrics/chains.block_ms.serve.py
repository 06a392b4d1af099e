"""chains.block_ms.serve: mean host span around backend.run_chains for the
chain sessions a scheduler tick dispatches (one block per session), in ms."""


def read(w):
    if w.mode != "sessions":
        return None
    d = [t1 - t0 for name, t0, t1, _ in w.spans if name == "chains.block"]
    return 1e3 * sum(d) / len(d) if d else None
