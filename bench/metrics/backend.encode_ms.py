"""backend.encode_ms: host encode time per candidate dispatch of the
backend (BackendStats.encode_s over n_dispatches, both as deltas over the
window), in ms."""


def read(w):
    n = w.after.get("n_dispatches", 0) - w.before.get("n_dispatches", 0)
    if w.mode != "sessions" or n <= 0:
        return None
    return 1e3 * (w.after["encode_s"] - w.before["encode_s"]) / n
