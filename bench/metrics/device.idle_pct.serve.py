"""device.idle_pct.serve: 100 x (1 - device busy / window) over the traced
window, busy being the union of the intervals in which an operation ran on
the device (profiler trace)."""


def read(w):
    if w.mode != "sessions" or w.trace is None or w.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
