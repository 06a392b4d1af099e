"""sessions_per_s: sessions that finished inside the window, over the
window's length (host clock)."""


def read(w):
    if w.mode != "sessions" or w.outcome.window_s <= 0:
        return None
    return w.outcome.completed_in_window / w.outcome.window_s
