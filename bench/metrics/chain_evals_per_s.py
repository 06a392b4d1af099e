"""chain_evals_per_s: designs priced by the chain blocks that completed in
the window (R x K per block), over the window's wall time (host clock, from
its opening to the end of the block that closed it)."""


def read(w):
    if w.mode != "searches" or w.outcome.window_s <= 0:
        return None
    return w.outcome.evals / w.outcome.window_s
