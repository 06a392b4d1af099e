"""explorer.gap_ms: host wall between consecutive chain blocks of one
search (reconcile, history, the next request), mean in ms."""


def read(w):
    if w.mode != "searches":
        return None
    by_search = {}
    for name, t0, t1, tag in w.spans:
        if name == "chains.block":
            by_search.setdefault(tag, []).append((t0, t1))
    gaps = [b[0] - a[1] for blocks in by_search.values()
            for a, b in zip(blocks, blocks[1:])]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
