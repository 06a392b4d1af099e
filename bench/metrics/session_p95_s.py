"""session_p95_s: 95th percentile (nearest rank) of session latency over
every session due in the window, each timed from its due time to the end of
the tick it finished in (host clock). A session that failed or had not
finished by the drain limit counts at that limit."""
from bench import stats


def read(w):
    if w.mode != "sessions" or not w.outcome.latencies:
        return None
    return stats.percentile(w.outcome.latencies, 95)
