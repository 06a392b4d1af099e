"""serve.tick_ms: mean host span of one DseService.step() (one scheduler
tick) in the window, in ms."""


def read(w):
    d = [t1 - t0 for name, t0, t1, _ in w.spans if name == "serve.tick"]
    return 1e3 * sum(d) / len(d) if d else None
