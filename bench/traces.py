"""From a profiler trace to device busy time, idle share, the top device
operations and the idle gaps, each gap named by the host span open in it.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event lists; :func:`reduce` works on those lists alone, so it can be
checked on events made by hand as well as on a recorded trace.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]  # (name, start_ns, duration_ns)

# host spans the benchmark opens around calls into the program's layers
HOST_SPANS = ("serve.tick", "backend.evaluate", "chains.block", "search")
WINDOW_SPAN = "bench.window"
# the device line whose events are the operations that ran
OP_LINES = ("XLA Ops", "XLA Modules")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Dict[str, object]:
    """Device operations per device plane and host spans, as event lists."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            for want in OP_LINES:
                if want in lines:
                    devices[plane.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in lines[want].events
                    ]
                    break
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS or e.name == WINDOW_SPAN:
                        host.append((e.name, int(e.start_ns), int(e.duration_ns)))
    return {"devices": devices, "host": host}


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _label(t_ns: int, host: Sequence[Event]) -> str:
    """The innermost host span open at ``t_ns``; ``host.other`` if none."""
    best, best_len = "host.other", None
    for name, s, d in host:
        if name == WINDOW_SPAN or not (s <= t_ns < s + d):
            continue
        if best_len is None or d < best_len:
            best, best_len = name, d
    if best == "search":  # inside a search, between its blocks
        return "explorer.gap"
    return best


def reduce(devices: Dict[str, List[Event]], host: List[Event], top: int = 10) -> dict:
    """Busy and idle time of the devices over the window span.

    The window is the host span named ``bench.window``. Busy is the union
    of the intervals in which an operation ran, clipped to the window, and
    averaged over the device planes. ``device_ops`` lists the operations
    that took the most device time; ``idle_gaps`` the idle time, summed by
    the host span that was open in each gap (at its midpoint), on the
    first device."""
    wins = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if not wins or not devices:
        raise ValueError("trace has no window span or no device plane")
    w0, w1 = wins[0]
    busy_total = 0.0
    gaps: Dict[str, float] = {}
    op_time: Dict[str, float] = {}
    for k, (plane, events) in enumerate(sorted(devices.items())):
        clipped = []
        for name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                op_time[name] = op_time.get(name, 0.0) + (b - a) * 1e-9
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        if k == 0:
            edge = w0
            for a, b in busy + [(w1, w1)]:
                if a > edge:
                    lab = _label((edge + a) // 2, host)
                    gaps[lab] = gaps.get(lab, 0.0) + (a - edge) * 1e-9
                edge = max(edge, b)
    window_s = (w1 - w0) * 1e-9
    ranked = lambda d: [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": busy_total / len(devices),
        "window_s": window_s,
        "device_ops": ranked(op_time),
        "idle_gaps": ranked(gaps),
    }
