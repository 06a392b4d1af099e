"""phase_sim_roofline.chains: the least time the phase simulation of every
design priced in the traced window could take on this chip (bench/roofline.py:
bytes at real widths over HBM bandwidth, or operations over peak rate,
whichever is larger) as a percentage of the device's busy time in the
window (profiler trace)."""
from bench import roofline


def read(w):
    if w.mode != "searches" or w.trace is None or w.work["designs"] == 0:
        return None
    if w.trace["busy_s"] <= 0:
        return None
    share, _ = roofline.roofline_share(w.work["bytes"], w.work["ops"],
                                       w.trace["busy_s"], w.device_kind)
    return share
