"""The expert-parallel step sweeps' least time on this chip
(``benchlib.roofline``: the algorithm's operations and bytes over the
chip's peaks) as a share of the device busy time spent on them; says
which peak bounds it."""
from benchlib import roofline


def read(m):
    work = m.outcome.work
    if m.summary is None or not work or m.summary.busy_s <= 0:
        return None
    t, bound = roofline.least_time(work[0], work[1], m.device_kind)
    return 100.0 * t / m.summary.busy_s, {"bound": bound}
