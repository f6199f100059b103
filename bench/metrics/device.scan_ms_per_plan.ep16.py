"""Device busy time in the traced window per plan served in it, in the
expert-parallel step cell."""


def read(m):
    if m.summary is None or not m.plans or m.summary.busy_s <= 0:
        return None
    return 1e3 * m.summary.busy_s / m.plans
