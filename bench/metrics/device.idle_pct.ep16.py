"""Share of the traced window in which no operation ran on the device,
in the expert-parallel step cell."""


def read(m):
    if m.summary is None:
        return None
    return m.summary.idle_pct
