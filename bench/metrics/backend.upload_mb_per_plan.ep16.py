"""Megabytes uploaded to the device per plan (the program's counter
``backend.h2d_bytes``: the set-up and scan tables and the staged
inputs)."""


def read(m):
    got = m.outcome.counters.get("h2d_bytes")
    if got is None or not m.plans:
        return None
    return got / 1e6 / m.plans
