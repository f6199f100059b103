"""Megabytes fetched from the device per plan (the program's counter
``backend.d2h_bytes``: the scan's padded outputs)."""


def read(m):
    got = m.outcome.counters.get("d2h_bytes")
    if got is None or not m.plans:
        return None
    return got / 1e6 / m.plans
