"""Hop bodies the device runs per plan (the program's counter
``backend.hop_steps``: padded alphas x waves x slots x predecessors x
routes x hops of each dispatch)."""


def read(m):
    got = m.outcome.counters.get("hop_steps")
    if got is None or not m.plans:
        return None
    return got / m.plans
