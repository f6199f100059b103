"""Device launches per plan (the backends' ``n_launches`` counters)."""


def read(m):
    if not m.plans:
        return None
    return m.probe.counter("launches") / m.plans
