"""Alpha points simulated per plan (``Plan.replay``: full + resumed)."""


def read(m):
    if not m.plans or "sims" not in m.outcome.counters:
        return None
    return m.outcome.counters["sims"] / m.plans
