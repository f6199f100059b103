"""Host time staging, launching and fetching the scan, per plan
(benchmark span ``bench.backend.dispatch``)."""


def read(m):
    s = m.span_s("bench.backend.dispatch")
    if s is None or not m.plans:
        return None
    return 1e3 * s / m.plans
