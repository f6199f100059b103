"""Host time decoding fetched scan results into schedules, per plan
(benchmark span ``bench.engine.decode``)."""


def read(m):
    s = m.span_s("bench.engine.decode")
    if s is None or not m.plans:
        return None
    return 1e3 * s / m.plans
