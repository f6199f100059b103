"""Host time building the device backend and its scan tables, per plan
(the program's spans ``repro.backend.build`` and
``repro.backend.tables``)."""


def read(m):
    got = m.outcome.counters.get("tables_s")
    if got is None or not m.plans:
        return None
    return 1e3 * got / m.plans
