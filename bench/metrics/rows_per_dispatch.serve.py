"""rows_per_dispatch.serve: real rows per engine dispatch in the window,
from ServerMetrics' ``rows_dispatched`` and ``dispatches``."""


def read(r):
    n = r.layer.get("dispatches", 0)
    if not n:
        return None
    return r.layer["rows_dispatched"] / n
