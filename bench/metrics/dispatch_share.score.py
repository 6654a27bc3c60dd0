"""dispatch_share.score: percent of the window inside the library's
``engines/dispatch`` spans: the engine and the per-tree copy to the host."""


def read(r):
    s = r.span_s("engines/dispatch")
    if s <= 0:
        return None
    return 100.0 * s / (r.t1 - r.t0)
