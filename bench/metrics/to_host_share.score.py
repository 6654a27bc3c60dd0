"""to_host_share.score: percent of the window inside the library's
``engines/to_host`` spans: the copy of each call's (rows, trees, 1)
per-tree output from the device to the host."""


def read(r):
    s = r.span_s("engines/to_host")
    if s <= 0:
        return None
    return 100.0 * s / (r.t1 - r.t0)
