"""gc_share.train: percent of the window inside the library's
``runtime/gc`` spans, one per garbage collection while the tracer is on.
A window with no collection reads 0; a program without the hook reads
nothing."""
from hook_spans import recorded


def read(r):
    if not recorded("runtime/gc"):
        return None
    return 100.0 * r.span_s("runtime/gc") / (r.t1 - r.t0)
