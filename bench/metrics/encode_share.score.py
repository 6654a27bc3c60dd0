"""encode_share.score: percent of the window inside the benchmark's own
``bench/encode`` spans around ``CompiledPredictor.encode``."""


def read(r):
    s = r.span_s("bench/encode", source="bench")
    if s <= 0:
        return None
    return 100.0 * s / (r.t1 - r.t0)
