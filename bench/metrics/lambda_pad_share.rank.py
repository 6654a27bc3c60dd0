"""lambda_pad_share.rank: percent of the (p, j) pair slots the lambda pass
evaluated that held no real pair touching the top k (padding slots, and
pairs counted from their other member), from the ``pair_slots`` and
``pairs`` counters of the library's ``ranking/lambda`` spans in the
window. Nothing from a program without the span."""


def read(r):
    spans = r.spans_named("ranking/lambda")
    slots = sum(s[3].get("pair_slots", 0) for s in spans)
    if not slots:
        return None
    pairs = sum(s[3].get("pairs", 0) for s in spans)
    return 100.0 * (slots - pairs) / slots
