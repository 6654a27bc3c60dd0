"""padding_share.serve: percent of the rows the server dispatched in the
window that were padding, from ServerMetrics' ``rows_padded`` and
``rows_dispatched``."""


def read(r):
    real, pad = r.layer.get("rows_dispatched", 0), r.layer.get(
        "rows_padded", 0)
    if real + pad <= 0:
        return None
    return 100.0 * pad / (real + pad)
