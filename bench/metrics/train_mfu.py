"""train_mfu: the least time of the histogram levels of every tree grown in
the window (``work.tree``) over the window, in percent: the whole step's
share of the chip's peak, whatever kernels run it."""
import work


def read(r):
    if not r.ops:          # no chip was traced: no share of its peak
        return None
    levels = r.layer.get("level_rows")
    if not levels:
        return None
    w = work.NOTHING
    for level_rows in levels:
        w = w + work.tree(level_rows, r.layer["features"])
    return work.share_pct(w, r.window_s, r.peaks)
