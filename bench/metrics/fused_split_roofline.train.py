"""fused_split_roofline.train: the least time of the histogram levels of
every tree grown in the window (``work.tree``) over the device time of the
fused split kernel's events in it, in percent. Nothing when the kernel did
not run."""
import work


def read(r):
    kernel_s = r.kernel_s("fused_split")
    levels = r.layer.get("level_rows")
    if kernel_s <= 0 or not levels:
        return None
    w = work.NOTHING
    for level_rows in levels:
        w = w + work.tree(level_rows, r.layer["features"])
    return work.share_pct(w, kernel_s, r.peaks)
