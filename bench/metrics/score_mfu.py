"""score_mfu: the least time of scoring every row the window returned
(``work.scoring``, once per call) over the window, in percent."""
import work


def read(r):
    if not r.ops:          # no chip was traced: no share of its peak
        return None
    layer = r.layer
    if not layer.get("calls"):
        return None
    one = work.scoring(layer["rows"], layer["features"], layer["trees"],
                       layer["depth"], layer["out_dim"],
                       layer.get("categorical_nodes", 0))
    w = work.Work(one.ops * layer["calls"], one.bytes * layer["calls"])
    return work.share_pct(w, r.window_s, r.peaks)
