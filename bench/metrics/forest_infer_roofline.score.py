"""forest_infer_roofline.score: the least time of scoring every row the
window returned (``work.scoring``, once per call) over the device time of
the forest kernel's events in it, in percent."""
import work


def read(r):
    kernel_s = r.kernel_s("forest_infer")
    if kernel_s <= 0 or not r.layer.get("calls"):
        return None
    return work.share_pct(_window_work(r.layer), kernel_s, r.peaks)


def _window_work(layer):
    one = work.scoring(layer["rows"], layer["features"], layer["trees"],
                       layer["depth"], layer["out_dim"],
                       layer.get("categorical_nodes", 0))
    return work.Work(one.ops * layer["calls"], one.bytes * layer["calls"])
