"""fetch_ms.train: ms per tree fetching the grown tree from the device and
decoding it into the host forest, from ``grower_device/fetch`` spans."""


def read(r):
    trees = r.layer.get("trees", 0)
    if not trees or not r.spans_named("grower_device/fetch"):
        return None
    return r.span_s("grower_device/fetch") * 1e3 / trees
