"""level_step_ms.train: ms per tree inside the device grower's level steps,
from the library's ``grower_device/level_step`` spans in the window. Under
the span tracer each step blocks until the device is done, so a span holds
the step's device time too. A step that compiled in the window is counted
in ``compiles_in_window`` and reported on standard error by the harness."""


def read(r):
    trees = r.layer.get("trees", 0)
    steps = r.spans_named("grower_device/level_step")
    if not trees or not steps:
        return None
    r.layer["compiles_in_window"] = sum(
        1 for s in steps if s[3].get("compile"))
    return r.span_s("grower_device/level_step") * 1e3 / trees
