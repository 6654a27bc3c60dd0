"""compiles.score: XLA compiles and compile-cache loads that begin in the
window, from the library's ``jax/compile`` spans (JAX's backend compile
event); the target is 0. A program without the hook reads nothing."""
from hook_spans import recorded


def read(r):
    if not recorded("jax/compile"):
        return None
    return len(r.spans_named("jax/compile"))
