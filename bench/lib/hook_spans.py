"""Whether the library records a runtime hook's span: the names in
``repro.obs.trace.HOOK_SPANS``. A reader of such a span reads nothing from a
program without the hook, and 0 from a window in which the hook fired
never."""
import program  # noqa: F401  (puts the library on the import path)


def recorded(name: str) -> bool:
    from repro.obs import trace
    return name in getattr(trace, "HOOK_SPANS", ())
