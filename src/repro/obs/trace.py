"""Structured tracing: nested spans with an injectable clock.

Design (DESIGN.md §13):

- One module-level active tracer (``_active``).  Instrumented code calls
  ``trace.span("subsystem/phase", **args)`` unconditionally; when no
  tracer is active the call returns a shared no-op context manager and
  does nothing else — the disabled path is one global load, one ``if``
  and a pre-allocated singleton, gated at ≤1% of a 50-tree GBT train by
  ``tests/test_obs.py::test_disabled_tracer_overhead_gate``.
- Span stacks are thread-local; finished top-level spans from every
  thread land in ``Tracer.roots`` (lock-protected), so lockstep RF
  blocks and server worker threads each get their own well-nested tree.
- The clock is injectable (``Tracer(clock=FakeClock().now)``), reusing
  the §9.3 pattern: span tests are deterministic and wall-clock-free.
- Spans survive exceptions: the ``with`` block closes the span on the
  error path too and tags it ``error=<ExcType>`` so a trace of a failed
  run shows *where* it died.
- While a tracer is active every span is also a
  ``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
  holds the library's spans on its host plane, nested, on the profiler's
  own clock. The ``Span`` record itself stays on the tracer's clock.
- While a tracer is active two hooks record what no call site can wrap:
  every garbage collection (``gc.callbacks``) as a ``runtime/gc`` span with
  ``generation=`` and ``collected=``, and every XLA compile or compile-cache
  load (JAX's ``/jax/core/compile/backend_compile_duration`` event) as a
  ``jax/compile`` span with ``fun_name=``. ``start()``/``capture()``
  install them and the return to no active tracer removes them, so nothing
  of them runs with tracing off.

Span names follow ``subsystem/phase`` (e.g. ``grower/gain_scan``,
``engines/dispatch``, ``checkpoint/save``); exporters group on the
full name and categorize on the prefix.
"""
from __future__ import annotations

import gc
import threading
from typing import Any, Callable, Dict, List, Optional

from . import clock as _clock

__all__ = ["Span", "Tracer", "span", "event", "capture", "enabled",
           "active", "start", "stop"]


class Span:
    """One timed phase: name, [t0, t1) in tracer-clock seconds, args,
    children. Plain attributes, no dataclass overhead on the hot path."""

    __slots__ = ("name", "t0", "t1", "args", "children", "tid")

    def __init__(self, name: str, t0: float, args: Dict[str, Any],
                 tid: str) -> None:
        self.name = name
        self.t0 = t0
        self.t1 = t0
        self.args = args
        self.children: List["Span"] = []
        self.tid = tid

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def walk(self):
        """Yield this span and every descendant, depth-first."""
        yield self
        for c in self.children:
            yield from c.walk()

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"name": self.name, "t0": self.t0,
                             "t1": self.t1, "tid": self.tid}
        if self.args:
            d["args"] = dict(self.args)
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, dur={self.duration:.6f}, "
                f"children={len(self.children)})")


class _SpanCtx:
    """Context manager that opens a Span on the calling thread's stack,
    inside a profiler annotation of the same name."""

    __slots__ = ("_tracer", "_span", "_ann")

    def __init__(self, tracer: "Tracer", name: str,
                 args: Dict[str, Any]) -> None:
        self._tracer = tracer
        self._span = tracer._open(name, args)
        self._ann = tracer._annotate(name)

    def __enter__(self) -> Span:
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self._span.args["error"] = exc_type.__name__
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._close(self._span)
        return False


class _NoopCtx:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NOOP_CTX = _NoopCtx()


class Tracer:
    """Collects well-nested spans per thread plus instant events."""

    def __init__(self, clock: Optional[Callable[[], float]] = None) -> None:
        self.clock: Callable[[], float] = clock or _clock.perf
        self.roots: List[Span] = []
        self.events: List[Dict[str, Any]] = []
        self._tls = threading.local()
        # reentrant: a collection's span may close while this thread holds it
        self._lock = threading.RLock()
        self._annotation = _annotation_type()

    # -- span lifecycle (called via _SpanCtx) --------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _open(self, name: str, args: Dict[str, Any]) -> Span:
        sp = Span(name, self.clock(), args, threading.current_thread().name)
        stack = self._stack()
        if stack:
            stack[-1].children.append(sp)
        stack.append(sp)
        return sp

    def _close(self, sp: Span) -> None:
        sp.t1 = self.clock()
        stack = self._stack()
        # Unwind to sp: exceptions that skipped inner __exit__ calls (or
        # a mis-nested close) must not leave orphans on the stack.
        while stack:
            top = stack.pop()
            if top is sp:
                break
            top.t1 = sp.t1
        if not stack:
            with self._lock:
                self.roots.append(sp)

    def _annotate(self, name: str):
        """An entered profiler annotation called ``name``, or None where
        JAX is not installed."""
        if self._annotation is None:
            return None
        ann = self._annotation(name)
        ann.__enter__()
        return ann

    def _record(self, name: str, t0: float, t1: float,
                args: Dict[str, Any]) -> None:
        """Add a span that has already ended, as a child of the calling
        thread's open span (or as a root)."""
        sp = Span(name, t0, args, threading.current_thread().name)
        sp.t1 = t1
        stack = self._stack()
        if stack:
            stack[-1].children.append(sp)
        else:
            with self._lock:
                self.roots.append(sp)

    def add_event(self, name: str, args: Dict[str, Any]) -> None:
        ev = {"name": name, "ts": self.clock(),
              "tid": threading.current_thread().name}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    # -- queries -------------------------------------------------------
    def span_count(self) -> int:
        return sum(1 for r in self.roots for _ in r.walk())

    def phase_names(self) -> List[str]:
        seen: Dict[str, None] = {}
        for r in self.roots:
            for s in r.walk():
                seen.setdefault(s.name, None)
        return list(seen)


def _annotation_type():
    """``jax.profiler.TraceAnnotation``, imported on first use so that
    ``obs`` stays importable without JAX; None without it."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


# ----------------------------------------------------------------------
# Hooks installed while a tracer is active: garbage collections and XLA
# compiles, which happen inside whatever span is open.
# ----------------------------------------------------------------------
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the spans the hooks record; a reader of a trace checks this to tell a
# window without collections or compiles from a program without the hooks
HOOK_SPANS = ("runtime/gc", "jax/compile")
_gc_open: List[Any] = []      # (tracer, span, annotation) of a collection


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        t = _active
        if t is not None:
            sp = t._open("runtime/gc", {"generation": info["generation"]})
            _gc_open.append((t, sp, t._annotate("runtime/gc")))
    elif _gc_open:
        t, sp, ann = _gc_open.pop()
        sp.args["collected"] = info["collected"]
        if ann is not None:
            ann.__exit__(None, None, None)
        t._close(sp)


def _on_compile(event: str, start: float, end: float, **kw: Any) -> None:
    """JAX reports the compile's bounds in wall-clock epoch seconds
    (``clock.wall``); they are moved onto the tracer's clock by the two
    clocks' offset now, just after the compile ended."""
    t = _active
    if event != _COMPILE_EVENT or t is None:
        return
    t1 = t.clock() - (_clock.wall() - end)
    t._record("jax/compile", t1 - (end - start), t1,
              {"fun_name": kw.get("fun_name")})


def _set_active(tracer: Optional["Tracer"]) -> None:
    """Make ``tracer`` the active one (caller holds ``_active_lock``), and
    install the hooks while one is active, remove them when none is."""
    global _active
    _active = tracer
    hooked = _on_gc in gc.callbacks
    if tracer is not None and not hooked:
        gc.callbacks.append(_on_gc)
        monitoring = _jax_monitoring()
        if monitoring is not None:
            monitoring.register_event_time_span_listener(_on_compile)
    elif tracer is None and hooked:
        gc.callbacks.remove(_on_gc)
        monitoring = _jax_monitoring()
        if monitoring is not None:
            monitoring.unregister_event_time_span_listener(_on_compile)


def _jax_monitoring():
    try:
        from jax import monitoring
    except ImportError:
        return None
    return monitoring


# ----------------------------------------------------------------------
# Module-level active tracer.  ``span``/``event`` are the only functions
# instrumented code should call; everything else is test/tooling surface.
# ----------------------------------------------------------------------
_active: Optional[Tracer] = None
_active_lock = threading.Lock()


def span(name: str, **args: Any):
    """Open a span if tracing is on; otherwise return the no-op ctx."""
    t = _active
    if t is None:
        return _NOOP_CTX
    return _SpanCtx(t, name, args)


def event(name: str, **args: Any) -> None:
    """Record an instant event (worker death, rollback, circuit open)."""
    t = _active
    if t is not None:
        t.add_event(name, args)


def enabled() -> bool:
    return _active is not None


def active() -> Optional[Tracer]:
    return _active


def start(clock: Optional[Callable[[], float]] = None) -> Tracer:
    """Install a fresh active tracer and return it (idempotent stop via
    ``stop()``). Prefer ``capture()`` unless you need manual control."""
    tracer = Tracer(clock=clock)
    with _active_lock:
        _set_active(tracer)
    return tracer


def stop() -> None:
    with _active_lock:
        _set_active(None)


class capture:
    """``with trace.capture() as tracer:`` — scoped tracing.

    Restores the previously active tracer on exit so captures nest; the
    inner capture sees only its own spans.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None) -> None:
        self._clock = clock
        self._prev: Optional[Tracer] = None
        self.tracer: Optional[Tracer] = None

    def __enter__(self) -> Tracer:
        with _active_lock:
            self._prev = _active
            self.tracer = Tracer(clock=self._clock)
            _set_active(self.tracer)
        return self.tracer

    def __exit__(self, exc_type, exc, tb) -> bool:
        with _active_lock:
            _set_active(self._prev)
        return False
