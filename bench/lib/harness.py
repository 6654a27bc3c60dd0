"""What every driver shares: the run's context, benchmark-side spans on the
profiler's clock, the measured window, and the shape of a driver's outcome."""
from __future__ import annotations

import contextlib
import math
import os
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np


class RunError(RuntimeError):
    """The run cannot measure this cell here: no chip, too few chips, a
    device missing from the peaks table, or a path that fell back from the
    one the cell measures. The run prints no result."""


@dataclass
class Ctx:
    cell: str
    config: dict
    params: dict          # the traffic mix's parameters, then the cell's
    limits: dict          # the cell's correctness limits
    seed: int
    seconds: float
    trace: bool
    t_start: float        # perf_counter when the process began
    chips: int = 1
    tmp: str = field(default_factory=lambda: tempfile.mkdtemp(
        prefix="bench-"))
    spans: list = field(default_factory=list)    # (name, t0, t1) perf

    def rng(self, stream: int) -> np.random.Generator:
        """An independent generator for one use of the seed."""
        return np.random.default_rng(self.stream_seed(stream))

    def stream_seed(self, stream: int) -> list:
        return [int(self.seed), int(stream)]

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark-side span around a call into a layer: kept in
        memory, and written to the profiler's trace while tracing."""
        t0 = time.perf_counter()
        try:
            if self.trace:
                import jax
                with jax.profiler.TraceAnnotation(name):
                    yield
            else:
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))


class Window:
    """The measured window: its host-clock bounds and, while ``trace`` is
    on, the profiler trace and the library's span tracer around it."""

    NAME = "bench/window"

    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        self.t0 = self.t1 = None
        self.trace_dir = os.path.join(ctx.tmp, "trace")
        self._ann = None

    def open(self) -> float:
        if self.ctx.trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(self.NAME)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self.t0

    def close(self, t1: float | None = None) -> float:
        self.t1 = time.perf_counter() if t1 is None else t1
        if self.ctx.trace:
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return self.t1


@dataclass
class Check:
    """One number compared with its limit: the run is correct only if
    ``value <= limit`` for every check."""
    name: str
    value: float
    limit: float

    def __post_init__(self):
        # a comparison that found nothing (or overflowed) fails, and is
        # printed as a finite number so the result line stays JSON
        v = float(self.value)
        self.value = v if math.isfinite(v) else 1e300

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class Outcome:
    setup_s: float
    window: Window
    metrics: dict                         # end-to-end name -> value
    checks: list                          # [Check]
    attempted: int
    failed: int
    layer: dict = field(default_factory=dict)   # data for per-layer readers
    notes: list = field(default_factory=list)   # stderr lines


def norm_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """|(|prog| - |ref|)| / |ref|: the gap between two norms, measured
    against the reference's."""
    r = float(np.linalg.norm(ref))
    return abs(float(np.linalg.norm(prog)) - r) / max(r, 1e-300)
