"""Reduction of a ``jax.profiler`` trace to device busy time, kernel time,
idle gaps and host spans, all on the profiler's one clock.

A TPU trace has one plane per chip (``/device:TPU:<i>``) whose ``XLA Ops``
line holds every operation the chip ran, and a host plane (``/host:CPU``)
whose lines hold the host's ``TraceAnnotation`` spans. Times are
nanoseconds on the profiler's clock. Nothing here imports the library under
test; ``load`` needs only JAX's ``ProfileData`` reader, and the reduction
works on plain ``Event`` tuples so it is tested on small recorded traces.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"

# Where each kernel of the library shows up in the trace: the prefix of its
# operation's name on the ``XLA Ops`` line (the name of the function given
# to ``pallas_call``). One table, so a later rename is one edit here.
KERNELS = {
    "fused_split": "%fused_split_pallas",
    "forest_infer": "%forest_predict_pallas_tiled",
    "histogram": "%histogram_pallas",
}


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane name -> [Event] ops
    host: list = field(default_factory=list)      # [Event] host spans


def load(directory: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    pd = ProfileData.from_file(files[-1])
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.devices[plane.name] = [
                        Event(ev.name, float(ev.start_ns),
                              float(ev.duration_ns)) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(Event(ev.name, float(ev.start_ns),
                                     float(ev.duration_ns))
                               for ev in line.events)
    return tr


def clip(events, t0: float, t1: float) -> list:
    """Events cut to the window [t0, t1); those outside it dropped."""
    out = []
    for e in events:
        s, t = max(e.start_ns, t0), min(e.end_ns, t1)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events) -> list:
    """Merged [start, end) intervals covered by ``events``."""
    iv = sorted((e.start_ns, e.end_ns) for e in events)
    out = []
    for s, t in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_ns(events, t0: float, t1: float) -> float:
    return sum(t - s for s, t in union(clip(events, t0, t1)))


def kernel_events(events, kernel: str) -> list:
    prefix = KERNELS[kernel]
    return [e for e in events if e.name.startswith(prefix)]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the short
    name of an operation, for the breakdown."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def top_ops(events, t0: float, t1: float, k: int = 10) -> list:
    """The ``k`` operations (by short name) with the most device time."""
    agg: dict = {}
    for e in clip(events, t0, t1):
        n = op_name(e.name)
        agg[n] = agg.get(n, 0.0) + e.dur_ns
    return [[n, v / 1e9] for n, v in
            sorted(agg.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(events, host_spans, t0: float, t1: float, k: int = 10) -> list:
    """The ``k`` longest device-idle gaps in the window, each labelled by
    what the host was doing in it: the innermost host span that covers at
    least half of the gap, else the span that covers most of it, else "no
    host span"."""
    busy = union(clip(events, t0, t1))
    gaps, cur = [], t0
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if cur < t1:
        gaps.append((cur, t1))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    return [[label_gap(host_spans, s, t), (t - s) / 1e9]
            for s, t in gaps[:k]]


def label_gap(host_spans, s: float, t: float) -> str:
    cover = [(min(h.end_ns, t) - max(h.start_ns, s), h) for h in host_spans
             if h.start_ns < t and h.end_ns > s]
    if not cover:
        return "no host span"
    half = [h for o, h in cover if o >= 0.5 * (t - s)]
    if half:
        return min(half, key=lambda h: h.dur_ns).name
    return max(cover, key=lambda oh: oh[0])[1].name


def find_host_span(host, name: str) -> Event | None:
    """The longest host span called ``name``."""
    hits = [h for h in host if h.name == name]
    return max(hits, key=lambda h: h.dur_ns) if hits else None


def clip_spans(spans, t0: float, t1: float) -> list:
    """(name, start, end, ...) host-clock spans cut to [t0, t1)."""
    out = []
    for s in spans:
        a, b = max(s[1], t0), min(s[2], t1)
        if b > a:
            out.append((s[0], a, b))
    return out


@dataclass
class Reading:
    """Everything a per-layer reader may read from one traced run: the
    driver's counts (``layer``), host spans of the benchmark and of the
    library on the host clock (seconds), and the device trace of the
    window on the profiler's clock (nanoseconds)."""
    layer: dict
    config: dict
    peaks: dict
    t0: float                  # window, host clock (s)
    t1: float
    bench_spans: list          # (name, t0, t1)
    obs_spans: list            # (name, t0, t1, args)
    ops: dict                  # device plane -> [Event] in the window
    w0_ns: float               # window, profiler clock (ns)
    w1_ns: float
    offset_ns: float           # profiler ns = host s * 1e9 + offset_ns

    @classmethod
    def from_run(cls, ctx, out, device) -> "Reading":
        win = out.window
        tr = load(win.trace_dir)
        anchor = find_host_span(tr.host, win.NAME)
        if anchor is None:
            raise FileNotFoundError(f"no {win.NAME!r} span in the trace")
        w0, w1 = anchor.start_ns, anchor.end_ns
        planes = sorted(tr.devices)[:ctx.chips]
        ops = {p: clip(tr.devices[p], w0, w1) for p in planes}
        return cls(layer=out.layer, config=ctx.config,
                   peaks=device["peaks"], t0=win.t0, t1=win.t1,
                   bench_spans=list(ctx.spans),
                   obs_spans=list(out.layer.get("obs_spans", [])),
                   ops=ops, w0_ns=w0, w1_ns=w1,
                   offset_ns=w0 - win.t0 * 1e9)

    @property
    def window_s(self) -> float:
        """Length of the traced window."""
        return (self.w1_ns - self.w0_ns) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran on a chip, averaged over the
        chips used."""
        if not self.ops:
            return 0.0
        return sum(busy_ns(ev, self.w0_ns, self.w1_ns)
                   for ev in self.ops.values()) / len(self.ops) / 1e9

    def idle_pct(self) -> float | None:
        if not self.ops or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of ``kernel`` (a key of KERNELS) in the window,
        summed over its events and the chips."""
        return sum(e.dur_ns for ev in self.ops.values()
                   for e in kernel_events(ev, kernel)) / 1e9

    def span_s(self, name: str, source: str = "obs") -> float:
        """Host seconds inside spans called ``name`` within the window:
        the library's (``obs``) or the benchmark's own (``bench``)."""
        spans = self.obs_spans if source == "obs" else self.bench_spans
        return sum(b - a for _, a, b in
                   clip_spans([s for s in spans if s[0] == name],
                              self.t0, self.t1))

    def spans_named(self, name: str) -> list:
        """The library's spans called ``name`` that begin in the window."""
        return [s for s in self.obs_spans
                if s[0] == name and self.t0 <= s[1] < self.t1]

    def host_events(self) -> list:
        """Both kinds of host span as Events on the profiler's clock."""
        return [Event(s[0], s[1] * 1e9 + self.offset_ns,
                      (s[2] - s[1]) * 1e9)
                for s in self.bench_spans + self.obs_spans]

    def breakdown(self) -> dict:
        ev = next(iter(self.ops.values()), [])
        return {"device_ops": top_ops(ev, self.w0_ns, self.w1_ns),
                "idle_gaps": idle_gaps(ev, self.host_events(),
                                       self.w0_ns, self.w1_ns)}
