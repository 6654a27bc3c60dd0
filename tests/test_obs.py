"""Observability layer (DESIGN.md §13): tracer, metrics registry,
exporters, training_logs schema — plus the disabled-path overhead gate.

Span-tree tests run on ``serving.faults.FakeClock`` (§9.3 pattern):
every duration below is exact, no wall clock involved.
"""
from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from repro.core.api import YdfError
from repro.obs import metrics as obs_metrics
from repro.obs import trace
from repro.obs.export import (chrome_trace, phase_summary, profile_dict,
                              validate_chrome_trace)
from repro.obs.logs import (REQUIRED_KEYS, build_training_logs,
                            summarize_training_logs, validate_training_logs)
from repro.serving.faults import FakeClock

pytestmark = pytest.mark.obs


# ------------------------------------------------------------------ tracer

def test_span_nesting_fake_clock():
    ck = FakeClock()
    with trace.capture(clock=ck.now) as tr:
        with trace.span("train/outer", trees=3):
            ck.advance(1.0)
            with trace.span("grower/inner"):
                ck.advance(0.25)
            ck.advance(0.5)
    assert len(tr.roots) == 1
    outer = tr.roots[0]
    assert outer.name == "train/outer"
    assert outer.args == {"trees": 3}
    assert outer.duration == pytest.approx(1.75)
    (inner,) = outer.children
    assert inner.name == "grower/inner"
    assert inner.t0 == pytest.approx(1.0)
    assert inner.duration == pytest.approx(0.25)
    assert tr.span_count() == 2
    assert tr.phase_names() == ["train/outer", "grower/inner"]


def test_span_exception_unwinding():
    ck = FakeClock()
    with trace.capture(clock=ck.now) as tr:
        with pytest.raises(RuntimeError):
            with trace.span("a"):
                ck.advance(1.0)
                with trace.span("b"):
                    ck.advance(1.0)
                    raise RuntimeError("boom")
    a = tr.roots[0]
    (b,) = a.children
    # both spans closed despite the exception, and the failing one is tagged
    assert b.args["error"] == "RuntimeError"
    assert a.args["error"] == "RuntimeError"
    assert a.t1 == b.t1 == pytest.approx(2.0)
    # the thread-local stack fully unwound: a new span is a fresh root
    with trace.capture(clock=ck.now) as tr2:
        with trace.span("c"):
            pass
    assert [r.name for r in tr2.roots] == ["c"]


def test_span_thread_isolation():
    ck = FakeClock()
    with trace.capture(clock=ck.now) as tr:
        def work(i: int):
            with trace.span("worker/block", i=i):
                with trace.span("worker/sub", i=i):
                    pass
        threads = [threading.Thread(target=work, args=(i,), name=f"w{i}")
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        with trace.span("main/own"):
            pass
    # each thread produced ITS OWN well-nested root; nothing leaked across
    assert len(tr.roots) == 5
    by_tid = {}
    for r in tr.roots:
        by_tid.setdefault(r.tid, []).append(r)
    for tid, roots in by_tid.items():
        if tid.startswith("w"):
            (r,) = roots
            assert r.name == "worker/block"
            assert [c.name for c in r.children] == ["worker/sub"]
            assert r.args["i"] == r.children[0].args["i"] == int(tid[1:])


def test_capture_nests_and_restores():
    ck = FakeClock()
    assert not trace.enabled()
    with trace.capture(clock=ck.now) as outer:
        with trace.span("outer/span"):
            with trace.capture(clock=ck.now) as inner:
                with trace.span("inner/span"):
                    pass
            assert trace.active() is outer
        assert [r.name for r in inner.roots] == ["inner/span"]
    assert not trace.enabled()
    assert [r.name for r in outer.roots] == ["outer/span"]
    # inner capture saw only its own spans
    assert all(s.name != "inner/span"
               for r in outer.roots for s in r.walk())


def test_events_and_disabled_noop():
    ck = FakeClock()
    with trace.capture(clock=ck.now) as tr:
        ck.advance(2.0)
        trace.event("distributed/worker_death", worker=3)
    assert tr.events[0]["name"] == "distributed/worker_death"
    assert tr.events[0]["ts"] == pytest.approx(2.0)
    assert tr.events[0]["args"] == {"worker": 3}
    # disabled: span() returns the shared no-op singleton, event() drops
    assert trace.span("x") is trace.span("y")
    trace.event("ignored")


# ------------------------------------------- profiler annotations and hooks

def _all_spans(tr):
    return [s for r in tr.roots for s in r.walk()]


def test_spans_land_on_profiler_host_plane(tmp_path):
    """While tracing, each span is also a profiler annotation: it shows on
    the profiler's host plane with its name, nested as in the tracer, and
    with the Span's duration."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with trace.capture() as tr:
            with trace.span("obs_test/outer", step=1):
                time.sleep(0.005)
                with trace.span("obs_test/inner"):
                    time.sleep(0.01)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [ev for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for ev in line.events if ev.name.startswith("obs_test/")]
    assert sorted(ev.name for ev in host) == ["obs_test/inner",
                                              "obs_test/outer"]
    got = {ev.name: ev for ev in host}
    outer, inner = got["obs_test/outer"], got["obs_test/inner"]
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)
    (o,) = [r for r in tr.roots if r.name == "obs_test/outer"]
    (i,) = [c for c in o.children if c.name == "obs_test/inner"]
    assert outer.duration_ns / 1e9 == pytest.approx(o.duration, abs=1e-3)
    assert inner.duration_ns / 1e9 == pytest.approx(i.duration, abs=1e-3)


def test_gc_hook_records_one_span_per_collection():
    import gc
    was_enabled = gc.isenabled()
    gc.disable()               # only the forced collection below may run
    try:
        with trace.capture() as tr:
            gc.collect()
    finally:
        if was_enabled:
            gc.enable()
    (sp,) = [s for s in _all_spans(tr) if s.name == "runtime/gc"]
    assert sp.args["generation"] == 2
    assert sp.args["collected"] >= 0
    assert sp.duration >= 0
    assert trace._on_gc not in gc.callbacks


def test_compile_hook_records_a_new_shape_inside_its_span():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((3, 7), jnp.float32)

    def obs_hook_probe(a):
        return a * 2.0 + 1.0

    f = jax.jit(obs_hook_probe)
    with trace.capture() as tr:
        with trace.span("obs_test/call"):
            f(x).block_until_ready()
    (call,) = [r for r in tr.roots if r.name == "obs_test/call"]
    comps = [s for s in call.walk() if s.name == "jax/compile"]
    assert len(comps) == 1
    (comp,) = comps
    assert "obs_hook_probe" in comp.args["fun_name"]
    assert call.t0 - 1e-3 <= comp.t0 <= comp.t1 <= call.t1 + 1e-3
    # a second call at the same shape compiles nothing
    with trace.capture() as tr2:
        f(x).block_until_ready()
    assert not [s for s in _all_spans(tr2) if s.name == "jax/compile"]


def test_hooks_are_installed_only_while_a_tracer_is_active():
    import gc

    from jax._src import monitoring

    def installed():
        gc_on = trace._on_gc in gc.callbacks
        jax_on = trace._on_compile in monitoring._event_time_span_listeners
        assert gc_on == jax_on
        return gc_on

    assert not installed()
    with trace.capture():
        assert installed()
        with trace.capture():
            assert installed()
        assert installed()             # the outer capture is still active
    assert not installed()
    trace.start()
    assert installed()
    trace.stop()
    assert not installed()
    trace.stop()                       # stopping twice removes nothing twice
    assert not installed()


def test_traced_predict_names_encode_dispatch_and_finalize(tiny_adult):
    """The pallas engine's dispatch (interpret mode here) splits into the
    batch's upload, the kernel with the reorder, and the copy back."""
    from repro.core import GradientBoostedTreesLearner
    from repro.core.engines import compile_predictor

    model = GradientBoostedTreesLearner(label="income", num_trees=2,
                                        max_depth=2).train(tiny_adult)
    pred = compile_predictor(model, "pallas")
    rows = {k: v[:16] for k, v in tiny_adult.items() if k != "income"}
    want = pred.predict(rows)          # compiles outside the capture
    with trace.capture() as tr:
        got = pred.predict(rows)
    np.testing.assert_array_equal(got, want)
    tops = [r for r in tr.roots if r.name.startswith("engines/")]
    assert [r.name for r in tops] == ["engines/encode", "engines/dispatch",
                                      "engines/finalize"]
    inner = [c for c in tops[1].children if c.name.startswith("engines/")]
    assert [c.name for c in inner] == ["engines/upload", "engines/kernel",
                                       "engines/to_host"]
    assert inner[2].args["bytes"] == 16 * model.forest.n_trees * 4
    assert tops[2].args["rows"] == 16


@pytest.mark.parametrize("engine", ["batched", "device"])
def test_traced_train_names_every_tree_boundary(tiny_adult, tmp_path,
                                                engine):
    """Each boosting iteration is a span holding the gradients, the
    statistics, the tree, and the boundary after it (update, losses,
    checkpoint); each job's preparation and finish have one; the device
    grower adds its per-tree upload and decode, and the first tree holds
    the one upload of the codes."""
    from repro.core import GradientBoostedTreesLearner

    n = 3
    with trace.capture() as tr:
        model = GradientBoostedTreesLearner(
            label="income", num_trees=n, max_depth=2,
            growth_engine=engine).train(tiny_adult,
                                        checkpoint=str(tmp_path / "ck"))
    assert model.training_logs["growth_engine"] == engine
    names = [s.name for s in _all_spans(tr)]
    for name in ("gbt/iteration", "gbt/grad_hess", "gbt/stats", "gbt/tree",
                 "gbt/boundary", "gbt/update", "gbt/loss",
                 "checkpoint/boundary"):
        assert names.count(name) == n, name
    assert names.count("learner/prepare") == 1
    assert names.count("learner/finish") == 1
    hooks = trace.HOOK_SPANS
    tops = [r.name for r in tr.roots if r.name not in hooks]
    assert tops == (["learner/prepare"] + ["gbt/iteration"] * n
                    + ["learner/finish"])
    for it in (r for r in tr.roots if r.name == "gbt/iteration"):
        assert [c.name for c in it.children if c.name not in hooks] == [
            "gbt/grad_hess", "gbt/stats", "gbt/tree", "gbt/boundary"]
    (prep,) = [r for r in tr.roots if r.name == "learner/prepare"]
    assert "grower/binning" in [s.name for s in prep.walk()]
    for b in (s for s in _all_spans(tr) if s.name == "gbt/boundary"):
        assert [c.name for c in b.children if c.name not in hooks] == [
            "gbt/update", "gbt/loss", "checkpoint/boundary"]
    device = ("grower_device/upload", "grower_device/decode")
    for name in device:
        assert names.count(name) == (n if engine == "device" else 0), name
    first_tree = next(s for s in _all_spans(tr) if s.name == "gbt/tree")
    codes = [s.name for s in first_tree.walk()].count("grower_device/codes")
    assert codes == names.count("grower_device/codes")
    assert codes == (1 if engine == "device" else 0)
    assert "grower_device/host_sync" not in names


# --------------------------------------------------------------- exporters

def _sample_tracer():
    ck = FakeClock()
    with trace.capture(clock=ck.now) as tr:
        with trace.span("gbt/tree", tree=0):
            ck.advance(0.5)
            with trace.span("grower/gain_scan", level=1):
                ck.advance(0.25)
        trace.event("checkpoint/rollback", tree=5)
    return tr


def test_chrome_trace_valid_and_normalized():
    tr = _sample_tracer()
    doc = chrome_trace(tr)
    validate_chrome_trace(doc)
    json.dumps(doc)                          # serializable end to end
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert xs["gbt/tree"]["ts"] == 0.0       # normalized to t_origin
    assert xs["gbt/tree"]["dur"] == pytest.approx(0.75e6)
    assert xs["grower/gain_scan"]["cat"] == "grower"
    assert xs["grower/gain_scan"]["ts"] == pytest.approx(0.5e6)
    insts = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert insts[0]["name"] == "checkpoint/rollback"
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert metas and metas[0]["args"]["name"]     # thread lanes named
    with pytest.raises(ValueError):
        validate_chrome_trace({"nope": []})


def test_phase_summary_self_time():
    tr = _sample_tracer()
    ph = phase_summary(tr)
    assert ph["gbt/tree"]["count"] == 1
    assert ph["gbt/tree"]["total_s"] == pytest.approx(0.75)
    assert ph["gbt/tree"]["self_s"] == pytest.approx(0.5)   # minus child
    assert ph["grower/gain_scan"]["self_s"] == pytest.approx(0.25)
    prof = profile_dict(tr)
    assert prof["schema_version"] == 1
    assert prof["span_count"] == 2
    json.dumps(prof)


# ---------------------------------------------------------------- metrics

def test_metrics_counters_gauges_histograms():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("requests").inc()
    reg.counter("requests").inc(2)
    assert reg.counter("requests").value == 3
    reg.counter("requests", engine="pallas").inc(5)
    assert reg.labeled_values("requests", "engine") == {"pallas": 5}
    h = reg.histogram("latency_s")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4
    assert h.mean == pytest.approx(2.5)
    assert h.percentile(50) in (2.0, 3.0)


def test_histogram_bounded_reservoir():
    h = obs_metrics.Histogram(cap=64)
    for i in range(1000):
        h.observe(float(i))
    assert h.count == 1000                   # exact count survives the cap
    assert h.total == pytest.approx(sum(range(1000)))
    assert len(h.values) <= 64


def test_registry_roundtrip_and_merge():
    a = obs_metrics.MetricsRegistry()
    a.counter("trees").inc(3)
    a.counter("dispatches", engine="numpy").inc(2)
    a.histogram("lat", outcome="ok").observe(1.5)
    d = a.to_dict()
    assert d["schema_version"] == 1
    json.dumps(d)
    b = obs_metrics.MetricsRegistry.from_dict(d)
    assert b.to_dict() == d                  # lossless round-trip
    # merge: counters add, histograms pool
    c = obs_metrics.MetricsRegistry()
    c.counter("trees").inc(3)
    c.histogram("lat", outcome="ok").observe(2.5)
    b.merge(c)
    assert b.counter("trees").value == 6
    h = b.histogram("lat", outcome="ok")
    assert h.count == 2 and h.mean == pytest.approx(2.0)
    assert b.counter("dispatches", engine="numpy").value == 2


# ----------------------------------------------------------- training logs

def test_build_training_logs_schema():
    logs = build_training_logs(learner="gbt", num_trees=10,
                               growth_engine="batched",
                               extra={"train_loss": [1.0], "skipme": None})
    assert all(k in logs for k in REQUIRED_KEYS)
    assert logs["schema_version"] == 1
    assert logs["train_loss"] == [1.0]
    assert "skipme" not in logs
    assert "profile" not in logs             # tracing was off
    validate_training_logs(logs)
    for bad in [{}, {**logs, "schema_version": 99},
                {**logs, "num_trees": -1},
                {**logs, "resilience": "nope"}]:
        with pytest.raises(YdfError):
            validate_training_logs(bad)


def test_training_logs_profile_attached_under_capture():
    ck = FakeClock()
    with trace.capture(clock=ck.now):
        with trace.span("grower/binning"):
            ck.advance(0.5)
        logs = build_training_logs(learner="gbt", num_trees=1)
    assert logs["profile"]["phases"]["grower/binning"]["count"] == 1
    lines = summarize_training_logs(logs)
    assert any("learner=gbt" in ln for ln in lines)
    assert any("profile" in ln for ln in lines)
    assert summarize_training_logs({"legacy": 1})[0].startswith(
        "Training logs (legacy)")


def test_learners_emit_schema_v1(tiny_adult):
    from repro.core import (CartLearner, GradientBoostedTreesLearner,
                            RandomForestLearner)
    for cls in (GradientBoostedTreesLearner, RandomForestLearner,
                CartLearner):
        kw = {"num_trees": 3} if cls is not CartLearner else {}
        model = cls(label="income", **kw).train(tiny_adult)
        logs = model.training_logs
        validate_training_logs(logs)
        assert logs["learner"] in ("gbt", "rf", "cart")
        assert any("Training logs (schema v1)" in ln
                   for ln in model.summary().splitlines())


def test_traced_train_covers_grower_phases(tiny_adult):
    from repro.core import GradientBoostedTreesLearner
    with trace.capture() as tr:
        model = GradientBoostedTreesLearner(
            label="income", num_trees=3).train(tiny_adult)
    names = set(tr.phase_names())
    assert {"grower/binning", "grower/hist_build", "grower/gain_scan",
            "grower/routing", "grower/leaf_stats"} <= names
    prof = model.training_logs["profile"]
    assert prof["phases"]["grower/gain_scan"]["count"] > 0
    validate_chrome_trace(chrome_trace(tr))


# ------------------------------------------------------------ CLI profile

def test_cli_profile_train_chrome_trace(tiny_adult, tmp_path, capsys):
    from repro.cli import main
    from repro.data.io import write_dataset
    csv = tmp_path / "train.csv"
    write_dataset(tiny_adult, f"csv:{csv}")
    out = tmp_path / "trace.json"
    main(["profile", "train", f"--dataset=csv:{csv}", "--label=income",
          f"--trace={out}", "--hparam", "num_trees=3"])
    doc = json.loads(out.read_text())
    validate_chrome_trace(doc)
    grower = {e["name"] for e in doc["traceEvents"]
              if e["ph"] == "X" and e["name"].startswith("grower/")}
    assert len(grower) >= 5, grower
    assert "phase" in capsys.readouterr().out


# ------------------------------------------------------- the overhead gate

def test_disabled_tracer_overhead_gate(tiny_adult):
    """The §13 acceptance gate: with no tracer installed, instrumentation
    must cost <= 1% of a 50-tree GBT train.

    Measured as (per-disabled-span cost) x (spans such a train emits)
    against the train's wall time, with the microbenchmark interleaved
    best-of-reps (the §11 checkpoint-gate protocol) so background load
    perturbs both sides equally. This scales the gate's sensitivity far
    beyond timing two trains (whose run-to-run jitter exceeds 1%).
    """
    from repro.core import GradientBoostedTreesLearner

    assert not trace.enabled()
    make = lambda: GradientBoostedTreesLearner(label="income", num_trees=50)

    # span count a 50-tree train emits, counted under a real capture
    with trace.capture() as tr:
        make().train(tiny_adult)
    n_spans = tr.span_count()

    # interleaved best-of: disabled-span loop vs empty loop
    N = 50_000
    def spans():
        for _ in range(N):
            with trace.span("grower/gain_scan", level=1):
                pass
    def baseline():
        for _ in range(N):
            pass
    best = [np.inf, np.inf]
    for _ in range(5):
        for i, fn in enumerate((spans, baseline)):
            t0 = time.perf_counter()
            fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    per_span = max(0.0, best[0] - best[1]) / N

    t0 = time.perf_counter()
    make().train(tiny_adult)
    train_s = time.perf_counter() - t0

    overhead = per_span * n_spans / train_s
    assert overhead <= 0.01, (
        f"disabled tracer costs {overhead:.2%} of a 50-tree train "
        f"({per_span * 1e9:.0f} ns/span x {n_spans} spans / {train_s:.2f}s)")
