"""The readers of the library's tracing spans on a small hand-built
reading: known spans give known values, and a program without the spans
(or, for the runtime hooks' spans, without the hooks) reads nothing."""
import pytest

import devtrace
import run


def reading(spans, trees=2, t0=0.0, t1=1.0):
    return devtrace.Reading(layer={"trees": trees}, config={}, peaks={},
                            t0=t0, t1=t1, bench_spans=[],
                            obs_spans=[(n, a, b, {}) for n, a, b in spans],
                            ops={}, w0_ns=0.0, w1_ns=(t1 - t0) * 1e9,
                            offset_ns=0.0)


# what a program before these spans records: no reader finds anything
OLD = [("gbt/grad_hess", 0.0, 0.01), ("gbt/tree", 0.01, 0.4),
       ("grower_device/fetch", 0.3, 0.35), ("engines/dispatch", 0.5, 0.9)]

TRAIN = [("learner/prepare", -2.0, -1.5),          # job 1, in set-up
         ("gbt/stats", 0.1, 0.15),
         ("gbt/boundary", 0.2, 0.4),
         ("runtime/gc", 0.25, 0.26),
         ("jax/compile", -1.0, -0.5),               # before the window
         ("gbt/stats", 0.5, 0.55),
         ("gbt/boundary", 0.9, 1.2),                # cut by the window
         ("jax/compile", 0.95, 0.97),
         ("runtime/gc", 0.99, 1.01)]

SCORE = [("engines/encode", 0.0, 0.4), ("engines/to_host", 0.5, 0.6),
         ("runtime/gc", 0.45, 0.47), ("engines/encode", 0.6, 0.9),
         ("engines/to_host", 0.95, 1.05)]


@pytest.mark.parametrize("name,spans,want", [
    ("learner_host_ms.train", TRAIN, (0.05 + 0.2 + 0.05 + 0.1) * 1e3 / 2),
    ("prepare_ms.train", TRAIN, 0.0),
    ("prepare_ms.train", TRAIN + [("learner/prepare", 0.6, 0.8)],
     0.2 * 1e3 / 2),
    ("gc_share.train", TRAIN, 2.0),
    ("compiles.train", TRAIN, 1),
    ("to_host_share.score", SCORE, 15.0),
    ("gc_share.score", SCORE, 2.0),
    ("compiles.score", SCORE, 0),
    ("gc_share.train", OLD, 0.0),          # hooks on, nothing collected
    ("compiles.score", [], 0),
])
def test_reader_reads_known_spans(name, spans, want):
    assert run._reader(name)(reading(spans)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "learner_host_ms.train", "prepare_ms.train", "to_host_share.score",
    "gc_share.train", "gc_share.score", "compiles.train", "compiles.score"])
def test_reader_reads_nothing_without_the_spans(name, monkeypatch):
    from repro.obs import trace
    monkeypatch.delattr(trace, "HOOK_SPANS")     # a program before the hooks
    assert run._reader(name)(reading(OLD)) is None
    assert run._reader(name)(reading([])) is None
