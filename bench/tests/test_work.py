"""Work counts against hand counts at tiny shapes, and each share at most
100% when the time it is given is its own lower bound."""
import importlib.util
import os

import pytest

import work

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def test_histogram_level_hand_count():
    # 10 rows, 2 features, 3 stats: 10 * 2 * 3 accumulates; each row reads
    # 2 one-byte codes, 3 float32 stats and a 4-byte node id
    w = work.histogram_level(10, 2)
    assert w.ops == 60
    assert w.bytes == 10 * (2 + 12 + 4)


def test_tree_sums_its_levels():
    w = work.tree([10, 10, 6], 2)
    assert w.ops == (10 + 10 + 6) * 2 * 3
    assert w.bytes == 26 * 18


def test_scoring_hand_count():
    # 5 rows, 3 features, 2 trees of depth 2 (3 internal nodes, 4 leaves
    # each), one categorical node: 5 * 2 * 2 compares; per row 3 code
    # bytes + 4 output bytes; tables 5 numerical nodes * 2 bytes + 1
    # categorical node * 33 bytes + 8 leaves * 4 bytes
    w = work.scoring(5, 3, 2, 2, out_dim=1, categorical_nodes=1)
    assert w.ops == 20
    assert w.bytes == 5 * 7 + 5 * 2 + 33 + 32


def test_least_time_and_bound():
    w = work.Work(ops=197e12, bytes=819e9 / 2)
    assert w.least_s(PEAKS) == pytest.approx(1.0)
    assert w.bound_by(PEAKS) == "compute"
    assert work.Work(1.0, 819e9).bound_by(PEAKS) == "memory"


@pytest.mark.parametrize("w", [work.histogram_level(1 << 20, 28),
                               work.tree([943718] * 6, 28),
                               work.scoring(500000, 28, 300, 6)])
def test_share_is_100_at_its_own_lower_bound(w):
    assert work.share_pct(w, w.least_s(PEAKS), PEAKS) == pytest.approx(100)
    assert work.share_pct(w, 2 * w.least_s(PEAKS), PEAKS) == pytest.approx(
        50)
    assert work.share_pct(w, 0.0, PEAKS) is None


class _Reading:
    """What the roofline readers read, with the kernel's time set to the
    work's own lower bound."""

    def __init__(self, layer, kernel_s, window_s, ops=True):
        self.layer, self.peaks = layer, PEAKS
        self._k, self.window_s = kernel_s, window_s
        self.ops = {"/device:TPU:0": []} if ops else {}

    def kernel_s(self, kernel):
        return self._k


def _read(metric, reading):
    spec = importlib.util.spec_from_file_location(
        metric, os.path.join(METRICS, metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(reading)


TRAIN = {"level_rows": [[1000, 1000, 990], [1000]], "features": 28}
SCORE = {"calls": 3, "rows": 5000, "features": 28, "trees": 300,
         "depth": 6, "out_dim": 1}


@pytest.mark.parametrize("metric,layer,least", [
    ("fused_split_roofline.train", TRAIN,
     work.tree([1000, 1000, 990, 1000], 28).least_s(PEAKS)),
    ("train_mfu", TRAIN, work.tree([1000, 1000, 990, 1000], 28)
     .least_s(PEAKS)),
    ("forest_infer_roofline.score", SCORE,
     3 * work.scoring(5000, 28, 300, 6).least_s(PEAKS)),
    ("score_mfu", SCORE, 3 * work.scoring(5000, 28, 300, 6).least_s(PEAKS)),
])
def test_roofline_readers_read_100_at_the_bound(metric, layer, least):
    assert _read(metric, _Reading(layer, least, least)) == pytest.approx(100)
    assert _read(metric, _Reading(layer, 4 * least, 4 * least)) == \
        pytest.approx(25)


def test_roofline_reader_silent_without_the_kernel():
    assert _read("fused_split_roofline.train",
                 _Reading(TRAIN, 0.0, 1.0)) is None


@pytest.mark.parametrize("metric,layer", [("train_mfu", TRAIN),
                                          ("score_mfu", SCORE)])
def test_mfu_reader_silent_without_a_traced_chip(metric, layer):
    assert _read(metric, _Reading(layer, 1.0, 1.0, ops=False)) is None
