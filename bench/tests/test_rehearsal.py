"""Each cell's whole run on the CPU at a tiny size: set-up, window, trace
reduction, readers and the reference comparison, past the look for a chip.
The numbers are CPU numbers; only the result's shape and its checks are
read here."""
import json
import os

import pytest

import rehearse
import run

BENCH = json.load(open(os.path.join(rehearse.BENCH, os.pardir,
                                    "BENCHMARK.json")))
CELLS = [w["name"] for w in rehearse.bench_with_online()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_its_end_to_end_metrics(cell):
    res = rehearse.rehearse(cell, seed=2**31 + 5)
    spec = rehearse.load(cell)
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == set(spec["cell"]["limits"])
    assert list(res)[-2:] == ["checks", "_notes"]
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_only_per_layer_metrics(cell):
    res = rehearse.rehearse(cell, seed=11, trace=True)
    spec = rehearse.load(cell)
    names = {m["name"] for m in spec["per_layer"]}
    assert res["metrics"] and set(res["metrics"]) <= names
    # no chip ran, so nothing is read from the device
    assert not any(n.startswith("device_idle") or "roofline" in n
                   or "mfu" in n for n in res["metrics"])
    assert res["device"]["busy_s"] == 0.0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["correct"] is True, res["checks"]


def test_every_cell_and_metric_is_found_by_name():
    for w in BENCH["workloads"]:
        spec = run.load_cell(w["name"])
        assert spec["end_to_end"] and spec["per_layer"]
        assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert callable(run._reader(m["name"]))


def test_no_chip_is_refused():
    with pytest.raises(run.RunError, match="no TPU"):
        run.check_device(1)
