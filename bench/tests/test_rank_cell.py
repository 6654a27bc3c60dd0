"""The ranking cell ``mslr_lambdamart.train`` rehearsed on the CPU at a
tiny size, through ``run.execute``: set-up, window, readers and the
reference comparison, past the look for a chip. Its tiny sizes are its
own (``rehearse.TINY`` is keyed by traffic and has none for
``rank_train``). The numbers are CPU numbers; only the result's shape and
its checks are read here. Also: the reference's faults and its bfloat16
control fail the cell's limits, the program's pair counter matches the
benchmark's own count, and a library without the device pass is refused
before anything is made."""
import copy
import importlib

import numpy as np
import pytest

import rehearse
import run

CELL = "mslr_lambdamart.train"
TINY = {"queries": 40, "hparams": {"num_trees": 5, "max_depth": 3},
        "params": {"device_impl": None}}


def tiny_spec(**limits) -> dict:
    spec = copy.deepcopy(run.load_cell(CELL))
    spec["config"]["queries"] = TINY["queries"]
    spec["config"]["learner"]["hparams"].update(TINY["hparams"])
    spec["cell"]["params"].update(TINY["params"])
    spec["cell"]["limits"].update(limits)
    return spec


def rehearse_rank(seed: int, trace: bool = False, **limits) -> dict:
    import program
    program.configure_compile_cache()
    return run.execute(tiny_spec(**limits), seed, 1.0, trace, rehearse.CPU)


def test_untraced_run_is_correct_and_reports_train_ms_per_tree():
    res = rehearse_rank(2**31 + 5)
    assert set(res["metrics"]) == {"train_ms_per_tree", "setup_s"}
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_traced_run_reads_the_ranking_spans():
    res = rehearse_rank(11, trace=True)
    m = res["metrics"]
    assert res["correct"] is True, res["checks"]
    assert {"ndcg_ms.rank", "lambda_pad_share.rank", "grad_hess_ms.train",
            "level_step_ms.train"} <= set(m)
    assert 0 < m["lambda_pad_share.rank"]["value"] < 100
    # no chip ran: nothing is read from the device
    assert "lambda_roofline.rank" not in m
    assert "fused_split_roofline.train" not in m


def _reference(seed: int):
    import rank_jobs
    import ranking_table
    spec = tiny_spec()
    cfg = spec["config"]
    ref = importlib.import_module(cfg["reference"])
    data = ranking_table.make_table(cfg["dataset"], cfg["queries"],
                                    [seed, 1])
    return ref, cfg["learner"]["hparams"], rank_jobs.encode(ref, cfg, data)


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged",
                                   "bf16_control"])
def test_reference_faults_fail_the_limits(fault):
    """The reference with a fault planted in the program's place, read as
    a run reads the program, fails at least one of the cell's limits."""
    import rank_control
    seed = 7
    ref, hp, enc = _reference(seed)
    got = rank_control.readings(ref, enc, hp, seed, 2, fault)
    limits = run.load_cell(CELL)["cell"]["limits"]
    assert any(got[n] > limits[n] for n in limits), got


def test_pair_counter_matches_the_benchmarks_count():
    import rank_work
    from repro.obs import trace
    from repro.tasks.ranking import group_layout, lambda_grad_device
    rng = np.random.default_rng(3)
    sizes = np.clip(np.rint(rng.lognormal(4.58, 0.64, 30)), 1, 1251)
    qid = np.repeat(np.arange(30), sizes.astype(np.int64))
    with trace.capture() as tr:
        lambda_grad_device(rng.normal(size=len(qid)),
                           rng.integers(0, 5, len(qid)).astype(float),
                           group_layout(qid), k=5)
    (span,) = [s for r in tr.roots for s in r.walk()
               if s.name == "ranking/lambda"]
    assert span.args["pairs"] == rank_work.top_k_pairs(sizes, 5)
    assert span.args["rows"] == len(qid)


def test_a_library_without_the_device_pass_is_refused(monkeypatch):
    import program  # noqa: F401  (puts the library on the import path)
    from repro.tasks import ranking
    monkeypatch.delattr(ranking, "LAMBDA_PASS")
    with pytest.raises(run.RunError, match="lambda pass"):
        run.execute(tiny_spec(), 7, 1.0, False, rehearse.CPU)


def test_the_table_bins_without_categorical_features():
    """Every one of the 136 columns, the five 0/1 boolean-model columns
    among them, bins as an ordered feature: the table stays on the fused
    kernel, which the cell demands on the chip (``device_impl`` pallas)."""
    import program  # noqa: F401  (puts the library on the import path)
    import ranking_table
    from repro.core.binning import bin_features
    from repro.core.dataspec import Semantic, dataset_from_raw
    ds_spec = tiny_spec()["config"]["dataset"]
    data = ranking_table.make_table(ds_spec, 30, [7, 1])
    feats = ranking_table.features(ds_spec)
    ds = dataset_from_raw({f: data[f] for f in feats})
    kinds = {ds.spec[f].semantic for f in feats}
    assert kinds == {Semantic.NUMERICAL, Semantic.BOOLEAN}
    assert not bin_features(ds, feats).is_cat.any()
