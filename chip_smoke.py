#!/usr/bin/env python3
"""Run the forest library's main path once on a TPU and check its results.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # multi-chip training only (4 chips)

One chip, three phases in this one process, through the entry points a
user calls (``Learner.train``, ``compile_predictor``, ``ForestServer``):

1. Device training with the fused split kernel. A HIGGS-shaped set (28
   numerical features, binary label, 2^18 training rows) trains a
   ``growth_engine="device"`` GBT (depth 6, 255 bins) and an RF lockstep
   block. Each must run on the device engine with the level step resolved
   to the compiled kernel, and agree with the batched host grower (numpy
   histograms) within the device-engine tests' predictive tolerance.
2. Default training. A GBT with no engine hparams on synth_adult (6
   numerical + 8 categorical columns) runs the batched grower, whose
   "auto" histogram backend is the histogram kernel on a TPU.
3. Serving. ``compile_predictor`` on both models must pick the pallas
   engine; 100k+ rows score within 1e-6 of the naive engine. A
   ``ForestServer`` then answers 40 requests of 1-128 rows with pallas
   dispatches only and no failure, shed or timeout.

``--four-chips`` runs only ``DistributedGBT`` on a 2x2 (data x model) mesh
of ``jax.devices()[:4]`` against a 1-device mesh, agreeing to 1e-4.

Each phase prints one JSON line. Compile and upload seconds are set-up
(``setup_s``); nothing here is a speed measurement. Any failed check
raises, so the script exits non-zero without a result line. The last line,
on success, is ``{"ok": true, "device": {...}}`` as JAX reports the device.
The script refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.jax_cache import configure_compile_cache  # noqa: E402

TRAIN_ROWS = 1 << 18
SERVE_ROWS = 100_000
SEED = 7


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def higgs_like(n: int, seed: int = SEED) -> dict:
    """HIGGS's shape (28 dense numerical features, binary label, no missing
    values) from the repo's seeded generator, as float32 columns."""
    from repro.data.tabular import SyntheticSpec, make_dataset
    d = make_dataset(SyntheticSpec("higgs_like", n, 28, 0, 2,
                                   missing_rate=0.0, seed=seed))
    return {k: (v if k == "label" else v.astype(np.float32))
            for k, v in d.items()}


def split(data: dict, n_train: int) -> tuple[dict, dict]:
    return ({k: v[:n_train] for k, v in data.items()},
            {k: v[n_train:] for k, v in data.items()})


def agreement(ref, dev, test) -> dict:
    """The device-engine tests' predictive contract for configurations where
    f32 gain ties may regrow a subtree: same node counts, mean |dp| < 2e-3,
    > 99% same class. Structure agreement is reported alongside."""
    pr, pd = ref.predict(test), dev.predict(test)
    pr, pd = pr.reshape(len(pr), -1)[:, -1], pd.reshape(len(pd), -1)[:, -1]
    out = {
        "n_nodes_equal": bool(np.array_equal(ref.forest.n_nodes,
                                             dev.forest.n_nodes)),
        "feature_agreement": float((ref.forest.feature
                                    == dev.forest.feature).mean()),
        "mean_abs_pred_diff": float(np.abs(pr - pd).mean()),
        "max_abs_pred_diff": float(np.abs(pr - pd).max()),
        "class_agreement": float(((pr > 0.5) == (pd > 0.5)).mean()),
    }
    check(out["n_nodes_equal"], f"node counts differ: {out}")
    check(out["mean_abs_pred_diff"] < 2e-3, f"predictions differ: {out}")
    check(out["class_agreement"] > 0.99, f"classes differ: {out}")
    return out


def check_device_run(model, what: str) -> None:
    logs = model.training_logs
    check(logs["growth_engine"] == "device",
          f"{what}: growth_engine={logs['growth_engine']!r}")
    check(logs["engine_fallback"] is None,
          f"{what}: engine_fallback={logs['engine_fallback']!r}")
    check(logs.get("device_impl") == "pallas",
          f"{what}: level step ran {logs.get('device_impl')!r} "
          f"({logs.get('device_impl_reason')})")


def phase_device_training(train, test, *, gbt_trees: int, rf_trees: int,
                          rf_depth: int):
    from repro.core import GradientBoostedTreesLearner, RandomForestLearner

    common = dict(label="label", validation_ratio=0.0, early_stopping="NONE",
                  num_trees=gbt_trees)
    t0 = time.perf_counter()
    gbt = GradientBoostedTreesLearner(**common,
                                      growth_engine="device").train(train)
    gbt_s = time.perf_counter() - t0
    check_device_run(gbt, "device GBT")
    ref = GradientBoostedTreesLearner(**common, growth_engine="batched",
                                      histogram_backend="numpy").train(train)
    emit("device_gbt", rows=len(train["label"]), features=28,
         trees=gbt.forest.n_trees, max_depth=6, max_bins=255,
         growth_engine="device", device_impl=gbt.training_logs["device_impl"],
         engine_fallback=None, train_s_with_compile=gbt_s,
         vs_batched=agreement(ref, gbt, test))

    rf_kw = dict(label="label", num_trees=rf_trees, max_depth=rf_depth,
                 compute_oob=False)
    t0 = time.perf_counter()
    rf = RandomForestLearner(**rf_kw, growth_engine="device").train(train)
    rf_s = time.perf_counter() - t0
    check_device_run(rf, "device RF")
    rf_ref = RandomForestLearner(**rf_kw, growth_engine="batched",
                                 histogram_backend="numpy").train(train)
    emit("device_rf", rows=len(train["label"]), trees=rf.forest.n_trees,
         lockstep_block=rf.training_logs["tree_parallelism"],
         max_depth=rf_depth, growth_engine="device",
         device_impl=rf.training_logs["device_impl"], engine_fallback=None,
         train_s_with_compile=rf_s, vs_batched=agreement(rf_ref, rf, test))
    return gbt


def phase_default_training(train, *, trees: int):
    from repro.core import GradientBoostedTreesLearner

    t0 = time.perf_counter()
    m = GradientBoostedTreesLearner(label="label", num_trees=trees).train(train)
    logs = m.training_logs
    check(logs["growth_engine"] == "batched",
          f"default GBT ran {logs['growth_engine']!r}")
    check(logs.get("histogram_backend") == "pallas",
          f"default GBT histograms ran {logs.get('histogram_backend')!r}")
    check(m.forest.n_trees > 0 and np.isfinite(m.forest.leaf_value).all(),
          "default GBT grew no finite trees")
    emit("default_gbt", rows=len(train["label"]),
         categorical_columns=sum(k.startswith("cat_") for k in train),
         trees=m.forest.n_trees, growth_engine=logs["growth_engine"],
         histogram_backend=logs["histogram_backend"],
         train_s_with_compile=time.perf_counter() - t0)
    return m


def score_vs_naive(name: str, model, data) -> None:
    """compile_predictor must pick the pallas engine; its scores on every
    row of ``data`` must match the naive engine to 1e-6."""
    import jax

    from repro.core.engines import compile_predictor

    t0 = time.perf_counter()
    pred = compile_predictor(model)
    check(pred.name == "pallas", f"{name}: compiled to {pred.name!r}")
    X = pred.encode(data)
    out = pred.predict_encoded(X)              # first call compiles
    jax.block_until_ready(out)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = np.asarray(pred.predict_encoded(X))
    score_s = time.perf_counter() - t0
    want = np.asarray(compile_predictor(model, "naive").predict_encoded(X))
    err = float(np.abs(out - want).max())
    check(out.shape == want.shape and np.isfinite(out).all(),
          f"{name}: bad output {out.shape}")
    check(np.allclose(out, want, rtol=0.0, atol=1e-6),
          f"{name}: pallas vs naive max |diff| {err}")
    emit(f"serve_{name}", engine=pred.name, rows=len(X),
         trees=model.forest.n_trees, setup_s=setup_s, score_s=score_s,
         max_abs_diff_vs_naive=err)


def phase_server(model, data, *, requests: int) -> None:
    from repro.serving.server import ForestServer

    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    srv = ForestServer(model, warmup=True)
    setup_s = time.perf_counter() - t0
    n = len(next(iter(data.values())))
    want = model.predict(data)
    t0 = time.perf_counter()
    for _ in range(requests):
        rows = int(rng.integers(1, 129))
        lo = int(rng.integers(0, n - rows))
        got = srv.predict({k: v[lo:lo + rows] for k, v in data.items()})
        check(np.allclose(got, want[lo:lo + rows], rtol=0.0, atol=1e-6),
              "server answer differs from model.predict")
    serve_s = time.perf_counter() - t0
    m = srv.metrics
    dispatches = dict(m.engine_dispatches)
    check(set(dispatches) == {"pallas"}, f"server dispatched {dispatches}")
    for k in ("failed", "shed", "timed_out", "fallback_dispatches"):
        check(getattr(m, k) == 0, f"server {k}={getattr(m, k)}")
    check(m.completed == requests, f"completed {m.completed}/{requests}")
    emit("forest_server", requests=requests, completed=m.completed,
         engine_dispatches=dispatches, failed=m.failed, shed=m.shed,
         timed_out=m.timed_out, fallback_dispatches=m.fallback_dispatches,
         setup_s=setup_s, serve_s_with_shape_compiles=serve_s)


def phase_four_chips(devices, *, rows: int, trees: int) -> None:
    """DistributedGBT on a 2x2 mesh of exactly ``devices[:4]`` against a
    one-device mesh; scores must agree to 1e-4 (the cross-mesh test's
    tolerance) and the inputs must be placed on all four devices."""
    from jax.sharding import Mesh

    from repro.core.distributed import DistGBTConfig, DistributedGBT

    check(len(devices) >= 4, f"--four-chips needs 4 devices, has "
          f"{len(devices)}")
    d = higgs_like(rows)
    X = np.stack([d[f"num_{j}"] for j in range(28)], 1)
    y = (d["label"] == "c1").astype(np.float64)
    edges = np.quantile(X, np.linspace(0, 1, 65)[1:-1], axis=0)   # 64 bins
    codes = np.stack([np.searchsorted(edges[:, j], X[:, j])
                      for j in range(28)], 1).astype(np.uint8)
    cfg = DistGBTConfig(max_depth=6, n_bins=64, num_trees=trees)
    four = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    one = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    t0 = time.perf_counter()
    m4 = DistributedGBT(cfg, four).fit(codes, y)
    t4 = time.perf_counter() - t0
    m1 = DistributedGBT(cfg, one).fit(codes, y)
    want_ids = sorted(dv.id for dv in devices[:4])
    check(m4.training_logs["devices"] == want_ids,
          f"4-chip inputs on devices {m4.training_logs['devices']}")
    check(m1.training_logs["devices"] == [devices[0].id],
          f"1-chip inputs on devices {m1.training_logs['devices']}")
    s4, s1 = m4.predict_scores(codes), m1.predict_scores(codes)
    err = float(np.abs(s4 - s1).max())
    check(np.allclose(s4, s1, atol=1e-4), f"meshes disagree: {err}")
    acc = float(((s4 > 0) == (y > 0.5)).mean())
    check(acc > 0.6, f"4-chip model accuracy {acc}")
    emit("distributed_gbt", rows=rows, features=28, trees=trees,
         mesh={"data": 2, "model": 2}, devices=want_ids,
         hist_impl=m4.training_logs["hist_impl"],
         max_abs_score_diff_vs_1chip=err,
         train_accuracy=acc, fit_s_with_compile=t4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the DistributedGBT 4-chip vs 1-chip check")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    emit("setup", compile_cache=configure_compile_cache(),
         platform=devices[0].platform, kind=devices[0].device_kind,
         count=len(devices))

    if args.four_chips:
        phase_four_chips(devices, rows=TRAIN_ROWS, trees=10)
        count = 4
    else:
        from repro.data.tabular import SUITE, make_dataset

        train, test = split(higgs_like(TRAIN_ROWS + SERVE_ROWS), TRAIN_ROWS)
        gbt = phase_device_training(train, test, gbt_trees=20, rf_trees=8,
                                    rf_depth=10)
        adult_train, adult_test = split(
            make_dataset(dataclasses.replace(SUITE[2],
                                             n=(1 << 15) + SERVE_ROWS)),
            1 << 15)
        adult = phase_default_training(adult_train, trees=20)
        score_vs_naive("higgs_gbt", gbt, test)
        score_vs_naive("adult_gbt", adult, adult_test)
        phase_server(adult, adult_test, requests=40)
        count = 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
