"""Bulk scoring: one caller in a closed loop calls
``CompiledPredictor.predict`` back to back on tables of raw float32
columns, each call returning the probabilities of every row.

Set-up draws the configuration's serving forest from the seed, compiles the
predictor (which must pick the pallas engine), makes ``batches`` tables of
``batch_rows`` rows and scores one of them once, which compiles the kernel
at the batch's shape. The window then calls ``predict`` until ``--seconds``
have passed; it closes when the last call returns. ``score_rows_per_s`` is
every row returned over the window.

Correctness: ``sample_rows`` rows of each table, drawn from the seed, are
kept from every call in the window and compared with the plain reference's
probabilities for the same rows after the window.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from harness import Check, Outcome, Window
import program
import tabular

_state: dict = {}


def feature_table(cfg, n, seed) -> dict:
    t = tabular.make_table(cfg["dataset"], n, seed)
    t.pop(cfg["dataset"]["label"]["name"])
    return t


def inputs(ctx):
    """(serving forest, tables, compared rows of each table) from the
    seed."""
    cfg, p = ctx.config, ctx.params
    rows = int(p["batch_rows"])
    sample = feature_table(cfg, int(p["forest_sample_rows"]),
                           ctx.stream_seed(1))
    forest = tabular.build_forest(cfg["dataset"], cfg["serving_forest"],
                                  ctx.stream_seed(2), sample)
    batches = [feature_table(cfg, rows, ctx.stream_seed(10 + b))
               for b in range(int(p["batches"]))]
    rng = ctx.rng(3)
    keep = [np.sort(rng.choice(rows, int(p["sample_rows"]), replace=False))
            for _ in batches]
    return forest, batches, keep


def run(ctx) -> Outcome:
    cfg, p = ctx.config, ctx.params
    rows = int(p["batch_rows"])
    forest, batches, keep = inputs(ctx)
    model = program.servable_model(cfg, forest)
    pred = program.compile_predictor(model, p.get("engine"))
    pred.predict(batches[0])                       # compiles at this shape
    win = Window(ctx)
    win.open()
    kept, calls, done = [], 0, 0
    with program.library_spans(ctx.trace) as obs_spans:
        while True:
            b = calls % len(batches)
            with ctx.span("bench/predict"):
                with ctx.span("bench/encode"):
                    X = pred.encode(batches[b])
                out = pred.predict_encoded(X)
            calls += 1
            done += len(out)
            kept.append((b, out[keep[b]]))
            if time.perf_counter() - win.t0 >= ctx.seconds:
                break
        win.close()
    _state.update(forest=forest, batches=batches, keep=keep, kept=kept)
    return Outcome(
        setup_s=win.t0 - ctx.t_start, window=win,
        metrics={"score_rows_per_s": done / (win.t1 - win.t0)},
        checks=[], attempted=calls, failed=0,
        layer={"calls": calls, "rows": rows, "obs_spans": obs_spans,
               "trees": int(cfg["serving_forest"]["trees"]),
               "depth": int(cfg["serving_forest"]["depth"]),
               "features": len(cfg["dataset"]["columns"]),
               "out_dim": 1,
               "categorical_nodes": int(forest.is_cat[forest.feature].sum())})


def reference_probs(ref, cfg, forest, table, idx,
                    round_table=None) -> np.ndarray:
    """The reference's probability of the positive class for rows ``idx``
    of a raw ``table``."""
    cols = []
    for c in cfg["dataset"]["columns"]:
        v = table[c["name"]][idx]
        if c["kind"] == "categorical":
            lookup = {s: i + 1 for i, s in enumerate(c["values"])}
            # the served model's dictionary: missing -> the most frequent
            v = np.array([1 if s is None else lookup.get(s, 0) for s in v])
        cols.append(v)
    return ref.sigmoid(ref.forest_logits(forest, cols, forest.is_cat,
                                         round_table))


def compare(ctx, out: Outcome) -> None:
    ref = importlib.import_module(ctx.config["reference"])
    st = {k: _state.pop(k) for k in ("forest", "batches", "keep", "kept")}
    want = [reference_probs(ref, ctx.config, st["forest"], t, i)
            for t, i in zip(st["batches"], st["keep"])]
    gap = 0.0
    for b, got in st["kept"]:
        gap = max(gap, float(np.max(np.abs(got[:, 1].astype(np.float64)
                                           - want[b]))))
    out.checks.append(Check("prob_gap", gap, ctx.limits["prob_gap"]))
    out.notes.append(f"compared {sum(len(g) for _, g in st['kept'])} "
                     f"sampled rows of {len(st['kept'])} calls")
