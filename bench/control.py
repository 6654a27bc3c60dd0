#!/usr/bin/env python3
"""The correctness comparison's control: the plain reference put in the
program's place, computed in the precision below the one the configuration
states, and judged by the same numbers a run compares.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]

The configurations state float32 statistics and float32 forest tables. The
control rounds them to bfloat16, the step that would tempt a later change:
for training, the gradient and hessian that every histogram and leaf sums
(``gbt_reference.boost(round_stats=round_bf16)``); for scoring and serving,
the thresholds and leaf values of the forest
(``gbt_reference.forest_logits(round_table=round_bf16)``). It reads the
same inputs a run of the cell reads at the same seed and prints one JSON
line per seed with the numbers a run would compare. The benchmark's own
runs never run it: its readings set the upper end of each limit, which
``PERF.md`` records. ``--fault half_batch|state_unchanged`` reads, for a training
cell, the reference with that fault planted in the program's place
instead.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import run  # noqa: E402  (puts bench/lib, drivers and configs on the path)
from harness import Ctx  # noqa: E402
import tabular  # noqa: E402


def _ctx(spec: dict, seed: int, seconds: float) -> Ctx:
    params = {**spec["mix"].get("params", {}),
              **spec["cell"].get("params", {})}
    return Ctx(cell=spec["entry"]["name"], config=spec["config"],
               params=params, limits=spec["cell"]["limits"], seed=seed,
               seconds=seconds, trace=False, t_start=0.0)


def train_readings(ctx: Ctx, fault: str | None = None) -> dict:
    """The control's readings, or with ``fault`` those of the reference put
    in the program's place with the fault planted: ``half_batch`` leaves
    half of the training rows out of every tree (the trees' statistics are
    summed over the rest); ``state_unchanged`` returns the model unchanged
    from the first boosting step."""
    import train_jobs
    cfg = ctx.config
    ref = importlib.import_module(cfg["reference"])
    hp = cfg["learner"]["hparams"]
    k = int(ctx.params["compare_trees"])
    data = tabular.make_table(cfg["dataset"], int(cfg["train_rows"]),
                              ctx.stream_seed(1))
    enc = ref.encode(data, tabular.features(cfg["dataset"]),
                     cfg["dataset"]["label"]["name"], int(hp["max_bins"]))
    del data
    feed = np.float32
    if fault == "half_batch":
        n = len(ref.validation_split(enc.codes.shape[1],
                                     float(hp["validation_ratio"]), ctx.seed))
        keep = np.sort(ctx.rng(4).permutation(n)[: n // 2])
        run = ref.boost(enc, hp, ctx.seed, k, rows_keep=keep)
    elif fault == "state_unchanged":
        run = ref.boost(enc, hp, ctx.seed, k)
        run.losses[0] = run.loss0
        run.outputs[0] = np.zeros_like(run.outputs[0])
    else:
        run = ref.boost(enc, hp, ctx.seed, k, round_stats=ref.round_bf16)
        feed = ref.round_bf16
    # the exact reference, taking the candidate's split where they tie, as
    # a run's comparison takes the program's
    exact = ref.boost(enc, hp, ctx.seed, k,
                      prefer=[ref.preference(t) for t in run.trees])
    return train_jobs.readings(ref, run.losses, run.outputs, exact.init,
                               exact, feed=feed)


def _gap(ref, cfg, forest, table, idx) -> float:
    import bulk_score
    exact = bulk_score.reference_probs(ref, cfg, forest, table, idx)
    low = bulk_score.reference_probs(ref, cfg, forest, table, idx,
                                     round_table=ref.round_bf16)
    return float(np.max(np.abs(low - exact)))


def score_readings(ctx: Ctx) -> dict:
    import bulk_score
    cfg = ctx.config
    ref = importlib.import_module(cfg["reference"])
    forest, batches, keep = bulk_score.inputs(ctx)
    return {"prob_gap": max(_gap(ref, cfg, forest, t, i)
                            for t, i in zip(batches, keep))}


def online_readings(ctx: Ctx) -> dict:
    import open_loop
    cfg = ctx.config
    ref = importlib.import_module(cfg["reference"])
    _, forest, reqs, _ = open_loop.requests(ctx)
    pick = list(range(len(reqs)))
    table = open_loop.joined(reqs, pick)
    n = len(next(iter(table.values())))
    return {"prob_gap": _gap(ref, cfg, forest, table, np.arange(n))}


READINGS = {"train_jobs": train_readings, "bulk_score": score_readings,
            "open_loop": online_readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", choices=("half_batch", "state_unchanged"),
                    default=None,
                    help="read a training fault instead of the control")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = _ctx(spec, seed, args.seconds)
        read = READINGS[spec["mix"]["driver"]]
        got = read(ctx, args.fault) if args.fault else read(ctx)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          args.fault or "control": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
