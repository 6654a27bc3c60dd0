#!/usr/bin/env python3
"""The ranking cell's control and faults: the plain reference put in the
program's place, read by the same numbers a run of ``mslr_lambdamart.train``
compares (``rank_jobs.readings``).

    python3 bench/rank_control.py --workload <cell> --seeds 1,2,3 \\
        [--fault half_batch|state_unchanged]

``bench/control.py`` reads the cells of the ``train_jobs``, ``bulk_score``
and ``open_loop`` drivers; this script reads the ``rank_jobs`` cell. The
control computes the reference in bfloat16, the precision below the
configuration's float32: the lambda gradient and hessian every histogram
and leaf sums (``boost(round_stats=round_bf16)``), and the gradient the
second tree is grown on. ``--fault half_batch`` leaves half of the training
rows out of every tree (the lambdas and the loss stay over every query);
``--fault state_unchanged`` returns the model unchanged from the first
boosting step. It reads the inputs a run of the cell reads at the same
seed and prints one JSON line per seed. The benchmark's own runs never run
it: its readings set the limits, which ``PERF.md`` records.
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
import rank_jobs  # noqa: E402
import ranking_table  # noqa: E402


def readings(ref, enc, hp: dict, seed: int, k: int,
             fault: str = "bf16_control") -> dict:
    """The readings of the reference with ``fault`` in the program's place:
    ``bf16_control``, ``half_batch`` or ``state_unchanged``."""
    if fault == "half_batch":
        n = len(ref.training_rows(enc.qid, float(hp["validation_ratio"]),
                                  seed))
        keep = np.sort(np.random.default_rng([seed, 4]).permutation(n)
                       [: n // 2])
        cand = ref.boost(enc, hp, seed, k, rows_keep=keep)
        grad = cand.grads[1].astype(np.float32)
    elif fault == "state_unchanged":
        cand = ref.boost(enc, hp, seed, k)
        cand.losses[0] = cand.loss0
        cand.outputs[0] = np.zeros_like(cand.outputs[0])
        grad = cand.grads[0].astype(np.float32)
    elif fault == "bf16_control":
        cand = ref.boost(enc, hp, seed, k, round_stats=ref.round_bf16)
        grad = ref.round_bf16(cand.grads[1])
    else:
        raise ValueError(f"unknown fault {fault!r}")
    # the exact reference, taking the candidate's split where they tie, as
    # a run's comparison takes the program's
    exact = ref.boost(enc, hp, seed, k,
                      prefer=[ref.preference(t) for t in cand.trees])
    return rank_jobs.readings(cand.losses, cand.outputs, grad, exact)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=("half_batch", "state_unchanged"),
                    default=None, help="read a fault instead of the control")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    cfg = spec["config"]
    ref = importlib.import_module(cfg["reference"])
    hp = cfg["learner"]["hparams"]
    k = int({**spec["mix"].get("params", {}),
             **spec["cell"].get("params", {})}["compare_trees"])
    for seed in (int(s) for s in args.seeds.split(",")):
        data = ranking_table.make_table(cfg["dataset"], int(cfg["queries"]),
                                        [seed, 1])
        enc = rank_jobs.encode(ref, cfg, data)
        del data
        got = readings(ref, enc, hp, seed, k, args.fault or "bf16_control")
        print(json.dumps({"workload": args.workload, "seed": seed,
                          args.fault or "control": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
