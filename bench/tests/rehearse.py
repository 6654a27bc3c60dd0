"""Drive a whole benchmark run on the CPU at a tiny size, past the harness's
look for a chip: the cell's own driver, window, trace reduction, readers
and reference comparison, with the configuration cut down by ``shrink``.
What such a run prints is not a device measurement; the tests read only
its structure and its checks.

    JAX_PLATFORMS=cpu python3 bench/tests/rehearse.py <cell> [seed] [trace]
"""
from __future__ import annotations

import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

# tiny sizes, and no demand for the chip's kernels, which the CPU lacks
TINY = {
    "train": {"config": {"train_rows": 4000},
              "hparams": {"num_trees": 6, "max_depth": 3},
              "params": {"device_impl": None}},
    "score": {"config": {},
              "forest": {"trees": 12},
              "params": {"engine": None, "batch_rows": 3000,
                         "forest_sample_rows": 3000, "sample_rows": 200}},
    "online": {"config": {"pool_rows": 2000},
               "forest": {"trees": 12},
               "params": {"engine": None, "rate_per_s": 150,
                          "max_batch": 64, "warm_requests": 20}},
}


ONLINE = os.path.join(HERE, "online_cell.json")


def bench_with_online() -> dict:
    """``BENCHMARK.json`` with the entries of the ``adult_gbt.online`` cell
    (``online_cell.json``), which the benchmark leaves out until the stalls
    its runs showed are understood (``PERF.md``, section 7). Its driver,
    traffic mix, cell file and readers stay tested through them."""
    with open(os.path.join(BENCH, os.pardir, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(ONLINE) as f:
        for key, entries in json.load(f).items():
            bench[key] = bench[key] + entries
    return bench


def load(cell: str) -> dict:
    """``run.load_cell`` of a benchmark cell or of the online cell."""
    return run.load_cell(cell, bench_with_online())


def shrink(spec: dict, **limits) -> dict:
    spec = copy.deepcopy(spec)
    t = TINY[spec["entry"]["traffic"]]
    spec["config"].update(t["config"])
    spec["config"]["learner"]["hparams"].update(t.get("hparams", {}))
    spec["config"]["serving_forest"].update(t.get("forest", {}))
    spec["cell"].setdefault("params", {}).update(t["params"])
    spec["cell"]["limits"].update(limits)
    return spec


def rehearse(cell: str, seed: int = 7, trace: bool = False,
             seconds: float = 1.0, **limits) -> dict:
    spec = shrink(load(cell), **limits)
    import program
    program.configure_compile_cache()
    return run.execute(spec, seed, seconds, trace, CPU)


if __name__ == "__main__":
    cell = sys.argv[1]
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 7
    trace = len(sys.argv) > 3 and sys.argv[3] == "1"
    res = rehearse(cell, seed, trace)
    for line in res.pop("_notes"):
        print(line, file=sys.stderr)
    print(json.dumps(res))
