#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. Everything that
belongs to it is data found by name:

* ``bench/configs/<config>.json``: the configuration (shapes, learner
  hyper-parameters, data generator parameters, serving forest, the name of
  its plain reference module beside it);
* ``bench/traffic/<mix>.json``: the traffic mix or job, whose ``driver``
  names the general generator in ``bench/drivers/`` that reads it;
* ``bench/workloads/<cell>.json``: the cell's own parameters (its offered
  rate, the path it must stay on) and the limits of its correctness
  comparison;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run refuses, printing no result, unless JAX's first device is a TPU whose
``device_kind`` is in ``bench/peaks.json`` and there are as many chips as the
cell asks for. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the measured window, the library's spans and the server's counters.
After the window the run compares what the timed path produced with the
plain reference; the numbers compared are printed with their limits as the
last lines on standard error and under ``checks`` in the result line.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _sub in ("lib", "drivers", "configs"):
    _p = os.path.join(HERE, _sub)
    if _p not in sys.path:
        sys.path.insert(0, _p)

import devtrace  # noqa: E402
from harness import Ctx, RunError  # noqa: E402


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, traffic
    mix and cell file loaded, and the metrics it reports."""
    bench = bench or _json(os.path.join(ROOT, "BENCHMARK.json"))
    hits = [w for w in bench["workloads"] if w["name"] == name]
    if not hits:
        raise RunError(f"no workload {name!r} in BENCHMARK.json")
    w = hits[0]
    config = _json(os.path.join(HERE, "configs", w["config"] + ".json"))
    mix = _json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    cell = _json(os.path.join(HERE, "workloads", name + ".json"))

    def reports(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return {"entry": w, "config": config, "mix": mix, "cell": cell,
            "end_to_end": e2e, "per_layer": layer}


def check_device(chips: int) -> dict:
    """The chips this run measures, or RunError."""
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise RunError(f"no TPU: JAX's first device is "
                       f"{devs[0].platform if devs else None!r}")
    kind = devs[0].device_kind
    peaks = _json(os.path.join(HERE, "peaks.json"))
    if kind not in peaks:
        raise RunError(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": kind, "count": chips,
            "peaks": peaks[kind]}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer_metrics(spec: dict, ctx: Ctx, out, reading) -> dict:
    """Each per-layer metric the cell reports, from its reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    got = {}
    for m in spec["per_layer"]:
        v = _reader(m["name"])(reading)
        if v is not None:
            got[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return got


def execute(spec: dict, seed: int, seconds: float, trace: bool,
            device: dict) -> dict:
    """Drive one run of a loaded cell on ``device`` and return its result
    object (without printing it)."""
    params = {**spec["mix"].get("params", {}),
              **spec["cell"].get("params", {})}
    ctx = Ctx(cell=spec["entry"]["name"], config=spec["config"],
              params=params, limits=spec["cell"]["limits"], seed=int(seed),
              seconds=float(seconds), trace=bool(trace), t_start=T_START,
              chips=int(spec["entry"]["chips"]))
    driver = importlib.import_module(spec["mix"]["driver"])
    try:
        out = driver.run(ctx)
        result = {"correct": False, "attempted": int(out.attempted),
                  "failed": int(out.failed)}
        dev = {"platform": device["platform"], "kind": device["kind"],
               "count": device["count"],
               "memory_peak_bytes": memory_peak_bytes(ctx.chips)
               if device["platform"] == "tpu" else 0}
        reading = None
        if trace:
            reading = devtrace.Reading.from_run(ctx, out, device)
            dev["busy_s"] = reading.busy_s
            dev["window_s"] = reading.window_s
        # the plain reference runs after the window, after the peak was
        # read and once the driver has dropped the program's state
        driver.compare(ctx, out)
        if trace:
            metrics = layer_metrics(spec, ctx, out, reading)
            if out.layer.get("compiles_in_window"):
                out.notes.append(f"{out.layer['compiles_in_window']} level "
                                 "steps compiled inside the window")
        else:
            metrics = {m["name"]: {"value": float(out.metrics[m["name"]]),
                                   "unit": m["unit"]}
                       for m in spec["end_to_end"] if m["name"] != "setup_s"}
            metrics["setup_s"] = {"value": float(out.setup_s), "unit": "s"}
        result["correct"] = bool(out.checks) and all(c.ok for c in
                                                    out.checks)
        result["metrics"] = metrics
        result["device"] = dev
        if reading is not None:
            result["breakdown"] = reading.breakdown()
        result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                            for c in out.checks}
        result["_notes"] = out.notes
        return result
    finally:
        shutil.rmtree(ctx.tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_cell(args.workload)
        import program
        program.configure_compile_cache()
        device = check_device(int(spec["entry"]["chips"]))
        result = execute(spec, args.seed, args.seconds, bool(args.trace),
                         device)
    except RunError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    notes = result.pop("_notes")
    for line in notes:
        print(line, file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
