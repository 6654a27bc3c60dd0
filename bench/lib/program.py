"""The benchmark's only door into the system under test.

Everything the benchmark asks of the library goes through here: the
learner a configuration names, the servable model of a seeded forest, the
compiled predictor, the server, and the guards that refuse a run whose path
fell back from the chip's kernels. The library is imported from ``src/``
of the checkout; a checkout without it fails at import.
"""
from __future__ import annotations

import contextlib
import os
import sys

import numpy as np

from harness import RunError

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


class PathError(RunError):
    """The run left the path the cell measures (a fallback, another engine)."""


def configure_compile_cache() -> str:
    """The library's fixed cache directory, with every program written to
    it however fast it compiled, so that a second run compiles nothing."""
    import jax
    from repro.jax_cache import configure_compile_cache as cfg
    path = cfg()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def learner(config: dict, seed: int):
    from repro.core import GradientBoostedTreesLearner
    spec = config["learner"]
    if spec["learner"] != "GRADIENT_BOOSTED_TREES":
        raise ValueError(f"unsupported learner {spec['learner']!r}")
    return GradientBoostedTreesLearner(
        label=config["dataset"]["label"]["name"], seed=int(seed),
        **spec["hparams"])


def checkpoint_policy(directory: str, cancel):
    """A policy that saves only when training ends (never on a cadence) and
    polls ``cancel`` at every tree boundary."""
    from repro.train.checkpoint import CheckpointPolicy
    return CheckpointPolicy(directory, every_n_trees=1 << 30, cancel=cancel)


def check_device_training(model, impl: str | None) -> None:
    """Refuse a model whose training left the device grower, or whose level
    step is not ``impl`` (None: any)."""
    logs = model.training_logs
    if logs["growth_engine"] != "device" or logs["engine_fallback"]:
        raise PathError(f"growth_engine={logs['growth_engine']!r}, "
                        f"fallback={logs['engine_fallback']!r}")
    if impl is not None and logs.get("device_impl") != impl:
        raise PathError(f"level step ran {logs.get('device_impl')!r}, not "
                        f"{impl!r} ({logs.get('device_impl_reason')})")


def servable_model(config: dict, forest):
    """A GradientBoostedTreesModel over ``forest`` (tabular.BenchForest),
    with a dataspec that encodes each categorical value as its index in
    the configuration's vocabulary plus one (0 is out-of-dictionary; a
    missing value takes code 1, the most frequent value)."""
    from repro.core.api import Task
    from repro.core.dataspec import Column, DataSpec, Semantic
    from repro.core.losses import Binomial
    from repro.core.models import GradientBoostedTreesModel
    from repro.core.tree import empty_forest

    ds = config["dataset"]
    cols = {}
    for c in ds["columns"]:
        if c["kind"] == "categorical":
            vals = list(c["values"])
            cols[c["name"]] = Column(c["name"], Semantic.CATEGORICAL,
                                     vocab=["<OOD>"] + vals,
                                     counts={v: 1 for v in vals})
        else:
            # no numerical column of these configurations has missing values
            cols[c["name"]] = Column(c["name"], Semantic.NUMERICAL)
    lab = ds["label"]
    cols[lab["name"]] = Column(lab["name"], Semantic.CATEGORICAL,
                               vocab=["<OOD>", lab["negative"],
                                      lab["positive"]])
    spec = DataSpec(columns=cols, n_rows=0)
    feats = [c["name"] for c in ds["columns"]]

    T, n_int = forest.feature.shape
    L = forest.leaf.shape[1]
    M = n_int + L
    f = empty_forest(T, M, 1, feature_names=feats)
    internal = np.arange(n_int)
    f.feature[:, :n_int] = forest.feature
    f.threshold[:, :n_int] = forest.threshold
    cat = forest.is_cat[forest.feature]
    f.cat_mask[:, :n_int] = np.where(cat[..., None], forest.cat_mask, 0)
    f.threshold[:, :n_int] = np.where(cat, 0.0, forest.threshold)
    f.left_child[:, :n_int] = 2 * internal + 1
    f.leaf_value[:, n_int:, 0] = forest.leaf
    f.n_nodes[:] = M
    f.depth = forest.depth
    f.out_dim = 1
    f.init_pred = np.array([forest.bias], np.float32)
    f.tree_class = np.zeros(T, np.int32)
    return GradientBoostedTreesModel(
        loss=Binomial(), forest=f, spec=spec, features=feats,
        label=lab["name"], task=Task.CLASSIFICATION,
        classes=[lab["negative"], lab["positive"]])


def compile_predictor(model, engine: str | None):
    """``compile_predictor(model)`` as a user calls it; refuses a predictor
    whose engine is not ``engine`` (None: any)."""
    from repro.core.engines import compile_predictor as cp
    pred = cp(model)
    if engine is not None and pred.name != engine:
        raise PathError(f"compile_predictor picked {pred.name!r}, "
                        f"not {engine!r}")
    return pred


def forest_server(model, *, deadline_s: float, max_batch: int,
                  engine: str | None):
    """A ForestServer over ``model`` whose engine chain starts at
    ``engine`` (None: any)."""
    from repro.serving.server import ForestServer
    srv = ForestServer(model, default_deadline_s=deadline_s,
                       max_batch=max_batch, max_results=1 << 30)
    first = srv.engine_status()[0]["engine"]
    if engine is not None and first != engine:
        raise PathError(f"server chain starts at {first!r}, not {engine!r}")
    return srv


def check_server_engines(srv, engine: str | None) -> dict:
    """Refuse a server that dispatched any engine but ``engine``."""
    used = srv.metrics.engine_dispatches
    if engine is not None and set(used) - {engine}:
        raise PathError(f"server dispatched engines {used}, "
                        f"not {engine!r} only")
    return used


def request_errors():
    from repro.serving.server import (RequestFailed, RequestShed,
                                      RequestTimedOut)
    return RequestShed, RequestTimedOut, RequestFailed


@contextlib.contextmanager
def library_spans(on: bool):
    """The library's own span tracer (``repro.obs.trace``) while ``on``.
    Yields a list that holds, once the block exits, every span the library
    recorded in it as (name, t0, t1, args) on the host clock."""
    spans: list = []
    if not on:
        yield spans
        return
    from repro.obs import trace
    tracer = trace.start()
    try:
        yield spans
    finally:
        trace.stop()
        spans.extend((s.name, s.t0, s.t1, dict(s.args))
                     for r in tracer.roots for s in r.walk())
