"""The benchmark's door into the library's ranking task, beside
``program.py``: the LambdaMART learner a ranking configuration names, the
guards that refuse a library or a run whose lambda pass is not on the
device, and that pass itself for the comparison after the window.
"""
from __future__ import annotations

import numpy as np

from program import PathError
from harness import RunError


def check_lambda_pass() -> None:
    """Refuse, before anything is made or trained, a library whose ranking
    loss does not compute its lambda gradients on the device: a host pass
    that pads every query to the largest builds (G, m, m) float64 tensors
    of tens of GB at this configuration's shape."""
    from repro.tasks import ranking
    where = getattr(ranking, "LAMBDA_PASS", None)
    if where != "device":
        raise RunError(f"the library's ranking lambda pass is {where!r}, "
                       "not 'device'")


def learner(config: dict, seed: int):
    from repro.core import GradientBoostedTreesLearner, Task
    spec = config["learner"]
    if spec["learner"] != "GRADIENT_BOOSTED_TREES" or spec["task"] != \
            "RANKING":
        raise ValueError(f"not a ranking GBT configuration: {spec}")
    return GradientBoostedTreesLearner(
        label=config["dataset"]["label"]["name"], task=Task.RANKING,
        seed=int(seed), **spec["hparams"])


def check_ranking_pass(model) -> None:
    """Refuse a model whose lambda pass did not run on the device."""
    where = model.training_logs.get("ranking_pass")
    if where != "device":
        raise PathError(f"ranking_pass={where!r}, not 'device'")


def lambda_gradient(scores: np.ndarray, rel: np.ndarray, qid: np.ndarray,
                    k: int) -> np.ndarray:
    """The library's device lambda gradient at ``scores`` over the queries
    ``qid``, as the learner computes it."""
    from repro.tasks.ranking import group_layout, lambda_grad_device
    g, _ = lambda_grad_device(scores, rel, group_layout(qid), k=k)
    return np.asarray(g, np.float64)
