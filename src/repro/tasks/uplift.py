"""Honest uplift trees (DESIGN.md §12.2; Rzepakowski & Jaroszewicz 2012).

task=UPLIFT rides the ordinary RF-style growth path: the ONLY new pieces are
the "uplift" splitter statistics layout ``[sum_y_treated, n_treated,
sum_y_control, n]`` and its Euclidean-distance gain ``n * (p_t - p_c)^2``
(splitters._score), plus leaves that store the local treatment effect
``p_t - p_c``. Everything else — binning, keyed feature sampling, lockstep
tree blocks, the compiled serving engines — is reused unchanged, which is
exactly the modularity claim the paper makes (§3.1).
"""
from __future__ import annotations

import numpy as np

from repro.obs import build_training_logs, trace
from repro.core.api import Learner, Task, YdfError, register_learner
from repro.core.grower import GrowthParams, grow_trees, resolve_engine
from repro.core.hparams import UpliftHparams
from repro.core.models import UpliftModel, prepare_train_data
from repro.core.splitters import SplitterParams
from repro.core.tree import empty_forest


def uplift_leaf(s: np.ndarray) -> np.ndarray:
    """Leaf value = local treatment effect p_t - p_c; a leaf whose bag
    misses one arm has no estimate and predicts 0 (neutral)."""
    nt = s[1]
    nc = s[3] - s[1]
    if nt <= 0 or nc <= 0:
        return np.zeros(1, np.float32)
    return np.array([s[0] / nt - s[2] / nc], np.float32)


@register_learner("UPLIFT_TREES")
class UpliftTreesLearner(Learner):
    """Forest of honest uplift trees; predict() = estimated uplift."""

    def __init__(self, label: str, task: Task = Task.UPLIFT, **kw):
        if task != Task.UPLIFT:
            raise YdfError(
                f"UPLIFT_TREES only supports task=UPLIFT, got {task}. "
                "Solution: use RANDOM_FOREST/GRADIENT_BOOSTED_TREES for "
                "classification or regression.")
        super().__init__(label, task, **kw)

    def default_hparams(self) -> UpliftHparams:
        return UpliftHparams()

    def train(self, dataset, valid=None, checkpoint=None) -> UpliftModel:
        hp: UpliftHparams = self.hparams
        with trace.span("learner/prepare", learner="uplift"):
            td = prepare_train_data(self, dataset, max_bins=hp.max_bins)
            N, F = td.binned.codes.shape
            t01 = td.treatment.astype(np.float64)
            base_stats = np.stack([td.y * t01, t01,
                                   td.y * (1.0 - t01), np.ones(N)], 1)

            if hp.num_candidate_attributes == "SQRT":
                ratio = min(1.0, np.sqrt(F) / F)
            elif hp.num_candidate_attributes == "ALL":
                ratio = 1.0
            else:
                ratio = float(hp.num_candidate_attributes)
            sp = SplitterParams(stat_kind="uplift", min_examples=hp.min_examples,
                                num_candidate_ratio=ratio)
            gp = GrowthParams(max_depth=hp.max_depth, max_nodes=hp.max_num_nodes,
                              splitter=sp, engine=hp.growth_engine,
                              histogram_backend=hp.histogram_backend,
                              feature_sampling="keyed",
                              sampling_key=self.seed & 0xFFFFFFFF)
            engine_used, fallback = resolve_engine(gp, td.binned, False)
            block = max(1, int(hp.tree_parallelism))
            forest = empty_forest(hp.num_trees, hp.max_num_nodes, 1,
                                  feature_names=td.features)
            forest.tree_class = None
            tree_rng = [np.random.default_rng((self.seed & 0xFFFFFFFF, 104729, t))
                        for t in range(hp.num_trees)]
        for b0 in range(0, hp.num_trees, block):
            ts = list(range(b0, min(b0 + block, hp.num_trees)))
            counts_b = []
            for t in ts:
                if hp.bootstrap:
                    counts_b.append(tree_rng[t].multinomial(
                        N, np.full(N, 1.0 / N)).astype(np.float64))
                else:
                    counts_b.append(np.ones(N))
            grow_trees(forest, ts, td.binned, td.X_raw,
                       [base_stats * c[:, None] for c in counts_b],
                       [c > 0 for c in counts_b], uplift_leaf, gp,
                       [tree_rng[t] for t in ts], td.num_lo, td.num_hi,
                       block=block)

        model = UpliftModel(
            treatment_col=getattr(hp, "treatment", "treatment"),
            forest=forest, spec=td.ds.spec, features=td.features,
            label=self.label, task=self.task, classes=None)
        model.training_logs = build_training_logs(
            learner="uplift", num_trees=forest.n_trees,
            growth_engine=engine_used, engine_fallback=fallback,
            extra={"tree_parallelism": block})
        return model
