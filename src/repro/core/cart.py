"""CART learner (Breiman et al. 1984): a single tree grown on a train split
and pruned bottom-up on a self-extracted validation split (reduced-error
pruning), as in YDF's CART.
"""
from __future__ import annotations

import numpy as np

from repro.obs import build_training_logs, trace
from repro.core.api import Learner, Task, register_learner
from repro.core.grower import GrowthParams, grow_tree
from repro.core.hparams import CartHparams
from repro.core.models import CartModel, extract_validation, prepare_train_data
from repro.core.splitters import SplitterParams
from repro.core.tree import Forest, predict_raw, empty_forest


@register_learner("CART")
class CartLearner(Learner):
    def default_hparams(self) -> CartHparams:
        return CartHparams()

    def train(self, dataset, valid=None, checkpoint=None) -> CartModel:
        hp: CartHparams = self.hparams
        rng = np.random.default_rng(self.seed)
        with trace.span("learner/prepare", learner="cart"):
            td = prepare_train_data(self, dataset, max_bins=hp.max_bins)
            N = td.ds.n_rows
            if valid is None and N >= 20:
                tr_idx, va_idx = extract_validation(N, hp.validation_ratio, self.seed)
            else:
                tr_idx, va_idx = np.arange(N), np.arange(0)
            if self.task == Task.CLASSIFICATION:
                C = td.n_classes
                stat_kind, out_dim = "class", C
                base = np.concatenate([np.eye(C)[td.y], np.ones((N, 1))], 1)

                def leaf_fn(s):
                    return (s[:-1] / max(s[-1], 1e-12)).astype(np.float32)
            else:
                stat_kind, out_dim = "moment", 1
                base = np.stack([td.y, np.square(td.y), np.ones(N)], 1)

                def leaf_fn(s):
                    return np.array([s[0] / max(s[-1], 1e-12)], np.float32)

            sp = SplitterParams(stat_kind=stat_kind, min_examples=hp.min_examples,
                                categorical_algorithm=hp.categorical_algorithm)
            gp = GrowthParams(max_depth=hp.max_depth, max_nodes=hp.max_num_nodes,
                              growing_strategy="LOCAL", splitter=sp,
                              engine=hp.growth_engine,
                              histogram_backend=hp.histogram_backend)
            forest = empty_forest(1, hp.max_num_nodes, out_dim,
                                  feature_names=td.features)
            forest.out_dim = out_dim
            forest.tree_class = None

            # -- checkpoint seam (DESIGN.md §11). A single tree has one interior
            # boundary: grown-but-unpruned. Pruning is deterministic given
            # (forest, seed-derived validation split), so resuming from the
            # "grown" stage and re-pruning is bit-identical to a clean run.
            from repro.train.checkpoint import (
                forest_payload, open_session, restore_forest)
            from repro.core.rf import training_data_fingerprint
            sess = open_session(checkpoint, self.train_config(),
                                training_data_fingerprint(td.X_raw, td.y))
            state = sess.resume() if sess is not None else None
            grown = pruned = False
            interrupted = False
            if state is not None:
                restore_forest(forest, state["forest"])
                grown, pruned = True, bool(state["done"])

            def _payload(complete: bool) -> dict:
                return {"kind": "cart", "trees_done": 1, "done": bool(complete),
                        "forest": forest_payload(forest, 1)}

        import contextlib
        with (sess if sess is not None else contextlib.nullcontext()):
            if not grown:
                w = np.zeros(N)
                w[tr_idx] = 1.0
                with trace.span("cart/grow"):
                    grow_tree(forest, 0, td.binned, td.X_raw,
                              base * w[:, None], w > 0, leaf_fn, gp, rng)
                if sess is not None and sess.should_stop():
                    # servable unpruned tree now; pruning happens on resume
                    interrupted = True
                    sess.save(1, _payload(False), done=False, force=True)
            if not pruned and not interrupted:
                if len(va_idx):
                    with trace.span("cart/prune", valid_rows=len(va_idx)):
                        _prune(forest, td.X_raw[va_idx], td.y[va_idx],
                               self.task)
                pruned = True
                if sess is not None:
                    sess.save(1, _payload(True), done=True, force=True)

        model = CartModel(winner_take_all=False, forest=forest, spec=td.ds.spec,
                          features=td.features, label=self.label, task=self.task,
                          classes=td.classes)
        model.training_logs = build_training_logs(
            learner="cart", num_trees=1,
            growth_engine=hp.growth_engine, engine_fallback=None,
            resilience=sess.events if sess is not None else None,
            interrupted=interrupted)
        return model


def _prune(forest: Forest, Xv: np.ndarray, yv: np.ndarray, task: Task) -> None:
    """Reduced-error pruning: convert an internal node to a leaf whenever that
    does not hurt validation accuracy / squared error."""
    t = 0
    n = int(forest.n_nodes[t])

    def valid_score() -> float:
        pr = predict_raw(forest, Xv)[:, 0]          # (Nv, out_dim)
        if task == Task.CLASSIFICATION:
            return float((pr.argmax(1) == yv).mean())
        return -float(np.mean(np.square(pr[:, 0] - yv)))

    # bottom-up: children have larger ids than parents by construction
    internal = [i for i in range(n) if forest.left_child[t, i] >= 0]
    for node in sorted(internal, reverse=True):
        before = valid_score()
        saved = forest.left_child[t, node]
        forest.left_child[t, node] = -1
        if valid_score() < before:
            forest.left_child[t, node] = saved      # revert
