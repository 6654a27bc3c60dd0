"""Gradient Boosted Trees learner (Friedman 2001), YDF-default-faithful:
paper App. C.1 defaults, LOSS_INCREASE early stopping on a self-extracted
validation set (§3.3), LOCAL or BEST_FIRST_GLOBAL growth, CART/RANDOM/ONE_HOT
categorical splits, optional sparse-oblique splits, deterministic training.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.obs import build_training_logs, trace
from repro.core.api import Learner, Task, YdfError, register_learner
from repro.core.grower import GrowthParams, grow_tree
from repro.core.hparams import GBTHparams
from repro.core.losses import make_loss
from repro.core.models import (
    GradientBoostedTreesModel,
    TrainData,
    extract_validation,
    prepare_train_data,
)
from repro.core.evaluation import Evaluation, evaluate_predictions
from repro.core.splitters import SplitterParams
from repro.core.tree import Forest, empty_forest, predict_raw


@register_learner("GRADIENT_BOOSTED_TREES")
class GradientBoostedTreesLearner(Learner):
    # hyper-parameter templates (``template="benchmark_rank1"``) are applied
    # by the Learner base BEFORE explicit overrides (§3.11)

    def default_hparams(self) -> GBTHparams:
        return GBTHparams()

    # ------------------------------------------------------------- train
    def train(self, dataset, valid=None, checkpoint=None
              ) -> GradientBoostedTreesModel:
        hp: GBTHparams = self.hparams
        rng = np.random.default_rng(self.seed)
        td = prepare_train_data(self, dataset, max_bins=hp.max_bins)

        # §3.3: extract validation from train when early stopping needs one.
        # Ranking keeps every group WHOLE on one side of the split — a torn
        # group corrupts both its lambda pairs and its NDCG.
        groups_v = None
        if valid is not None:
            train_idx = np.arange(td.ds.n_rows)
            Xv, yv, wv, groups_v = _encode_eval_set(self, td, valid)
        elif hp.early_stopping != "NONE" and hp.validation_ratio > 0:
            if self.task == Task.RANKING:
                from repro.tasks.ranking import group_aware_split
                train_idx, valid_idx = group_aware_split(
                    td.groups, hp.validation_ratio, self.seed)
            else:
                train_idx, valid_idx = extract_validation(
                    td.ds.n_rows, hp.validation_ratio, self.seed)
            Xv, yv = td.X_raw[valid_idx], td.y[valid_idx]
            wv = td.w[valid_idx]
            if td.groups is not None:
                groups_v = td.groups[valid_idx]
        else:
            train_idx = np.arange(td.ds.n_rows)
            Xv = yv = wv = None

        sub_td = _subset_td(td, train_idx)
        N = len(train_idx)
        y, w = sub_td.y, sub_td.w

        if self.task == Task.RANKING:
            # built here, not in make_loss: the loss owns the train/valid
            # group layouts, which only exist after the split above
            from repro.tasks.ranking import LambdaMARTLoss, group_layout
            loss = LambdaMARTLoss(
                y, group_layout(sub_td.groups), k=hp.ndcg_truncation,
                y_valid=yv,
                layout_valid=None if yv is None else group_layout(groups_v))
        else:
            loss = make_loss(self.task, hp.loss, td.n_classes)
        K = loss.out_dim

        max_nodes = (hp.max_num_nodes if hp.growing_strategy == "BEST_FIRST_GLOBAL"
                     else 2 ** (hp.max_depth + 1))
        oblique = hp.split_axis == "SPARSE_OBLIQUE"
        n_num = int((~td.binned.is_cat).sum())
        forest = empty_forest(hp.num_trees * K, max_nodes, 1,
                              oblique_dims=n_num if oblique else 0,
                              feature_names=td.features)
        forest.init_pred = np.zeros(K, np.float32)
        init = loss.init_pred(y, w)
        forest.init_pred[:] = init
        forest.out_dim = K
        forest.tree_class = np.arange(hp.num_trees * K, dtype=np.int32) % K

        sp = SplitterParams(
            stat_kind="gh", min_examples=hp.min_examples,
            l2=hp.l2_regularization, categorical_algorithm=hp.categorical_algorithm,
            num_candidate_ratio=(hp.num_candidate_attributes_ratio
                                 if hp.num_candidate_attributes_ratio > 0 else 1.0),
            oblique=oblique,
            oblique_num_projections_exponent=hp.sparse_oblique_num_projections_exponent,
        )
        gp = GrowthParams(max_depth=hp.max_depth, max_nodes=max_nodes,
                          growing_strategy=hp.growing_strategy, splitter=sp,
                          engine=hp.growth_engine,
                          histogram_backend=hp.histogram_backend,
                          sampling_key=self.seed & 0xFFFFFFFF)
        from repro.core.grower import engine_details, resolve_engine
        engine_used, engine_fallback = resolve_engine(gp, td.binned, oblique)
        shrink, l2 = hp.shrinkage, hp.l2_regularization

        def leaf_fn(s):
            # s = [sum g, sum h_gain, sum h_true, count]; Newton step * shrinkage
            return np.array([-shrink * s[0] / (s[2] + l2 + 1e-12)], np.float32)

        pred = np.tile(init[None, :], (N, 1)).astype(np.float64)
        pred_v = (np.tile(init[None, :], (len(yv), 1)).astype(np.float64)
                  if yv is not None else None)
        best_loss, best_t, patience = np.inf, 0, hp.early_stopping_patience
        train_losses, valid_losses = [], []

        # -- checkpoint seam (DESIGN.md §11): the bit-identical-resume
        # closure is (forest slices, pred, pred_v, early-stop bookkeeping,
        # rng.bit_generator.state) snapshotted at tree boundaries. The seam
        # sits OUTSIDE grow_tree, so host-batched and device engines
        # checkpoint identically.
        from repro.train.checkpoint import (
            forest_payload, open_session, restore_forest)
        from repro.core.rf import training_data_fingerprint
        sess = open_session(checkpoint, self.train_config(),
                            training_data_fingerprint(td.X_raw, td.y))
        trees_done, stopped, interrupted = 0, False, False

        def _payload(complete: bool) -> dict:
            return {"kind": "gbt", "trees_done": trees_done,
                    "done": bool(complete),
                    "forest": forest_payload(forest, trees_done * K),
                    "pred": np.copy(pred),
                    "pred_v": None if pred_v is None else np.copy(pred_v),
                    "rng_state": rng.bit_generator.state,
                    "best_loss": float(best_loss), "best_t": int(best_t),
                    "train_losses": list(train_losses),
                    "valid_losses": list(valid_losses)}

        if sess is not None:
            state = sess.resume()
            if state is not None:
                trees_done = int(state["trees_done"])
                stopped = bool(state["done"])
                restore_forest(forest, state["forest"])
                pred[:] = state["pred"]
                if pred_v is not None and state["pred_v"] is not None:
                    pred_v[:] = state["pred_v"]
                rng.bit_generator.state = state["rng_state"]
                best_loss = state["best_loss"]
                best_t = state["best_t"]
                train_losses = list(state["train_losses"])
                valid_losses = list(state["valid_losses"])

        import contextlib
        with (sess if sess is not None else contextlib.nullcontext()):
            for it in range(trees_done, hp.num_trees):
                if stopped:
                    break
                with trace.span("gbt/grad_hess", iteration=it):
                    g, h = loss.grad_hess(pred, y, w)
                bag = w if hp.subsample >= 1.0 else w * (rng.random(N) < hp.subsample)
                for k in range(K):
                    t = it * K + k
                    stats = np.stack([
                        g[:, k] * bag,
                        (h[:, k] if hp.use_hessian_gain else np.ones(N)) * bag,
                        h[:, k] * bag,
                        bag,
                    ], axis=1).astype(np.float64)
                    with trace.span("gbt/tree", tree=t, iteration=it):
                        node_of = grow_tree(forest, t, sub_td.binned,
                                            sub_td.X_raw, stats, bag > 0,
                                            leaf_fn, gp, rng,
                                            sub_td.num_lo, sub_td.num_hi)
                    vals = forest.leaf_value[t, np.maximum(node_of, 0), 0]
                    upd = np.where(node_of >= 0, vals, 0.0)
                    if hp.subsample < 1.0:  # OOB examples still move (predict path)
                        oob = (bag <= 0)
                        if oob.any():
                            tr = predict_raw(_one_tree(forest, t), sub_td.X_raw[oob])
                            upd = upd.copy()
                            upd[oob] = tr[:, 0, 0]
                    pred[:, k] += upd
                    if pred_v is not None:
                        pv = predict_raw(_one_tree(forest, t), Xv)[:, 0, 0]
                        pred_v[:, k] += pv
                trees_done = it + 1
                train_losses.append(loss.value(pred, y, w))
                if pred_v is not None:
                    vl = loss.value(pred_v, yv, wv)
                    valid_losses.append(vl)
                    if vl < best_loss - 1e-9:
                        best_loss, best_t = vl, it + 1
                    elif hp.early_stopping == "LOSS_INCREASE" and it + 1 - best_t >= patience:
                        stopped = True
                if sess is not None:
                    complete = stopped or trees_done == hp.num_trees
                    if not complete and sess.should_stop():
                        interrupted = True
                    sess.save(trees_done, _payload(complete), done=complete,
                              force=complete or interrupted)
                    if interrupted:
                        break

        n_keep = (best_t if pred_v is not None and hp.early_stopping != "NONE"
                  and not interrupted else trees_done) * K
        forest = forest.truncated(max(min(n_keep, trees_done * K), K))
        self_eval = None
        if pred_v is not None and len(yv):
            act = loss.activation(pred_v)
            if self.task == Task.CLASSIFICATION:
                self_eval = evaluate_predictions(self.task, act, yv,
                                                 classes=td.classes,
                                                 source="validation")
            elif self.task == Task.RANKING:
                self_eval = evaluate_predictions(self.task, act, yv,
                                                 groups=groups_v,
                                                 source="validation")
            else:
                self_eval = evaluate_predictions(self.task, act, yv,
                                                 source="validation")
        # a loss that holds training-set state (LambdaMART's group layouts)
        # ships a stripped serving head instead, so pickled models stay small
        model_loss = loss.serving_head() if hasattr(loss, "serving_head") else loss
        model = GradientBoostedTreesModel(
            loss=model_loss, forest=forest, spec=td.ds.spec,
            features=td.features, label=self.label, task=self.task,
            classes=td.classes, self_evaluation=self_eval)
        if self.task == Task.RANKING:
            model.ranking_group = hp.ranking_group
        model.training_logs = build_training_logs(
            learner="gbt", num_trees=forest.n_trees // K,
            growth_engine=engine_used, engine_fallback=engine_fallback,
            resilience=sess.events if sess is not None else None,
            interrupted=interrupted,
            extra={"train_loss": train_losses, "valid_loss": valid_losses,
                   **engine_details(gp, td.binned, engine_used)})
        return model


def _one_tree(forest: Forest, t: int) -> Forest:
    return dataclasses.replace(
        forest,
        feature=forest.feature[t:t + 1], threshold=forest.threshold[t:t + 1],
        split_bin=forest.split_bin[t:t + 1], cat_mask=forest.cat_mask[t:t + 1],
        left_child=forest.left_child[t:t + 1],
        leaf_value=forest.leaf_value[t:t + 1], n_nodes=forest.n_nodes[t:t + 1],
        obl_weights=None if forest.obl_weights is None else forest.obl_weights[t:t + 1],
        obl_features=None if forest.obl_features is None else forest.obl_features[t:t + 1],
        tree_class=None if forest.tree_class is None else forest.tree_class[t:t + 1])


def _encode_eval_set(learner, td: TrainData, valid):
    """Encode an external validation set with the TRAINING dataspec so class
    indices and imputation match (paper §3.3 external-valid path). For
    ranking the 4th return is the valid set's group ids (else None), read
    from the RAW column — the training vocabulary must not collapse unseen
    validation groups into one out-of-dictionary bucket."""
    from repro.core.models import _as_vertical, raw_matrix
    vds = _as_vertical(valid, td.ds.spec)
    Xv = raw_matrix(vds, td.features)
    if learner.task == Task.CLASSIFICATION:
        enc = vds.categorical[learner.label]
        if (enc <= 0).any():
            raise YdfError(
                f'Validation label "{learner.label}" contains values unseen in '
                "training (or missing). Solution: filter those rows.")
        yv = (enc - 1).astype(np.int32)
    else:
        yv = vds.numerical[learner.label].astype(np.float64)
    groups_v = None
    if learner.task == Task.RANKING:
        from repro.core.dataspec import VerticalDataset
        gcol = learner.hparams.ranking_group
        if isinstance(valid, VerticalDataset):
            col = np.asarray(valid.column(gcol))
        else:
            if gcol not in valid:
                raise YdfError(
                    f'Ranking validation set is missing the group column '
                    f'"{gcol}".')
            col = np.asarray(valid[gcol], dtype=object).ravel()
        groups_v = np.unique(col.astype(str),
                             return_inverse=True)[1].astype(np.int64)
    return Xv, yv, np.ones(len(yv), np.float64), groups_v


def _subset_td(td: TrainData, idx: np.ndarray) -> TrainData:
    import dataclasses as dc
    if len(idx) == td.ds.n_rows and (idx == np.arange(len(idx))).all():
        return td
    binned = dc.replace(td.binned, codes=td.binned.codes[idx])
    return dc.replace(td, binned=binned, X_raw=td.X_raw[idx], y=td.y[idx],
                      w=td.w[idx],
                      groups=None if td.groups is None else td.groups[idx])
