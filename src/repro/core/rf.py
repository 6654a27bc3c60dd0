"""Random Forest learner (Breiman 2001): bootstrap bagging, per-node attribute
sampling (sqrt rule default), deep trees, winner-take-all voting, and
out-of-bag Self-Evaluation (§3.6).
"""
from __future__ import annotations

import hashlib

import numpy as np

from repro.core.api import Learner, Task, YdfError, register_learner
from repro.core.evaluation import evaluate_predictions
from repro.core.grower import (
    GrowthParams,
    engine_details,
    grow_trees,
    resolve_engine,
)
from repro.core.hparams import RFHparams
from repro.obs import build_training_logs, trace
from repro.core.models import RandomForestModel, prepare_train_data
from repro.core.splitters import SplitterParams
from repro.core.tree import empty_forest, predict_raw


def training_data_fingerprint(X: np.ndarray, y: np.ndarray) -> str:
    """Digest of the encoded feature matrix + labels. The BatchEncoder
    reproduces ``raw_matrix`` bit-for-bit (tested), so re-encoding the
    training dataset at analysis time yields the same digest — and any
    other dataset (even one of equal size) does not."""
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(X, np.float32).tobytes())
    h.update(np.ascontiguousarray(y, np.float64).tobytes())
    return h.hexdigest()


@register_learner("RANDOM_FOREST")
class RandomForestLearner(Learner):
    # hyper-parameter templates (``template="benchmark_rank1"``) are applied
    # by the Learner base BEFORE explicit overrides (§3.11)

    def default_hparams(self) -> RFHparams:
        return RFHparams()

    def train(self, dataset, valid=None, checkpoint=None) -> RandomForestModel:
        hp: RFHparams = self.hparams
        td = prepare_train_data(self, dataset, max_bins=hp.max_bins)
        N, F = td.binned.codes.shape
        if self.task == Task.CLASSIFICATION:
            C = td.n_classes
            stat_kind, out_dim, S = "class", C, C + 1
            onehot = np.eye(C)[td.y]                     # (N, C)
            base_stats = np.concatenate([onehot, np.ones((N, 1))], 1)

            def leaf_fn(s):
                tot = max(s[-1], 1e-12)
                return (s[:-1] / tot).astype(np.float32)
        else:
            stat_kind, out_dim, S = "moment", 1, 3
            base_stats = np.stack([td.y, np.square(td.y), np.ones(N)], 1)

            def leaf_fn(s):
                return np.array([s[0] / max(s[-1], 1e-12)], np.float32)

        if hp.num_candidate_attributes == "SQRT":
            ratio = min(1.0, np.sqrt(F) / F)  # Breiman rule of thumb
        elif hp.num_candidate_attributes == "ALL":
            ratio = 1.0
        else:
            ratio = float(hp.num_candidate_attributes)
        oblique = hp.split_axis == "SPARSE_OBLIQUE"
        sp = SplitterParams(
            stat_kind=stat_kind, min_examples=hp.min_examples,
            categorical_algorithm=hp.categorical_algorithm,
            num_candidate_ratio=ratio, oblique=oblique,
            oblique_num_projections_exponent=hp.sparse_oblique_num_projections_exponent)
        # Per-tree rng streams + keyed per-node feature sampling: every draw
        # is a function of (seed, tree) or (seed, tree, node), never of the
        # order trees or nodes are processed in. That makes the growth
        # schedule semantics-free, so independent trees can grow as lockstep
        # BLOCKS (one level pass over tree_parallelism trees at a time —
        # grower.grow_trees / DESIGN.md §6.3) with forests bit-identical to
        # sequential growth at equal seeds (tested).
        gp = GrowthParams(max_depth=hp.max_depth, max_nodes=hp.max_num_nodes,
                          growing_strategy=hp.growing_strategy, splitter=sp,
                          engine=hp.growth_engine,
                          histogram_backend=hp.histogram_backend,
                          feature_sampling="keyed",
                          sampling_key=self.seed & 0xFFFFFFFF)
        engine_used, fallback = resolve_engine(gp, td.binned, oblique)
        block = max(1, int(hp.tree_parallelism))
        n_num = int((~td.binned.is_cat).sum())
        forest = empty_forest(hp.num_trees, hp.max_num_nodes, out_dim,
                              oblique_dims=n_num if oblique else 0,
                              feature_names=td.features)
        forest.out_dim = out_dim
        forest.tree_class = None
        forest.init_pred = np.zeros(out_dim, np.float32)

        oob_sum = np.zeros((N, out_dim), np.float64)
        oob_cnt = np.zeros(N, np.int64)
        tree_rng = [np.random.default_rng((self.seed & 0xFFFFFFFF, 104729, t))
                    for t in range(hp.num_trees)]

        # -- checkpoint seam (DESIGN.md §11). RF checkpoints only at
        # LOCKSTEP BLOCK boundaries so the resumed `range(trees_done, ...)`
        # realigns with the tree-parallel blocks; per-tree keyed rng streams
        # are re-derived from (seed, tree), so no generator state is stored.
        from repro.train.checkpoint import (
            forest_payload, open_session, restore_forest)
        sess = open_session(checkpoint, self.train_config(),
                            training_data_fingerprint(td.X_raw, td.y))
        trees_done, interrupted = 0, False

        def _payload(complete: bool) -> dict:
            return {"kind": "rf", "trees_done": trees_done,
                    "done": bool(complete),
                    "forest": forest_payload(forest, trees_done),
                    "oob_sum": np.copy(oob_sum), "oob_cnt": np.copy(oob_cnt)}

        if sess is not None:
            state = sess.resume()
            if state is not None:
                trees_done = int(state["trees_done"])
                restore_forest(forest, state["forest"])
                oob_sum[:] = state["oob_sum"]
                oob_cnt[:] = state["oob_cnt"]

        import contextlib
        with (sess if sess is not None else contextlib.nullcontext()):
            for b0 in range(trees_done, hp.num_trees, block):
                ts = list(range(b0, min(b0 + block, hp.num_trees)))
                counts_b, stats_b = [], []
                for t in ts:
                    if hp.bootstrap:
                        counts = tree_rng[t].multinomial(
                            N, np.full(N, 1.0 / N)).astype(np.float64)
                    else:
                        counts = np.ones(N)
                    counts_b.append(counts)
                    stats_b.append(base_stats * counts[:, None])
                with trace.span("rf/block", first_tree=ts[0],
                                trees=len(ts)):
                    grow_trees(forest, ts, td.binned, td.X_raw, stats_b,
                               [c > 0 for c in counts_b], leaf_fn, gp,
                               [tree_rng[t] for t in ts], td.num_lo,
                               td.num_hi, block=block)
                if hp.compute_oob and hp.bootstrap:
                    from repro.core.gbt import _one_tree
                    for bi, t in enumerate(ts):
                        oob = counts_b[bi] == 0
                        if not oob.any():
                            continue
                        pr = predict_raw(_one_tree(forest, t), td.X_raw[oob])[:, 0]
                        if hp.winner_take_all and out_dim > 1:
                            vote = np.zeros_like(pr)
                            vote[np.arange(len(pr)), pr.argmax(1)] = 1.0
                            pr = vote
                        oob_sum[oob] += pr
                        oob_cnt[oob] += 1
                trees_done = ts[-1] + 1
                if sess is not None:
                    complete = trees_done == hp.num_trees
                    if not complete and sess.should_stop():
                        interrupted = True
                    sess.save(trees_done, _payload(complete), done=complete,
                              force=complete or interrupted)
                    if interrupted:
                        break
        if interrupted:
            # servable truncated model: only fully-grown trees survive
            forest = forest.truncated(max(trees_done, 1))

        self_eval = None
        if hp.compute_oob and hp.bootstrap and (oob_cnt > 0).any():
            seen = oob_cnt > 0
            preds = oob_sum[seen] / oob_cnt[seen, None]
            if self.task == Task.CLASSIFICATION:
                preds = preds / np.maximum(preds.sum(1, keepdims=True), 1e-12)
                self_eval = evaluate_predictions(
                    self.task, preds, td.y[seen], classes=td.classes,
                    source="out-of-bag")
            else:
                self_eval = evaluate_predictions(self.task, preds[:, 0],
                                                 td.y[seen], source="out-of-bag")

        model = RandomForestModel(
            winner_take_all=hp.winner_take_all, forest=forest, spec=td.ds.spec,
            features=td.features, label=self.label, task=self.task,
            classes=td.classes, self_evaluation=self_eval)
        oob_logs = None
        if self_eval is not None:
            # surface the OOB result (it was previously reachable only via
            # self_evaluation) and the per-example coverage
            oob_logs = {
                "source": self_eval.source,
                "n_examples": self_eval.n_examples,
                "metrics": {k: float(v) for k, v in self_eval.metrics.items()
                            if isinstance(v, float)},
                "coverage": float((oob_cnt > 0).mean()),
                "mean_trees_per_example": float(oob_cnt.mean()),
            }
        model.training_logs = build_training_logs(
            learner="rf", num_trees=forest.n_trees,
            growth_engine=engine_used, engine_fallback=fallback,
            resilience=sess.events if sess is not None else None,
            interrupted=interrupted,
            extra={"tree_parallelism": block, "oob": oob_logs,
                   **engine_details(gp, td.binned, engine_used)})
        if hp.compute_oob and hp.bootstrap:
            # everything needed to REGENERATE the per-tree bootstrap bags
            # post-hoc (the multinomial draw is the first consumption of each
            # per-tree rng stream): the OOB permutation-importance engine
            # (repro/analysis) rebuilds counts from this instead of the model
            # storing T x N masks. The fingerprint lets that engine verify a
            # dataset IS the training set (same encoded features + labels),
            # not merely one of the same size.
            model.bag_info = {
                "seed": self.seed & 0xFFFFFFFF, "n_rows": N,
                "num_trees": forest.n_trees,
                "fingerprint": training_data_fingerprint(td.X_raw, td.y)}
        return model
