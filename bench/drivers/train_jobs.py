"""Training jobs back to back through ``Learner.train``.

The table is made from the seed before anything is timed. Job 1 begins in
set-up: dataspec, binning, upload and its first tree, which loads every
program the first tree uses. The window opens at the first tree boundary
(the learner's cooperative ``cancel`` probe, polled once per boosting
iteration) and closes at the first boundary at or after ``--seconds``. A job
that ends inside the window (early stopping, or its last tree) is followed
by the next on the same table, with its dataspec and binning inside the
window. ``train_ms_per_tree`` is the window over the trees completed in it.

Correctness: the first ``compare_trees`` trees of job 1 against the plain
reference boosted from the same seed on the same rows
(``bench/configs/<reference>.py``), which takes the program's split
wherever the greedy rule ties: the training loss after each tree, the
gradient that the second tree is grown on, and the trees' summed output on
the training rows (``readings``).
"""
from __future__ import annotations

import importlib
import shutil
import tempfile
import time

import numpy as np

from harness import Check, Outcome, Window, norm_gap
import program
import tabular

_state: dict = {}


def run(ctx) -> Outcome:
    cfg, p = ctx.config, ctx.params
    data = tabular.make_table(cfg["dataset"], int(cfg["train_rows"]),
                              ctx.stream_seed(1))
    learner = program.learner(cfg, ctx.seed)
    win = Window(ctx)
    stamps: list = []                  # tree boundaries inside the window
    stop = {"now": False}

    def cancel() -> bool:
        if win.t0 is None:
            win.open()
            return False
        now = time.perf_counter()
        stamps.append(now)
        if now - win.t0 >= ctx.seconds:
            win.close(now)
            stop["now"] = True
            return True
        return False

    jobs = []        # (model, first tree in the window, trees in the window)
    first = None
    extra_trees = 0
    with program.library_spans(ctx.trace) as obs_spans:
        while not stop["now"]:
            ck = tempfile.mkdtemp(dir=ctx.tmp)
            before = len(stamps)
            with ctx.span("bench/train_job"):
                model = learner.train(data, checkpoint=program
                                      .checkpoint_policy(ck, cancel))
            shutil.rmtree(ck, ignore_errors=True)
            program.check_device_training(model, p.get("device_impl"))
            # a job that ended inside the window completed one more tree
            # than it stamped: its last, after which ``cancel`` is not polled
            done_inside = not stop["now"]
            extra_trees += int(done_inside)
            in_win = len(stamps) - before + int(done_inside)
            jobs.append((model if ctx.trace else None,
                         1 if first is None else 0, in_win))
            if first is None:
                first = model
            del model
    trees = len(stamps) + extra_trees
    ms_per_tree = (win.t1 - win.t0) * 1e3 / trees
    hp = cfg["learner"]["hparams"]
    layer = {"trees": trees, "jobs": len(jobs),
             "obs_spans": obs_spans,
             "train_rows": _train_rows(cfg),
             "features": len(cfg["dataset"]["columns"]),
             "max_depth": int(hp["max_depth"])}
    _state.update(data=data, model=first, jobs=jobs)
    return Outcome(setup_s=win.t0 - ctx.t_start, window=win,
                   metrics={"train_ms_per_tree": ms_per_tree},
                   checks=[], attempted=trees, failed=0, layer=layer)


def _train_rows(cfg) -> int:
    n = int(cfg["train_rows"])
    return n - int(round(n * float(cfg["learner"]["hparams"]
                                    ["validation_ratio"])))


def route(forest, t: int, codes: np.ndarray, is_cat: np.ndarray):
    """Route (F, n) bin codes through the library's tree ``t`` as training
    routes them: code >= split_bin (numerical) or the category's mask bit
    (categorical) goes to the right child. Returns (leaf node of each row,
    [rows in a splitting node at each depth])."""
    n = codes.shape[1]
    node = np.zeros(n, np.int64)
    ar = np.arange(n)
    level_rows = []
    for _ in range(int(forest.depth) + 1):
        left = forest.left_child[t, node]
        inner = left >= 0
        if not inner.any():
            break
        level_rows.append(int(inner.sum()))
        f = np.maximum(forest.feature[t, node], 0)
        c = codes[f, ar].astype(np.int64)
        word = forest.cat_mask[t, node, c // 32]
        bit = (word >> (c % 32).astype(np.uint32)) & 1
        go = np.where(is_cat[f], bit == 1, c >= forest.split_bin[t, node])
        node = np.where(inner, left + go, node)
    return node, level_rows


def preference(forest, t: int, is_cat: np.ndarray):
    """The library's tree ``t`` as the reference's ``grow_tree``
    preference: node id -> (feature, go-right table over the 256 codes),
    None at a leaf."""
    codes = np.arange(256)

    def split(nid: int):
        if nid >= forest.left_child.shape[1] or forest.left_child[t, nid] < 0:
            return None
        j = int(forest.feature[t, nid])
        if is_cat[j]:
            words = forest.cat_mask[t, nid, codes // 32]
            return j, ((words >> (codes % 32).astype(np.uint32)) & 1) == 1
        return j, codes >= int(forest.split_bin[t, nid])
    return split


def tree_output(forest, t: int, codes: np.ndarray,
                is_cat: np.ndarray) -> np.ndarray:
    node, _ = route(forest, t, codes, is_cat)
    return forest.leaf_value[t, node, 0].astype(np.float64)


def window_levels(jobs, codes: np.ndarray, is_cat: np.ndarray) -> list:
    """For every tree completed in the window, the training rows in a
    splitting node at each of its depths. A tree that early stopping cut
    from the returned model is counted as its root level alone."""
    out = []
    n = codes.shape[1]
    for model, first, count in jobs:
        f = model.forest
        for t in range(first, first + count):
            out.append(route(f, t, codes, is_cat)[1] if t < f.n_trees
                       else [n])
    return out


def compare(ctx, out: Outcome) -> None:
    """Reference comparison; appends the checks to ``out``."""
    cfg = ctx.config
    ref = importlib.import_module(cfg["reference"])
    data, model, jobs = (_state.pop(k) for k in ("data", "model", "jobs"))
    hp = cfg["learner"]["hparams"]
    k = int(ctx.params["compare_trees"])
    enc = ref.encode(data, tabular.features(cfg["dataset"]),
                     cfg["dataset"]["label"]["name"], int(hp["max_bins"]))
    del data
    k = min(k, model.forest.n_trees)
    tr = ref.boost(enc, hp, ctx.seed, k, prefer=[
        preference(model.forest, t, enc.is_cat) for t in range(k)])
    codes = enc.codes[:, tr.rows]
    if ctx.trace:
        out.layer["level_rows"] = window_levels(jobs, codes, enc.is_cat)
    del jobs
    logs = model.training_logs["train_loss"]
    outs = [tree_output(model.forest, t, codes, enc.is_cat)
            for t in range(k)]
    gaps = readings(ref, logs[:k], outs, float(model.forest.init_pred[0]),
                    tr, feed=np.float32)
    lim = ctx.limits
    out.checks += [Check(name, gaps[name], lim[name]) for name in lim]
    out.notes.append(f"compared the first {k} trees of job 1 on "
                     f"{len(tr.rows)} training rows; reference losses "
                     f"{tr.losses}, program {list(logs[:k])}; "
                     + ", ".join(f"{n} {v!r}" for n, v in gaps.items()))


def readings(ref, losses, outs, init: float, tr, feed) -> dict:
    """The numbers compared, of a run's first trees against the boosting
    trace ``tr`` of the reference module ``ref``: ``losses`` is the training
    loss after each tree, ``outs`` each tree's output on the training rows,
    ``init`` the initial logit, and ``feed`` the precision in which the
    grower is fed its statistics.

    * ``loss_gap``: the largest gap between the training losses after a
      tree, over the loss the reference has removed so far;
    * ``grad_gap``: the gap between the norms of the gradient the second
      tree is grown on, worked out from the state after the first tree and
      fed as the grower gets it, over the reference's norm;
    * ``change_gap``: the gap between the norms of the trees' summed output
      (the change of the model's logits), over the reference's.
    """
    k = len(outs)
    loss_gap = max(abs(losses[t] - tr.losses[t]) / (tr.loss0 - tr.losses[t])
                   for t in range(k))
    g = feed(ref.gradient(init + outs[0], tr.y)).astype(np.float64)
    g_ref = ref.gradient(tr.init + tr.outputs[0], tr.y)
    return {"loss_gap": loss_gap, "grad_gap": norm_gap(g, g_ref),
            "change_gap": norm_gap(sum(outs), sum(tr.outputs[:k]))}
