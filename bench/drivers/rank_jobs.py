"""LambdaMART training jobs back to back through ``Learner.train``
(task=RANKING), on one grouped table made from the seed.

The run is refused at once, before the table is made, unless the library
computes its lambda gradients on the device (``rank_program``); after
each job, unless the job stayed on the device grower at the cell's level
step with no fallback, and its lambda pass ran on the device. The window
is ``train_jobs``'s: it opens at the first tree boundary (the learner's
``cancel`` probe) and closes at the first at or after ``--seconds``; a job
that ends inside it is followed by the next on the same table.
``train_ms_per_tree`` is the window over the trees completed in it.

Correctness: the first ``compare_trees`` trees of job 1 against the plain
reference (``bench/configs/<reference>.py``) boosted from the same seed on
the same queries, which takes the program's split wherever the greedy rule
ties (``readings``).
"""
from __future__ import annotations

import importlib
import shutil
import tempfile
import time

import numpy as np

from harness import Check, Outcome, Window, norm_gap
import program
import rank_program
import ranking_table
import train_jobs

_state: dict = {}


def run(ctx) -> Outcome:
    rank_program.check_lambda_pass()
    cfg, p = ctx.config, ctx.params
    data = ranking_table.make_table(cfg["dataset"], int(cfg["queries"]),
                                    ctx.stream_seed(1))
    learner = rank_program.learner(cfg, ctx.seed)
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
            rank_program.check_ranking_pass(model)
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
    hp = cfg["learner"]["hparams"]
    layer = {"trees": trees, "jobs": len(jobs), "obs_spans": obs_spans,
             "features": len(cfg["dataset"]["columns"]),
             "max_depth": int(hp["max_depth"])}
    _state.update(data=data, model=first, jobs=jobs)
    return Outcome(setup_s=win.t0 - ctx.t_start, window=win,
                   metrics={"train_ms_per_tree":
                            (win.t1 - win.t0) * 1e3 / trees},
                   checks=[], attempted=trees, failed=0, layer=layer)


def encode(ref, cfg, data):
    ds, hp = cfg["dataset"], cfg["learner"]["hparams"]
    return ref.encode(data, ranking_table.features(ds), ds["label"]["name"],
                      ds["group"], int(hp["max_bins"]))


def compare(ctx, out: Outcome) -> None:
    """Reference comparison; appends the checks to ``out``."""
    cfg = ctx.config
    ref = importlib.import_module(cfg["reference"])
    data, model, jobs = (_state.pop(k) for k in ("data", "model", "jobs"))
    hp = cfg["learner"]["hparams"]
    enc = encode(ref, cfg, data)
    del data
    k = min(int(ctx.params["compare_trees"]), model.forest.n_trees)
    is_cat = enc.enc.is_cat
    tr = ref.boost(enc, hp, ctx.seed, k, prefer=[
        train_jobs.preference(model.forest, t, is_cat) for t in range(k)])
    codes = enc.enc.codes[:, tr.rows]
    out.layer["train_rows"] = len(tr.rows)
    if ctx.trace:
        out.layer["level_rows"] = train_jobs.window_levels(jobs, codes,
                                                           is_cat)
    del jobs
    losses = model.training_logs["train_loss"][:k]
    outs = [train_jobs.tree_output(model.forest, t, codes, is_cat)
            for t in range(k)]
    # the library's own lambda pass at its state after the first tree
    g = rank_program.lambda_gradient(outs[0], tr.rel, enc.qid[tr.rows],
                                     int(hp["ndcg_truncation"]))
    gaps = readings(losses, outs, g, tr)
    out.checks += [Check(name, gaps[name], ctx.limits[name])
                   for name in ctx.limits]
    out.notes.append(f"compared the first {k} trees of job 1 on "
                     f"{len(tr.rows)} training rows of {len(tr.queries)} "
                     f"queries; reference losses {tr.losses}, program "
                     f"{list(losses)}; "
                     + ", ".join(f"{n} {v!r}" for n, v in gaps.items()))


def readings(losses, outs, grad, tr) -> dict:
    """The numbers compared, of a run's first trees against the reference's
    boosting trace ``tr``: ``losses`` is the training loss (1 - NDCG@k)
    after each tree, ``outs`` each tree's output on the training rows and
    ``grad`` the lambda gradient the second tree is grown on.

    * ``loss_gap``: the largest gap between the training losses after a
      tree, over the loss the reference has removed so far;
    * ``grad_gap``: the gap between the norms of that gradient and of the
      reference's at its own state after the first tree, over the
      reference's norm;
    * ``change_gap``: the gap between the norms of the trees' summed
      output (the change of the model's scores), over the reference's.
    """
    k = len(outs)
    loss_gap = max(abs(losses[t] - tr.losses[t]) / (tr.loss0 - tr.losses[t])
                   for t in range(k))
    return {"loss_gap": loss_gap,
            "grad_gap": norm_gap(np.asarray(grad, np.float64), tr.grads[1]),
            "change_gap": norm_gap(sum(outs), sum(tr.outputs[:k]))}
