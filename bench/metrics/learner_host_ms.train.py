"""learner_host_ms.train: host ms per tree in the learner's own work around
each tree, from the library's spans in the window: ``gbt/stats`` (stacking
the tree's statistics) and ``gbt/boundary`` (from the tree's return to the
next iteration: the predictions' update, the losses and early stopping, the
checkpoint probe and save). Nothing from a program without these spans."""


def read(r):
    trees = r.layer.get("trees", 0)
    if not trees or not r.spans_named("gbt/boundary"):
        return None
    return (r.span_s("gbt/stats") + r.span_s("gbt/boundary")) * 1e3 / trees
