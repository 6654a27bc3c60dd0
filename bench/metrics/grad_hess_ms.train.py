"""grad_hess_ms.train: host ms per tree in the learner's gradient and
hessian step, from the library's ``gbt/grad_hess`` spans in the window."""


def read(r):
    trees = r.layer.get("trees", 0)
    if not trees or not r.spans_named("gbt/grad_hess"):
        return None
    return r.span_s("gbt/grad_hess") * 1e3 / trees
