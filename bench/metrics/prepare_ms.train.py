"""prepare_ms.train: host ms per tree spent preparing jobs inside the
window, from the library's ``learner/prepare`` spans (dataspec, encoding,
validation split, binning, the data fingerprint and the device upload of
the codes, up to a job's first tree). A window that holds no job boundary
reads 0; a program that records no ``learner/prepare`` span at all (job 1
records one during set-up) reads nothing."""


def read(r):
    trees = r.layer.get("trees", 0)
    if not trees or not any(s[0] == "learner/prepare" for s in r.obs_spans):
        return None
    return r.span_s("learner/prepare") * 1e3 / trees
