"""ndcg_ms.rank: host ms per tree computing NDCG@k, the ranking loss the
learner reports after each tree on the training and validation queries,
from the library's ``ranking/ndcg`` spans in the window. Nothing from a
program without the span."""


def read(r):
    trees = r.layer.get("trees", 0)
    if not trees or not r.spans_named("ranking/ndcg"):
        return None
    return r.span_s("ranking/ndcg") * 1e3 / trees
