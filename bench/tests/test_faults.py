"""A run with the timed path broken underneath comes out not correct: once
for each fault a cell can have. Each fault is planted in the program's
output, the comparison and its limits are the cells' own."""
import numpy as np
import pytest

import program
import rehearse


class _Learner:
    """The configuration's learner with a fault on what it trains or
    returns."""

    def __init__(self, inner, fault):
        self.inner, self.fault = inner, fault

    def train(self, data, checkpoint=None):
        if self.fault == "half_batch":
            # half of the rows left out; the mean is taken over the rest
            n = len(next(iter(data.values())))
            data = {k: v[: n // 2] for k, v in data.items()}
        model = self.inner.train(data, checkpoint=checkpoint)
        if self.fault == "state_unchanged":
            # the first boosting step returns the model unchanged
            model.forest.leaf_value[0] = 0.0
        return model


@pytest.mark.parametrize("cell", ["higgs_gbt.train", "adult_gbt.train"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_fault_is_not_correct(monkeypatch, cell, fault):
    make = program.learner
    monkeypatch.setattr(program, "learner",
                        lambda cfg, seed: _Learner(make(cfg, seed), fault))
    res = rehearse.rehearse(cell, seed=3)
    assert res["correct"] is False, res["checks"]


class _AlteredPredictor:
    """A predictor that alters the answers of one run of 256 rows, the
    size of a tile, in every call."""

    def __init__(self, inner):
        self.inner = inner

    def encode(self, table):
        return self.inner.encode(table)

    def predict_encoded(self, X):
        out = np.array(self.inner.predict_encoded(X))
        lo = len(out) // 3
        out[lo:lo + 256] = out[lo:lo + 256, ::-1]
        return out

    def predict(self, table):
        return self.predict_encoded(self.encode(table))


def test_scoring_with_an_altered_answer_is_not_correct(monkeypatch):
    make = program.compile_predictor
    monkeypatch.setattr(program, "compile_predictor",
                        lambda m, e: _AlteredPredictor(make(m, e)))
    res = rehearse.rehearse("higgs_gbt.score", seed=3)
    assert res["correct"] is False, res["checks"]


def test_serving_with_an_altered_answer_is_not_correct(monkeypatch):
    make = program.forest_server

    def server(*a, **kw):
        srv = make(*a, **kw)
        result = srv.result
        claimed = []

        def altered(ticket):
            out = result(ticket)
            claimed.append(ticket)
            # the answer to the window's 50th request is altered
            return out[:, ::-1] if len(claimed) == 50 + WARM else out
        srv.result = altered
        return srv

    spec = rehearse.shrink(rehearse.load("adult_gbt.online"))
    p = {**spec["mix"]["params"], **spec["cell"]["params"]}
    top = int(p["max_batch"]) - 1 + max(s["max"] for s in p["sizes"])
    WARM = top + int(p["warm_requests"])
    monkeypatch.setattr(program, "forest_server", server)
    res = rehearse.rehearse("adult_gbt.online", seed=3)
    assert res["correct"] is False, res["checks"]
