"""The control, the plain reference in bfloat16 put in the program's place,
fails the cell's own limits, at a size a test run holds. On the chip it
runs at the cells' own sizes (``bench/control.py``), and its readings there
set the upper end of each limit."""
import pytest

import control
import rehearse

CELLS = ["higgs_gbt.train", "adult_gbt.online", "higgs_gbt.score",
         "adult_gbt.train"]


def _spec(cell):
    """The cell cut to a test's size: a training cell keeps its own trees
    on at most 20,000 rows (at the tiny depth the control's rounding
    barely moves the first trees); the others take ``rehearse.shrink``."""
    spec = rehearse.load(cell)
    if spec["mix"]["driver"] != "train_jobs":
        return rehearse.shrink(spec)
    cfg = spec["config"]
    cfg["train_rows"] = min(int(cfg["train_rows"]), 20000)
    return spec


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 4242])
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell, seed):
    spec = _spec(cell)
    ctx = control._ctx(spec, seed, 1.0)
    got = control.READINGS[spec["mix"]["driver"]](ctx)
    limits = spec["cell"]["limits"]
    assert set(limits) <= set(got)
    assert any(got[n] > limits[n] for n in limits), got
