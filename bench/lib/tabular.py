"""Seeded tabular data and forests at a configuration's published shape.

One general generator reads the ``dataset`` section of a configuration file
(``bench/configs/<config>.json``): its columns, each with a distribution,
and a label model. Everything is vectorized numpy drawn from one
``np.random.default_rng(seed)``, so the same seed gives the same rows.
Numerical columns come out as float32 arrays, categorical columns as object
arrays of strings with ``None`` for a missing value, and the label as a
string array.

``build_forest`` draws the serving forest of a configuration: complete
trees of the stated depth whose numerical thresholds sit on the data's bin
boundaries and whose categorical conditions are random category subsets.
It returns plain arrays (``BenchForest``); ``bench/lib/program.py`` turns
them into the library's model, and the plain reference traverses them
directly.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# A categorical mask covers codes 0..255 as 8 uint32 words, as the serving
# kernel's tables do.
MASK_WORDS = 8


def vocab(col: dict) -> list[str]:
    """A categorical column's values, most frequent first."""
    return list(col["values"])


def _numerical(col: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    k = col["kind"]
    if k == "lognormal":
        x = rng.lognormal(col["mu"], col["sigma"], n)
    elif k == "normal":
        x = rng.normal(col.get("mean", 0.0), col["std"], n)
        if "clip" in col:
            x = np.clip(x, -col["clip"], col["clip"])
    elif k == "uniform":
        x = rng.uniform(col["lo"], col["hi"], n)
    elif k == "discrete":
        x = rng.choice(np.asarray(col["values"], np.float64), n,
                       p=np.asarray(col["probs"]) / np.sum(col["probs"]))
    elif k == "integer":
        x = np.clip(np.rint(rng.normal(col["mean"], col["std"], n)),
                    col["lo"], col["hi"])
    elif k == "zero_inflated":
        x = np.where(rng.random(n) < col["nonzero"],
                     np.rint(rng.lognormal(col["mu"], col["sigma"], n)), 0.0)
    else:
        raise ValueError(f"unknown column kind {k!r}")
    return x.astype(np.float32)


def _standardize(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    s = x.std()
    return (x - x.mean()) / (s if s > 0 else 1.0)


def make_table(spec: dict, n: int, seed: int) -> dict:
    """``n`` rows of the configuration's dataset: {column: array} with the
    label under ``spec["label"]["name"]``."""
    rng = np.random.default_rng(seed)
    out: dict = {}
    z = np.zeros(n)
    signal = []
    for col in spec["columns"]:
        if col["kind"] == "categorical":
            vals = vocab(col)
            p = np.asarray(col["probs"], np.float64)
            idx = rng.choice(len(vals), n, p=p / p.sum())
            # a fixed effect per category, drawn from the same stream
            effect = rng.normal(0.0, col.get("weight", 0.0), len(vals))
            z += effect[idx]
            arr = np.asarray(vals, dtype=object)[idx]
            miss = col.get("missing", 0.0)
            if miss:
                arr[rng.random(n) < miss] = None
            out[col["name"]] = arr
        else:
            x = _numerical(col, n, rng)
            out[col["name"]] = x
            w = col.get("weight", 0.0)
            if w:
                s = _standardize(x)
                signal.append(s)
                z += w * s
    # pairwise interactions between consecutive signal columns: the shape of
    # signal that trees of depth > 1 pick up
    lab = spec["label"]
    for a, b in zip(signal[::2], signal[1::2]):
        z += lab.get("interaction", 0.0) * a * b
    z += lab["bias"] + rng.logistic(0.0, lab.get("noise", 1.0), n)
    pos = z > 0
    out[lab["name"]] = np.where(pos, lab["positive"], lab["negative"])
    return out


def features(spec: dict) -> list[str]:
    return [c["name"] for c in spec["columns"]]


def quantile_bounds(x: np.ndarray, n_bins: int) -> np.ndarray:
    """Ascending bin boundaries of a float32 column: midpoints between its
    distinct values when it has at most ``n_bins`` of them, else its
    ``n_bins``-quantiles (the discretization of a histogram learner)."""
    uniq = np.unique(x.astype(np.float64))
    if len(uniq) <= 1:
        return np.empty(0, np.float64)
    if len(uniq) <= n_bins:
        return (uniq[1:] + uniq[:-1]) / 2.0
    qs = np.quantile(x.astype(np.float64),
                     np.linspace(0, 1, n_bins + 1)[1:-1], method="nearest")
    return np.unique(qs)


@dataclass
class BenchForest:
    """Complete binary trees in breadth-first order: node i has children
    2i + 1 (condition false) and 2i + 2 (condition true). Internal nodes
    are 0 .. 2**depth - 2; the rest are leaves."""
    feature: np.ndarray     # (T, I) int32 column index, I internal nodes
    threshold: np.ndarray   # (T, I) float32; numerical: go right iff x >= t
    cat_mask: np.ndarray    # (T, I, MASK_WORDS) uint32; bit c set: code c right
    is_cat: np.ndarray      # (F,) bool
    leaf: np.ndarray        # (T, L) float32 leaf values
    bias: float             # added to the sum over trees
    depth: int


def build_forest(spec: dict, forest_spec: dict, seed: int,
                 sample: dict) -> BenchForest:
    """Draw ``forest_spec["trees"]`` complete trees of depth
    ``forest_spec["depth"]``. Numerical thresholds are bin boundaries of
    ``sample`` (rows of the configuration's data), so every threshold is
    one of at most ``max_bins`` values per feature."""
    rng = np.random.default_rng(seed)
    T, D = int(forest_spec["trees"]), int(forest_spec["depth"])
    n_int, n_leaf = 2 ** D - 1, 2 ** D
    cols = spec["columns"]
    F = len(cols)
    is_cat = np.array([c["kind"] == "categorical" for c in cols])
    bounds = [None if is_cat[j] else
              quantile_bounds(sample[c["name"]], forest_spec["max_bins"])
              .astype(np.float32) for j, c in enumerate(cols)]
    feat = rng.integers(0, F, (T, n_int)).astype(np.int32)
    thr = np.zeros((T, n_int), np.float32)
    mask = np.zeros((T, n_int, MASK_WORDS), np.uint32)
    u = rng.random((T, n_int))
    bits = rng.random((T, n_int, 256)) < 0.5
    pick = rng.random((T, n_int, 2))
    rows = np.arange(T)[:, None], np.arange(n_int)[None, :]
    for j in range(F):
        sel = feat == j
        if is_cat[j]:
            # codes 1..V are the vocabulary (0 is out-of-dictionary). The
            # library reads a node with an empty mask as numerical, so each
            # mask holds one code forced in and another forced out.
            V = len(cols[j]["values"])
            keep = np.zeros(256, bool)
            keep[1:V + 1] = True
            b = bits & keep
            c_in = 1 + (pick[..., 0] * V).astype(np.int64)
            c_out = 1 + (c_in - 1 + 1 + (pick[..., 1] * (V - 1))
                         .astype(np.int64)) % V
            b[rows + (c_in,)] = True
            b[rows + (c_out,)] = False
            words = np.packbits(b.reshape(T, n_int, MASK_WORDS, 32),
                                axis=-1, bitorder="little")
            mask[sel] = words.view(np.uint32).reshape(T, n_int,
                                                      MASK_WORDS)[sel]
        else:
            bj = bounds[j]
            k = np.minimum((u * len(bj)).astype(np.int64), len(bj) - 1)
            thr[sel] = bj[k[sel]]
    leaf = rng.normal(0.0, forest_spec["leaf_std"], (T, n_leaf)) \
        .astype(np.float32)
    bias = float(np.float32(rng.normal(0.0, 0.1)))
    return BenchForest(feature=feat, threshold=thr, cat_mask=mask,
                       is_cat=is_cat, leaf=leaf, bias=bias, depth=D)
