"""Seeded learning-to-rank tables at a configuration's published shape.

Reads the ``dataset`` section of a ranking configuration
(``bench/configs/mslr_lambdamart.json``): the query sizes' distribution,
the columns (each drawn by ``tabular``'s column kinds) and a graded label
model. Every array comes from one ``np.random.default_rng(seed)``, so the
same seed gives the same rows.

Rows come grouped: the rows of query 0, then of query 1, and so on. The
query column holds fixed-width names (``q000017``), whose string order is
their numeric order. Grades come from a latent value per row, a per-query
effect plus a weighted sum of standardized columns, pairwise interactions
of consecutive signal columns and logistic noise, cut at its global
quantiles so that each grade takes its stated share of the rows.
"""
from __future__ import annotations

import numpy as np

import tabular


def query_sizes(spec: dict, queries: int,
                rng: np.random.Generator) -> np.ndarray:
    q = spec["query_sizes"]
    sizes = np.rint(rng.lognormal(q["mu"], q["sigma"], queries))
    return np.clip(sizes, q["lo"], q["hi"]).astype(np.int64)


def make_table(spec: dict, queries: int, seed) -> dict:
    """``queries`` queries of the configuration's dataset: {column: array},
    float32 columns, the grades (float32) under the label's name and the
    query names under ``spec["group"]``."""
    rng = np.random.default_rng(seed)
    sizes = query_sizes(spec, queries, rng)
    qid = np.repeat(np.arange(queries), sizes)
    n = len(qid)
    lab = spec["label"]
    out: dict = {}
    z = rng.normal(0.0, lab["query_std"], queries)[qid]
    signal = []
    for col in spec["columns"]:
        x = tabular._numerical(col, n, rng)
        out[col["name"]] = x
        w = col.get("weight", 0.0)
        if w:
            s = tabular._standardize(x)
            signal.append(s)
            z += w * s
    for a, b in zip(signal[::2], signal[1::2]):
        z += lab.get("interaction", 0.0) * a * b
    z += rng.logistic(0.0, lab["noise"], n)
    cuts = np.quantile(z, np.cumsum(lab["shares"])[:-1])
    grades = np.asarray(lab["grades"], np.float32)
    out[lab["name"]] = grades[np.searchsorted(cuts, z, side="right")]
    width = len(str(queries - 1))
    out[spec["group"]] = np.char.add("q", np.char.zfill(
        qid.astype(str), width)).astype(object)
    return out


def features(spec: dict) -> list[str]:
    return [c["name"] for c in spec["columns"]]
