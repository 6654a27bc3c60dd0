"""Plain reference for the LambdaMART configuration: the group-aware
validation split, LambdaMART's lambda gradients over every pair of each
query, NDCG@k and Newton-leaf boosting, in straightforward numpy and
float64, importing nothing of the library under test. Binning and tree
growth are ``gbt_reference``'s (``encode``, ``grow_tree(prefer=)``).

What it follows, stated once so each departure can be checked:

* Queries: the query column's values in string order are queries
  0..Q-1; a row's query is its value's position in that order.
* Validation (the library's ``group_aware_split``): a seeded permutation
  of the Q queries; the first round(Q * validation_ratio) of it are held
  out whole; trees grow on the rows of the rest, in row order.
* Ranks: 1-based, by score descending; equal scores rank by row order.
  Discount d = 1 / log2(1 + rank) for rank <= k, else 0. Gain 2^rel - 1.
  IDCG: the k largest gains at ranks 1..k. NDCG = DCG / IDCG, 0 where
  IDCG is 0; the loss is 1 - the mean NDCG over the training queries.
* Lambdas (Burges 2010), for every pair (i, j) of a query with
  rel_i > rel_j: rho = 1 / (1 + exp(s_i - s_j)), |dZ| = |gain_i - gain_j|
  |d_i - d_j| / IDCG; g_i -= rho |dZ|, g_j += rho |dZ|, and h_i, h_j +=
  rho (1 - rho) |dZ|. Then h = max(h, 1e-12). Every pair is evaluated,
  those with no member in the top k too, so the reference checks the
  program's restriction to pairs that touch the top k.
* Trees: ``gbt_reference.grow_tree`` on the gradient with the count as
  the gain's denominator (``use_hessian_gain`` false), leaves
  -shrinkage * G / (H + l2). The initial score is 0.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import gbt_reference as gbt

H_FLOOR = 1e-12


@dataclass
class RankEncoded:
    enc: gbt.Encoded         # (F, N) codes of the feature columns
    rel: np.ndarray          # (N,) float64 grades
    qid: np.ndarray          # (N,) int64 query of each row


def encode(table: dict, features: list[str], label: str, group: str,
           max_bins: int) -> RankEncoded:
    enc = gbt.encode(table, features, label, max_bins)
    qid = np.unique(np.asarray(table[group]).astype(str),
                    return_inverse=True)[1].astype(np.int64)
    return RankEncoded(enc, np.asarray(table[label], np.float64), qid)


def training_rows(qid: np.ndarray, ratio: float, seed: int) -> np.ndarray:
    """Ascending rows of the queries that are not held out."""
    n_q = int(qid.max()) + 1 if len(qid) else 0
    held = np.random.default_rng(seed).permutation(n_q)[
        :int(round(n_q * ratio))]
    return np.flatnonzero(~np.isin(qid, held))


def query_rows(qid: np.ndarray) -> list:
    """Each query's rows (ascending) among the given rows."""
    order = np.argsort(qid, kind="stable")
    cuts = np.flatnonzero(np.diff(qid[order])) + 1
    return np.split(order, cuts)


def _ranks(s: np.ndarray) -> np.ndarray:
    rank = np.empty(len(s), np.int64)
    rank[np.argsort(-s, kind="stable")] = np.arange(1, len(s) + 1)
    return rank


def _discounts(s: np.ndarray, k: int) -> np.ndarray:
    rank = _ranks(s)
    return np.where(rank <= k, 1.0 / np.log2(1.0 + rank), 0.0)


def _idcg(gains: np.ndarray, k: int) -> float:
    top = np.sort(gains)[::-1][:k]
    return float((top / np.log2(np.arange(2, len(top) + 2))).sum())


def ndcg(scores: np.ndarray, rel: np.ndarray, queries: list,
         k: int) -> float:
    """Mean NDCG@k over ``queries`` (lists of rows of ``scores``)."""
    vals = []
    for rows in queries:
        gains = np.power(2.0, rel[rows]) - 1.0
        idcg = _idcg(gains, k)
        dcg = float((gains * _discounts(scores[rows], k)).sum())
        vals.append(dcg / idcg if idcg > 0 else 0.0)
    return float(np.mean(vals))


def lambdas(scores: np.ndarray, rel: np.ndarray, queries: list,
            k: int) -> tuple[np.ndarray, np.ndarray]:
    """(g, h) over every pair of each query, one query at a time."""
    g = np.zeros(len(scores))
    h = np.zeros(len(scores))
    for rows in queries:
        s, r = scores[rows], rel[rows]
        gains = np.power(2.0, r) - 1.0
        idcg = _idcg(gains, k)
        if idcg <= 0 or len(rows) < 2:
            continue
        d = _discounts(s, k)
        win = r[:, None] > r[None, :]
        with np.errstate(over="ignore"):
            rho = 1.0 / (1.0 + np.exp(s[:, None] - s[None, :]))
        dz = (np.abs(gains[:, None] - gains[None, :])
              * np.abs(d[:, None] - d[None, :]) / idcg)
        lam = np.where(win, rho * dz, 0.0)
        hl = np.where(win, rho * (1.0 - rho) * dz, 0.0)
        g[rows] = lam.sum(axis=0) - lam.sum(axis=1)
        h[rows] = hl.sum(axis=0) + hl.sum(axis=1)
    return g, np.maximum(h, H_FLOOR)


@dataclass
class RankTrace:
    rows: np.ndarray         # training rows
    queries: list            # each training query's positions in ``rows``
    rel: np.ndarray          # the training rows' grades
    loss0: float             # 1 - NDCG@k at the initial score 0
    losses: list             # training loss after each tree
    outputs: list            # per-tree output on the training rows
    grads: list              # the lambda gradient each tree was grown on
    trees: list


def boost(data: RankEncoded, hp: dict, seed: int, n_trees: int,
          round_stats=None, rows_keep=None, prefer=()) -> RankTrace:
    """The first ``n_trees`` boosting iterations. ``round_stats``
    (optional) maps g and h before they are summed, which is how a lower
    precision is put in; ``rows_keep`` (optional) restricts the rows the
    trees see while the lambdas and the loss stay over every training
    query: the "half the batch left out" fault. ``prefer[t]`` (optional)
    is tree t's ``grow_tree`` preference."""
    k = int(hp["ndcg_truncation"])
    rows = training_rows(data.qid, float(hp["validation_ratio"]), seed)
    queries = query_rows(data.qid[rows])
    rel = data.rel[rows]
    codes = data.enc.codes[:, rows]
    z = np.zeros(len(rows))
    tr = RankTrace(rows, queries, rel, 1.0 - ndcg(z, rel, queries, k), [],
                   [], [], [])
    grow = np.arange(len(rows)) if rows_keep is None else rows_keep
    for t in range(n_trees):
        g, h = lambdas(z, rel, queries, k)
        sub = gbt.Encoded(codes[:, grow], data.enc.is_cat, data.enc.n_bins,
                          rel[grow], None)
        tree, out = gbt.grow_tree(sub, np.arange(len(grow)), g[grow],
                                  h[grow], hp, round_stats,
                                  prefer[t] if t < len(prefer) else None)
        if rows_keep is not None:
            out = gbt.apply_tree(tree, codes)
        z = z + out
        tr.grads.append(g)
        tr.trees.append(tree)
        tr.outputs.append(out)
        tr.losses.append(1.0 - ndcg(z, rel, queries, k))
    return tr


preference = gbt.preference
round_bf16 = gbt.round_bf16
