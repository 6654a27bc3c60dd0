"""Plain reference for the GBT configurations: binning, boosting and forest
traversal written from the published semantics in straightforward numpy
and float64, importing nothing of the library under test.

What it follows, stated once so each departure can be checked:

* Dictionaries: a categorical column's values ordered by count, most
  frequent first (ties by string order), code 0 for out-of-dictionary; a
  missing value takes the most frequent value. The label's classes follow
  the same order; class 1 (the less frequent) is the positive logit.
* Discretization (YDF's ``num_discretized_numerical_bins``): at most 255
  bins; a column with at most 255 distinct values splits at the midpoints
  between them, else at its 255-quantiles (nearest sample). Code of x =
  number of boundaries below x. Categorical codes are dictionary codes.
* Validation: ``validation_ratio`` of the rows, drawn by a seeded
  permutation, are held out; trees grow on the rest.
* Binomial log-likelihood: initial logit log(p / (1 - p)) of the training
  positive rate (float32), gradient p - y, hessian p (1 - p).
* Level-wise (LOCAL) growth to ``max_depth``. Split score of a node
  0.5 G^2 / n (YDF's default ``use_hessian_gain=false`` divides by the
  count); gain = score(left) + score(right) - score(parent), both sides at
  least ``min_examples`` rows, and above max(1e-12, 4e-6 |score(parent)|).
  Numerical: ordered bins, left = bins below the split. Categorical (CART):
  bins ordered by G / n, then scanned in that order. Ties go to the lowest
  feature, then the lowest position; gains within 1e-10 of the parent's
  score of each other are ties (the first tree's gradient takes two
  values, so small nodes tie exactly, and float64 rounding must not pick).
  A comparison passes the splits of the tree it checks as a preference:
  where gains tie within float32's rounding (1e-5 of the parent's score),
  the reference takes the checked tree's split, so both stay on one branch
  where the rule does not decide; a split that gains less is never taken.
* Leaf value: -shrinkage * G / (H + l2), stored as float32.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

MIN_GAIN = 1e-12
REL_GAIN = 4e-6
TIE_REL = 1e-10
FOLLOW_REL = 1e-5
N_BINS = 256


# ------------------------------------------------------------- encoding

def dictionary(values: np.ndarray) -> list[str]:
    """Column values ordered by count (most frequent first, ties by string
    order), missing (None) excluded."""
    present = np.asarray([v for v in values if v is not None], dtype=str) \
        if values.dtype == object else values.astype(str)
    uniq, cnt = np.unique(present, return_counts=True)
    return [str(u) for u in uniq[np.argsort(-cnt, kind="stable")]]


def dictionary_codes(values: np.ndarray, vocab: list[str]) -> np.ndarray:
    """1-based codes in ``vocab``; missing -> the most frequent value (1)."""
    lookup = {v: i + 1 for i, v in enumerate(vocab)}
    return np.fromiter((1 if v is None else lookup.get(str(v), 0)
                        for v in values), np.int64, len(values))


def boundaries(x: np.ndarray, max_bins: int) -> np.ndarray:
    x = x.astype(np.float64)
    uniq = np.unique(x)
    if len(uniq) <= 1:
        return np.empty(0)
    if len(uniq) <= max_bins:
        return (uniq[1:] + uniq[:-1]) / 2.0
    q = np.quantile(x, np.linspace(0, 1, max_bins + 1)[1:-1],
                    method="nearest")
    return np.unique(q)


@dataclass
class Encoded:
    codes: np.ndarray        # (F, N) uint8, feature-major
    is_cat: np.ndarray       # (F,) bool
    n_bins: np.ndarray       # (F,) int64
    y: np.ndarray            # (N,) float64 in {0, 1}
    raw: list                # per feature: float64 values or codes


def encode(table: dict, features: list[str], label: str,
           max_bins: int) -> Encoded:
    F = len(features)
    N = len(table[label])
    codes = np.zeros((F, N), np.uint8)
    is_cat = np.zeros(F, bool)
    n_bins = np.zeros(F, np.int64)
    raw = []
    for j, name in enumerate(features):
        col = np.asarray(table[name])
        if col.dtype == object:
            c = dictionary_codes(col, dictionary(col))
            c = np.minimum(c, max_bins - 1)
            codes[j] = c
            is_cat[j] = True
            n_bins[j] = c.max() + 1
            raw.append(c)
        else:
            x = col.astype(np.float64)
            b = boundaries(x, max_bins)
            codes[j] = np.searchsorted(b, x, side="left")
            n_bins[j] = len(b) + 1
            raw.append(x)
    classes = dictionary(np.asarray(table[label]))
    y = (np.asarray(table[label]).astype(str) == classes[1]).astype(np.float64)
    return Encoded(codes, is_cat, n_bins, y, raw)


def validation_split(n: int, ratio: float, seed: int) -> np.ndarray:
    """Sorted indices of the training rows."""
    perm = np.random.default_rng(seed).permutation(n)
    return np.sort(perm[int(round(n * ratio)):])


# ------------------------------------------------------------- growing

def _score(g, n):
    return 0.5 * g * g / (n + 1e-12)


def _best_split(hg: np.ndarray, hn: np.ndarray, is_cat, n_bins,
                min_examples: int):
    """hg, hn: (F, B) gradient sums and counts of one node. Returns (gain,
    feature, go-right table over codes (B,)) or None."""
    F, B = hg.shape
    G, n = hg[0].sum(), hn[0].sum()
    parent = _score(G, n)
    # gains equal but for float64 rounding are ties
    tie = TIE_REL * abs(parent)
    found = []                      # (gain, feature, position) per feature
    orders = {}
    for j in range(F):
        g, c = hg[j], hn[j]
        if is_cat[j]:
            key = np.where(np.arange(B) >= n_bins[j], np.inf,
                           g / np.maximum(c, 1e-12))
            order = np.argsort(key, kind="stable")
            g, c = g[order], c[order]
            orders[j] = order
            valid_pos = np.arange(B) < n_bins[j] - 1
        else:
            valid_pos = np.arange(B) < B - 1
        gl, cl = np.cumsum(g), np.cumsum(c)
        gr, cr = G - gl, n - cl
        gain = _score(gl, cl) + _score(gr, cr) - parent
        ok = valid_pos & (cl >= min_examples) & (cr >= min_examples)
        gain = np.where(ok, gain, -np.inf)
        b = int(np.argmax(gain >= gain.max() - tie))
        found.append((gain[b], j, b))
    top = max(g for g, _, _ in found)
    gain, j, b = next(c for c in found if c[0] >= top - tie)
    if not gain > max(MIN_GAIN, REL_GAIN * abs(parent)):
        return None
    right = np.zeros(B, bool)
    if is_cat[j]:
        rank = np.empty(B, np.int64)
        rank[orders[j]] = np.arange(B)
        right = (rank > b) & (np.arange(B) < n_bins[j])
    else:
        right[b + 1:] = True
    return gain, j, right


@dataclass
class RefTree:
    """Breadth-first dict tree: node -> (feature, right table) or leaf."""
    splits: dict             # node id -> (feature, (B,) bool go-right)
    leaves: dict             # node id -> float32 leaf value
    children: dict           # node id -> (left id, right id)


def _split_gain(hg: np.ndarray, hn: np.ndarray, right: np.ndarray,
                min_examples: int) -> float:
    """The gain of sending the codes in ``right`` right, from one feature's
    (B,) gradient sums and counts."""
    G, n = hg.sum(), hn.sum()
    gr, cr = hg[right].sum(), hn[right].sum()
    if cr < min_examples or n - cr < min_examples:
        return -np.inf
    return _score(G - gr, n - cr) + _score(gr, cr) - _score(G, n)


def grow_tree(enc: Encoded, rows: np.ndarray, g: np.ndarray, h: np.ndarray,
              hp: dict, round_stats=None, prefer=None):
    """One level-wise tree on training ``rows`` with per-row gradient ``g``
    and hessian ``h`` (aligned with ``rows``). ``round_stats`` (optional)
    maps both statistics before they are summed, for the split search and
    the leaf values alike, which is how a lower precision is put in.

    ``prefer`` (optional) maps a node id to a (feature, go-right table)
    split or None. Where the greedy rule allows more than one split (gains
    equal within ``FOLLOW_REL`` of the parent's score, float32's rounding
    of a gain), the preferred one is taken if it is among them; a split
    that gains less is never taken. Comparing with a tree grown elsewhere,
    this keeps the two on one branch where the rule does not decide.
    Returns (tree, per-row output)."""
    F = enc.codes.shape[0]
    depth, min_ex = int(hp["max_depth"]), int(hp["min_examples"])
    shrink, l2 = float(hp["shrinkage"]), float(hp["l2_regularization"])
    if round_stats is not None:
        g, h = round_stats(g), round_stats(h)
    node = np.zeros(len(rows), np.int64)
    codes = enc.codes[:, rows]
    tree = RefTree({}, {}, {})
    frontier = [0]
    next_id = 1
    for _ in range(depth):
        if not frontier:
            break
        order = np.argsort(node, kind="stable")
        bounds = np.searchsorted(node[order], frontier + [frontier[-1] + 1])
        new_frontier = []
        for k, nid in enumerate(frontier):
            idx = order[bounds[k]:bounds[k + 1]]
            if len(idx) < 2 * min_ex:
                continue
            hg = np.empty((F, N_BINS))
            hn = np.empty((F, N_BINS))
            for j in range(F):
                cj = codes[j, idx]
                hg[j] = np.bincount(cj, weights=g[idx], minlength=N_BINS)
                hn[j] = np.bincount(cj, minlength=N_BINS)
            found = _best_split(hg, hn, enc.is_cat, enc.n_bins, min_ex)
            if found is None:
                continue
            gain, j, right = found
            alt = prefer(nid) if prefer is not None else None
            if alt is not None:
                slack = FOLLOW_REL * abs(_score(hg[0].sum(), hn[0].sum()))
                if _split_gain(hg[alt[0]], hn[alt[0]], alt[1],
                               min_ex) >= gain - slack:
                    j, right = alt
            left_id, right_id = next_id, next_id + 1
            next_id += 2
            tree.splits[nid] = (j, right)
            tree.children[nid] = (left_id, right_id)
            go = right[codes[j, idx]]
            node[idx] = np.where(go, right_id, left_id)
            new_frontier += [left_id, right_id]
        frontier = new_frontier
    out = np.zeros(len(rows))
    G = np.bincount(node, weights=g, minlength=next_id)
    H = np.bincount(node, weights=h, minlength=next_id)
    for nid in np.unique(node):
        v = np.float32(-shrink * G[nid] / (H[nid] + l2 + 1e-12))
        tree.leaves[int(nid)] = v
        out[node == nid] = float(v)
    return tree, out


def log_loss(z: np.ndarray, y: np.ndarray) -> float:
    return float(np.mean(np.logaddexp(0.0, z) - y * z))


@dataclass
class BoostTrace:
    rows: np.ndarray         # training rows
    y: np.ndarray            # their labels
    init: float
    loss0: float
    losses: list             # training loss after each tree
    outputs: list            # per-tree output on the training rows
    trees: list


def boost(enc: Encoded, hp: dict, seed: int, n_trees: int,
          round_stats=None, rows_keep=None, prefer=()) -> BoostTrace:
    """The first ``n_trees`` boosting iterations. ``rows_keep`` (optional)
    restricts the rows the trees see while the loss stays over all training
    rows: the "half the batch left out" fault. ``prefer[t]`` (optional) is
    tree t's ``grow_tree`` preference."""
    N = enc.codes.shape[1]
    rows = validation_split(N, float(hp["validation_ratio"]), seed)
    y = enc.y[rows]
    p = np.clip(y.mean(), 1e-6, 1 - 1e-6)
    init = float(np.float32(np.log(p / (1 - p))))
    z = np.full(len(rows), init)
    trace = BoostTrace(rows, y, init, log_loss(z, y), [], [], [])
    grow_rows = np.arange(len(rows)) if rows_keep is None else rows_keep
    for t in range(n_trees):
        pr = sigmoid(z)
        g = pr - y
        h = np.maximum(pr * (1 - pr), 1e-12)
        sub = enc.codes[:, rows[grow_rows]]
        tree, out = grow_tree(Encoded(sub, enc.is_cat, enc.n_bins, y, None),
                              np.arange(len(grow_rows)), g[grow_rows],
                              h[grow_rows], hp, round_stats,
                              prefer[t] if t < len(prefer) else None)
        if rows_keep is not None:
            out = apply_tree(tree, enc.codes[:, rows])
        z = z + out
        trace.trees.append(tree)
        trace.outputs.append(out)
        trace.losses.append(log_loss(z, y))
    return trace


def preference(tree: RefTree):
    """A reference tree's splits as a ``grow_tree`` preference."""
    return tree.splits.get


def apply_tree(tree: RefTree, codes: np.ndarray) -> np.ndarray:
    """Per-row output of a reference tree on (F, n) codes."""
    node = np.zeros(codes.shape[1], np.int64)
    for nid in sorted(tree.splits):          # parents before children
        j, right = tree.splits[nid]
        at = node == nid
        go = right[codes[j, at]]
        lid, rid = tree.children[nid]
        node[at] = np.where(go, rid, lid)
    vals = np.zeros(max(tree.leaves) + 1, np.float64)
    for nid, v in tree.leaves.items():
        vals[nid] = v
    return vals[node]


# ------------------------------------------------- forest traversal

def forest_logits(forest, columns: list, is_cat: np.ndarray,
                  round_table=None) -> np.ndarray:
    """Sum over complete breadth-first trees (tabular.BenchForest layout)
    plus the bias, in float64. ``columns``: per feature, float32 values
    (numerical) or int codes (categorical). A numerical node goes right iff
    x >= threshold; a categorical node iff its mask holds the code.
    ``round_table`` (optional) maps the float32 thresholds and leaf values
    before use, which is how a lower precision is put in."""
    T, I = forest.feature.shape
    n = len(columns[0])
    thr, leaf = forest.threshold, forest.leaf
    if round_table is not None:
        thr, leaf = round_table(thr), round_table(leaf)
    X = np.stack([np.asarray(c, np.float32) for c in columns])   # (F, n)
    code = np.stack([np.asarray(c).astype(np.int64) if is_cat[j] else
                     np.zeros(n, np.int64) for j, c in enumerate(columns)])
    total = np.full(n, float(forest.bias))
    ar = np.arange(n)
    for t in range(T):
        node = np.zeros(n, np.int64)
        for _ in range(forest.depth):
            f = forest.feature[t, node]
            cat = is_cat[f]
            x = X[f, ar]
            c = code[f, ar]
            words = forest.cat_mask[t, node, c // 32]
            go_cat = (words >> (c % 32).astype(np.uint32)) & 1
            go = np.where(cat, go_cat == 1, x >= thr[t, node])
            node = 2 * node + 1 + go
        total += leaf[t, node - I].astype(np.float64)
    return total


def sigmoid(z: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-z))


def gradient(z: np.ndarray, y: np.ndarray) -> np.ndarray:
    """The binomial log-likelihood's gradient p - y at logits ``z``."""
    return sigmoid(z) - y


# ------------------------------------------------- lower precisions

def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (round half to even), as float32."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)
