"""LambdaMART ranking (DESIGN.md §12.1; Burges 2010).

The RANKING task rides the ordinary GBT learner: the only new piece is the
loss. Groups (queries) are laid out once per table in buckets of
power-of-two widths (``group_layout``), so a heavy-tailed size mix pads each
group to less than twice its size instead of every group to the largest.
The lambda gradients are one jitted float32 program over that layout
(``lambda_grad_device``), which evaluates only the pairs that touch the top
k. ``lambda_grad_naive`` is the float64 oracle: every pair of each group,
one group at a time. NDCG, the reported loss, stays on the host in float64.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np

from repro.obs import trace

# Where the lambda gradients are computed: read by callers (the benchmark)
# before they train, to refuse a library that computes them elsewhere.
LAMBDA_PASS = "device"

MIN_WIDTH = 8           # narrowest bucket: groups of 1..8 rows share it
H_FLOOR = 1e-12         # Newton leaves stay finite in pairless nodes


# ------------------------------------------------------------ group layout

@dataclass(frozen=True)
class Bucket:
    """The groups of one width. ``index[g, i]`` is a row index into the
    flat (N,) arrays; padding slots repeat the group's last row and are
    masked out by ``mask``. Within a group, slots follow row order."""
    width: int
    index: np.ndarray       # (G_b, width) int64 rows
    mask: np.ndarray        # (G_b, width) bool: True for real rows
    groups: np.ndarray      # (G_b,) positions in GroupLayout.sizes


@dataclass(frozen=True)
class GroupLayout:
    """Size-bucketed gather/scatter plan for per-group segment ops: each
    group lives in the bucket of width max(MIN_WIDTH, next power of two of
    its size), so a group of more than MIN_WIDTH / 2 rows is padded to less
    than twice its size. Scatter back with ``flat[b.index[b.mask]] =
    padded[b.mask]``: every real slot maps to a distinct row."""
    n_rows: int
    sizes: np.ndarray       # (G,) group sizes, in group-id order
    buckets: tuple          # Bucket per width, ascending

    @property
    def n_groups(self) -> int:
        return len(self.sizes)

    @property
    def widths(self) -> list:
        return [b.width for b in self.buckets]

    @property
    def padded_rows(self) -> int:
        """Slots over all buckets, padding included."""
        return int(sum(b.index.size for b in self.buckets))

    def pad(self, flat: np.ndarray, fill: float = 0.0) -> list:
        """Per bucket, ``flat`` gathered to (G_b, width) float64."""
        out = []
        for b in self.buckets:
            x = flat[b.index].astype(np.float64)
            x[~b.mask] = fill
            out.append(x)
        return out

    def unpad(self, padded: list) -> np.ndarray:
        out = np.zeros(self.n_rows, np.float64)
        for b, x in zip(self.buckets, padded):
            out[b.index[b.mask]] = x[b.mask]
        return out

    def group_rows(self):
        """Each group's rows, ascending, in group-id order."""
        rows = [None] * self.n_groups
        for b in self.buckets:
            for gi, idx, m in zip(b.groups, b.index, b.mask):
                rows[gi] = idx[m]
        return rows


def _bucket_width(size: int) -> int:
    return max(MIN_WIDTH, 1 << max(0, int(size) - 1).bit_length())


def group_layout(groups: np.ndarray) -> GroupLayout:
    """Build the bucketed layout from per-row group ids (any order)."""
    groups = np.asarray(groups, np.int64).reshape(-1)
    order = np.argsort(groups, kind="stable")
    sg = groups[order]
    if len(sg) == 0:
        return GroupLayout(0, np.zeros(0, np.int64), ())
    starts = np.flatnonzero(np.r_[True, sg[1:] != sg[:-1]])
    sizes = np.diff(np.r_[starts, len(sg)]).astype(np.int64)
    with trace.span("ranking/layout", groups=len(sizes), rows=len(groups)
                    ) as sp:
        widths = np.array([_bucket_width(s) for s in sizes], np.int64)
        buckets = []
        for w in np.unique(widths):
            sel = np.flatnonzero(widths == w)
            ar = np.arange(int(w))
            size = sizes[sel, None]
            idx = starts[sel, None] + np.minimum(ar[None, :], size - 1)
            buckets.append(Bucket(int(w), order[idx], ar[None, :] < size,
                                  sel))
        layout = GroupLayout(len(groups), sizes, tuple(buckets))
        if sp is not None:
            sp.args.update(widths=layout.widths,
                           padded_rows=layout.padded_rows)
    return layout


# ------------------------------------------------------- padded NDCG pieces

def _padded_rank_discounts(S: np.ndarray, valid: np.ndarray,
                           k: int) -> np.ndarray:
    """(G, m) rank discounts: d_i = 1/log2(1+rank_i) for rank_i <= k else 0,
    ranks 1-based by score descending with stable index tie-break. Padding
    slots sort last (score -> -inf) and get discount 0 via the rank cut."""
    s = np.where(valid, S, -np.inf)
    order = np.argsort(-s, axis=1, kind="stable")
    G, m = S.shape
    rank = np.empty((G, m), np.int64)
    np.put_along_axis(rank, order, np.broadcast_to(np.arange(1, m + 1), (G, m)),
                      axis=1)
    d = np.where(rank <= k, 1.0 / np.log2(1.0 + rank), 0.0)
    return np.where(valid, d, 0.0)


def _padded_idcg(gains: np.ndarray, valid: np.ndarray, k: int) -> np.ndarray:
    """(G,) ideal DCG@k from padded gains (2^rel - 1, zero on padding)."""
    g = np.where(valid, gains, -np.inf)
    top = -np.sort(-g, axis=1)[:, :k]
    disc = 1.0 / np.log2(np.arange(2, top.shape[1] + 2, dtype=np.float64))
    return (np.where(np.isfinite(top), top, 0.0) * disc).sum(axis=1)


def _gains(rel: np.ndarray) -> np.ndarray:
    return np.power(2.0, np.asarray(rel, np.float64)) - 1.0


# -------------------------------------------------------- lambda gradients

def _lambda_pass(S: np.ndarray, R: np.ndarray, valid: np.ndarray,
                 k: int) -> tuple[np.ndarray, np.ndarray]:
    """The oracle's all-pairs kernel over padded (G, m) tensors.

    For each ordered pair (i, j) with rel_i > rel_j (both valid):
      rho   = 1 / (1 + exp(s_i - s_j))              (RankNet crossing prob.)
      |ΔZ|  = |gain_i - gain_j| * |d_i - d_j| / IDCG (NDCG@k swap delta)
      g_i -= rho*|ΔZ|;  g_j += rho*|ΔZ|
      h_i += rho*(1-rho)*|ΔZ|;  h_j likewise
    Newton leaves (-Σg/Σh) then push winners' scores up.
    """
    gains = np.where(valid, _gains(R), 0.0)
    disc = _padded_rank_discounts(S, valid, k)
    idcg = _padded_idcg(gains, valid, k)                       # (G,)
    inv_idcg = np.where(idcg > 0, 1.0 / np.maximum(idcg, 1e-300), 0.0)

    sdiff = S[:, :, None] - S[:, None, :]                      # s_i - s_j
    with np.errstate(over="ignore"):
        rho = 1.0 / (1.0 + np.exp(sdiff))
    dz = (np.abs(gains[:, :, None] - gains[:, None, :])
          * np.abs(disc[:, :, None] - disc[:, None, :])
          * inv_idcg[:, None, None])
    M = ((R[:, :, None] > R[:, None, :])
         & valid[:, :, None] & valid[:, None, :])
    lam = np.where(M, rho * dz, 0.0)
    hlam = np.where(M, rho * (1.0 - rho) * dz, 0.0)
    g = lam.sum(axis=1) - lam.sum(axis=2)       # loser gets +, winner gets -
    h = hlam.sum(axis=1) + hlam.sum(axis=2)
    return g, h


def lambda_grad_naive(scores: np.ndarray, rel: np.ndarray,
                      layout: GroupLayout, k: int = 5
                      ) -> tuple[np.ndarray, np.ndarray]:
    """The float64 oracle: one ``_lambda_pass`` per group at the group's
    own (m_g, m_g) size, over every pair. Ranks break score ties by row
    index, as the device pass does."""
    g_out = np.zeros(layout.n_rows, np.float64)
    h_out = np.zeros(layout.n_rows, np.float64)
    scores = np.asarray(scores, np.float64)
    rel = np.asarray(rel, np.float64)
    for rows in layout.group_rows():
        valid = np.ones((1, len(rows)), bool)
        gg, hg = _lambda_pass(scores[rows][None], rel[rows][None], valid, k)
        g_out[rows] = gg[0]
        h_out[rows] = hg[0]
    return g_out, h_out


def _bucket_lambdas(S, gains, valid, inv_idcg, k: int):
    """(G, m) g and h of one bucket, float32, over the pairs that touch
    the top k of each group.

    Why the restriction is exact: a pair's weight carries |d_i - d_j|, and
    the discount d is 0 at every rank below k. A pair whose two members
    both rank below k therefore has |d_i - d_j| = 0 and adds exactly 0 to
    g and h. Every other pair has a member at one of the ranks 1..k; with
    p the higher-ranked member's rank, the pair is entry (p, j) of the
    (G, k, m) block with j ranked after p, and is counted once.
    """
    import jax.numpy as jnp
    from jax import lax

    G, m = S.shape
    kk = min(k, m)
    iota = lax.broadcasted_iota(jnp.int32, (G, m), 1)
    # descending score, ties by slot (= row order); -0.0 ties with 0.0
    key = jnp.where(valid, -jnp.where(S == 0, 0.0, S), jnp.inf)
    _, by_rank = lax.sort((key, iota), dimension=1, num_keys=2)
    top = by_rank[:, :kk]                                      # (G, kk)
    onehot = top[:, :, None] == iota[:, None, :]               # (G, kk, m)
    disc = jnp.asarray(1.0 / np.log2(np.arange(2, kk + 2)), jnp.float32)
    # slot j's discount: that of its rank when it is in the top kk, else 0
    d_slot = jnp.sum(jnp.where(onehot, disc[None, :, None], 0.0), axis=1)
    s_top = jnp.take_along_axis(S, top, axis=1)
    gain_top = jnp.take_along_axis(gains, top, axis=1)
    valid_top = jnp.take_along_axis(valid, top, axis=1)
    # slot j ranks after top position p: not among top[:, :p + 1]
    after = jnp.cumsum(onehot.astype(jnp.int32), axis=1) == 0
    dg = gain_top[:, :, None] - gains[:, None, :]   # > 0: the top one wins
    pair = after & valid_top[:, :, None] & valid[:, None, :] & (dg != 0)
    sign = jnp.where(dg > 0, 1.0, -1.0)
    # winner's score minus loser's; rho = 1 / (1 + exp(s_w - s_l))
    x = sign * (s_top[:, :, None] - S[:, None, :])
    rho = 1.0 / (1.0 + jnp.exp(x))
    dz = (jnp.abs(dg) * jnp.abs(disc[None, :, None] - d_slot[:, None, :])
          * inv_idcg[:, None, None])
    lam = jnp.where(pair, sign * rho * dz, 0.0)     # + : the top one won
    hl = jnp.where(pair, rho * (1.0 - rho) * dz, 0.0)
    g = (jnp.sum(lam, axis=1)
         - jnp.sum(jnp.where(onehot, jnp.sum(lam, axis=2)[:, :, None], 0.0),
                   axis=1))
    h = (jnp.sum(hl, axis=1)
         + jnp.sum(jnp.where(onehot, jnp.sum(hl, axis=2)[:, :, None], 0.0),
                   axis=1))
    return g, h


@functools.lru_cache(maxsize=None)
def _lambda_program():
    """One jitted program per bucket ladder (jit keys on the shapes):
    (N,) float32 scores -> (N,) float32 g and h."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("k",))
    def program(scores, buckets, row_slot, k):
        with jax.named_scope("lambda_pass"):
            gs, hs = [], []
            for index, valid, gains, inv_idcg in buckets:
                g, h = _bucket_lambdas(scores[index], gains, valid, inv_idcg,
                                       k)
                gs.append(g.reshape(-1))
                hs.append(h.reshape(-1))
            return (jnp.concatenate(gs)[row_slot],
                    jnp.concatenate(hs)[row_slot])

    return program


@dataclass
class RankingTable:
    """One table's groups and grades, laid out once: the host float64
    pieces NDCG needs and, uploaded on first use and kept, the device
    arrays of the lambda pass (bucket index maps, masks, gains, 1/IDCG and
    each row's flat slot)."""
    layout: GroupLayout
    rel: np.ndarray
    k: int
    gains: list = field(init=False)         # per bucket (G_b, width), 0 pad
    idcg: list = field(init=False)          # per bucket (G_b,) float64
    pair_slots: int = field(init=False)     # (p, j) entries evaluated
    pairs: int = field(init=False)          # real pairs touching the top k
    _device: tuple | None = field(default=None, init=False, repr=False)

    def __post_init__(self):
        self.rel = np.asarray(self.rel, np.float64)
        self.gains = [np.where(b.mask, _gains(R), 0.0)
                      for b, R in zip(self.layout.buckets,
                                      self.layout.pad(self.rel))]
        self.idcg = [_padded_idcg(gains, b.mask, self.k)
                     for b, gains in zip(self.layout.buckets, self.gains)]
        self.pair_slots = int(sum(b.index.size * min(self.k, b.width)
                                  for b in self.layout.buckets))
        kk = np.minimum(self.k, self.layout.sizes)
        self.pairs = int((kk * self.layout.sizes - kk * (kk + 1) // 2).sum())

    def device_arrays(self):
        if self._device is None:
            import jax.numpy as jnp
            buckets, slot, ofs = [], np.zeros(self.layout.n_rows, np.int64), 0
            for b, gains, idcg in zip(self.layout.buckets, self.gains,
                                      self.idcg):
                inv = np.where(idcg > 0, 1.0 / np.maximum(idcg, 1e-300), 0.0)
                buckets.append((jnp.asarray(b.index.astype(np.int32)),
                                jnp.asarray(b.mask),
                                jnp.asarray(gains.astype(np.float32)),
                                jnp.asarray(inv.astype(np.float32))))
                flat = ofs + np.arange(b.index.size).reshape(b.index.shape)
                slot[b.index[b.mask]] = flat[b.mask]
                ofs += b.index.size
            self._device = (tuple(buckets),
                            jnp.asarray(slot.astype(np.int32)))
        return self._device

    def lambdas(self, scores: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(N,) float32 g and h at ``scores`` from the device pass. Under
        an active tracer the ``ranking/lambda`` span waits for the device,
        so it holds the pass's device time."""
        import jax
        import jax.numpy as jnp
        buckets, row_slot = self.device_arrays()
        with trace.span("ranking/lambda", rows=self.layout.n_rows,
                        pair_slots=self.pair_slots, pairs=self.pairs):
            out = _lambda_program()(
                jnp.asarray(np.asarray(scores, np.float32)), buckets,
                row_slot, k=self.k)
            if trace.enabled():
                jax.block_until_ready(out)
        return np.asarray(out[0]), np.asarray(out[1])

    def ndcg(self, scores: np.ndarray) -> float:
        vals = np.zeros(self.layout.n_groups)
        for b, S, gains, idcg in zip(self.layout.buckets,
                                     self.layout.pad(scores), self.gains,
                                     self.idcg):
            dcg = (gains * _padded_rank_discounts(S, b.mask, self.k)).sum(1)
            vals[b.groups] = np.where(idcg > 0,
                                      dcg / np.maximum(idcg, 1e-300), 0.0)
        return float(vals.mean()) if len(vals) else 0.0


def lambda_grad_device(scores: np.ndarray, rel: np.ndarray,
                       layout: GroupLayout, k: int = 5
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Flat (N,) float32 lambda gradients and hessians from the device
    pass (no hessian floor)."""
    return RankingTable(layout, rel, k).lambdas(scores)


# ----------------------------------------------------------------- the loss

@dataclass
class RankingActivation:
    """Picklable serving head (losses.Loss ``activation`` contract): raw
    GBT scores ARE the ranking scores."""

    def activation(self, scores: np.ndarray) -> np.ndarray:
        return np.asarray(scores)[:, 0]


class LambdaMARTLoss:
    """The GBT ``Loss`` for task=RANKING (drop-in for losses.Loss).

    Holds the train/validation ranking tables; ``value`` reports
    ``1 - mean NDCG@k`` (lower is better, so LOSS_INCREASE early stopping
    works unchanged) and dispatches train vs valid by label-array identity.
    ``serving_head()`` strips the group arrays so pickled models stay small.
    """
    name = "LAMBDA_MART_NDCG"
    out_dim = 1

    def __init__(self, y_train: np.ndarray, layout_train: GroupLayout,
                 k: int = 5, y_valid: np.ndarray | None = None,
                 layout_valid: GroupLayout | None = None):
        self.k = int(k)
        self._y_train = y_train
        self._y_valid = y_valid
        self._train = RankingTable(layout_train, y_train, self.k)
        self._valid = (None if y_valid is None else
                       RankingTable(layout_valid, y_valid, self.k))

    def _table_for(self, y) -> tuple[RankingTable, str]:
        if y is self._y_train:
            return self._train, "train"
        if self._y_valid is not None and y is self._y_valid:
            return self._valid, "valid"
        raise ValueError(
            "LambdaMARTLoss saw a label array it has no group layout for; "
            "it is bound to the training/validation sets it was built with.")

    def init_pred(self, y, w):
        return np.zeros(1, np.float32)

    def grad_hess(self, pred, y, w):
        table, _ = self._table_for(y)
        g, h = table.lambdas(np.asarray(pred)[:, 0])
        # ranking groups are the weighting unit; per-example w stays 1 —
        # guard h away from 0 so Newton leaves stay finite in pairless nodes
        return (g.astype(np.float64)[:, None],
                np.maximum(h.astype(np.float64), H_FLOOR)[:, None])

    def value(self, pred, y, w):
        table, split = self._table_for(y)
        with trace.span("ranking/ndcg", split=split,
                        rows=table.layout.n_rows):
            return 1.0 - table.ndcg(np.asarray(pred)[:, 0])

    def training_logs(self) -> dict:
        """Where the lambda pass ran and the training table's buckets."""
        return {"ranking_pass": LAMBDA_PASS,
                "ranking_bucket_widths": self._train.layout.widths}

    def activation(self, scores):
        return np.asarray(scores)[:, 0]

    def serving_head(self):
        return RankingActivation()


def group_aware_split(groups: np.ndarray, ratio: float, seed: int
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Train/valid row split that keeps every group WHOLE (a group torn
    across the split would corrupt both its lambda pairs and its NDCG)."""
    groups = np.asarray(groups, np.int64)
    uniq = np.unique(groups)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(uniq))
    n_valid = int(round(len(uniq) * ratio))
    valid_groups = set(uniq[perm[:n_valid]].tolist())
    in_valid = np.isin(groups, list(valid_groups))
    return np.flatnonzero(~in_valid), np.flatnonzero(in_valid)
