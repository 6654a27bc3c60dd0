"""Device-resident training engine (DESIGN.md §6).

One training level = one compiled XLA program. The host never sees a
histogram: per level the jitted ``level step`` samples candidate features
(hash-keyed, sampling.py), accumulates per-(tree, slot, feature, bin)
gradient stats, runs the gain scans (numerical cumulative-sum; categorical
Fisher-order / one-hot), argmaxes the best split per frontier slot, allocates
children, routes every example, derives child stats, and writes the chosen
conditions into device-resident forest arrays. The only per-level host
traffic is one int32 — the compacted frontier width, used to pick the next
power-of-two shape bucket — and the forest arrays are fetched once per tree
block at the end.

Shapes are fixed per level: the frontier is padded to a power of two and
inactive slots are masked, so the jit cache holds at most
``log2(max_frontier)`` programs per configuration. Wide frontiers are
processed in ``W``-slot chunks inside the step so histogram scratch stays
bounded (the full ``(slots, F, B, S)`` tensor is never materialized for deep
trees).

Random Forests grow a block of K trees in lockstep: every state array
carries a leading tree axis and K is padded to the block size so all blocks
share one compiled program. Tree independence is preserved because feature
subsets are keyed by (tree, node), not drawn from a shared stream.

On TPU the numerical hist+gain pipeline is the fused Pallas kernel
(kernels/histogram/fused.py); on CPU hosts the same math runs as jnp inside
the jit (the kernel's interpret mode is only for the CI smoke —
resolve_backend's rule that interpret mode must never be the silent hot path
applies here too). Datasets with categorical features always use the jnp
path, which shares ``score_stats`` with the kernel. ``resolve_impl`` names
the choice and its reason; the learners put both in ``training_logs``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from repro.obs import trace
from repro.core.api import YdfError
from repro.core.binning import BinnedFeatures
from repro.core.sampling import keyed_feature_select_jnp, sample_size
from repro.core.splitters import REL_GAIN_EPS as _REL_EPS
from repro.core.tree import MASK_WORDS, Forest

_B = 256          # bin axis (uint8 codes)
_W_CAP = 512      # per-chunk slot width inside the level step
# Widest frontier at which routing reads each example's per-slot values by a
# one-hot over the P slots (P compare-selects per example and value) rather
# than by gathers: for numerical splits, and with category masks (eight more
# values per slot). Each is where the two cross, timing `_route` alone on a
# TPU v5e at N = 944,128, F = 28, one tree (median of 10 calls, one-hot vs
# gather): numerical 1.26 vs 1.87 ms at P = 32, 11.0 vs 14.9 at 8,192, 20.7
# vs 14.9 at 16,384; with masks 1.56 vs 7.31 ms at 32, 12.9 vs 22.1 at
# 2,048, 24.6 vs 22.2 at 4,096.
_DENSE_ROUTING_MAX_P = 8192
_DENSE_ROUTING_MAX_P_CAT = 2048

# (cfg, K, N, P) shape buckets whose level step has already been jitted in
# this process — lets the grower compile every frontier width with the
# first tree, and tracing label a first call at a bucket as compile time
# and the rest as execute time (DESIGN.md §13.2).
_stepped_shapes: set = set()


def device_unsupported_reason(params, binned: BinnedFeatures | None = None,
                              oblique_active: bool = False) -> str | None:
    """None when the device engine supports this configuration, else a
    human-readable reason (callers fall back to the batched host engine)."""
    sp = params.splitter
    if params.growing_strategy != "LOCAL":
        return ("growing_strategy=BEST_FIRST_GLOBAL is heap-ordered and "
                "host-sequential; device engine is level-wise (LOCAL) only")
    if oblique_active or sp.oblique:
        return "sparse-oblique projections scan raw columns on the host"
    if sp.categorical_algorithm == "RANDOM":
        return ("categorical_algorithm=RANDOM draws per-feature trial masks "
                "from the host rng stream")
    if sp.num_candidate_ratio < 1.0 and params.feature_sampling != "keyed":
        return ("per-node feature sampling on device requires keyed "
                "(hash-based) sampling; feature_sampling='stream' draws from "
                "the host rng")
    return None


def resolve_impl(impl: str, has_cat: bool) -> tuple[str, str | None]:
    """The level step's implementation for a ``device_impl`` request, plus
    the reason when "auto" resolves to anything but the fused kernel.
    Explicit requests are honored or refused — never silently swapped."""
    if impl in (None, "auto"):
        import jax
        backend = jax.default_backend()
        if backend != "tpu":
            return "jnp", (f"jax backend {backend!r} has no TPU; the fused "
                           "kernel would run in interpret mode")
        if has_cat:
            return "jnp", ("categorical features: the fused kernel scans "
                           "ordered bins only")
        return "pallas", None
    if impl in ("pallas", "interpret") and has_cat:
        raise YdfError(
            f"device_impl={impl!r} uses the fused numerical kernel, which "
            "does not handle categorical features. Solutions: (1) use "
            "device_impl='jnp', (2) drop categorical features.")
    if impl not in ("jnp", "pallas", "interpret"):
        raise YdfError(f"Unknown device_impl {impl!r}. Expected one of: "
                       "'auto', 'jnp', 'pallas', 'interpret'.")
    return impl, None


@dataclass(frozen=True)
class _StepConfig:
    kind: str
    l2: float
    min_examples: int
    min_gain: float
    cat_mode: str          # none | cart | onehot
    sample: bool           # per-node keyed feature sampling active
    sampling_key: int
    kf: int                # candidate features per node
    F: int
    S: int
    M: int                 # node capacity
    max_nodes: int         # allocation budget (<= M)
    impl: str              # jnp | pallas | interpret


@functools.lru_cache(maxsize=64)
def _level_step(cfg: _StepConfig):
    """Build the jitted level step for one engine configuration. The returned
    function recompiles per input shape bucket (P doubles level to level, K
    fixed per block) — at most log2(max_frontier) variants live in cache."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.histogram.fused import (
        NEG_INF,
        _numerical_gains,
        fused_split_lane_major,
        fused_split_pallas,
        score_stats,
    )

    kind, l2, min_ex = cfg.kind, cfg.l2, cfg.min_examples
    kf, S, M = cfg.kf, cfg.S, cfg.M

    def order_key(h):
        """jnp mirror of splitters._order_key on (..., B, S) histograms."""
        n = jnp.maximum(h[..., -1], 1e-12)
        if kind == "gh":
            return h[..., 0] / jnp.maximum(h[..., 1], 1e-12)
        if kind == "class":
            return h[..., 1] / n
        return h[..., 0] / n

    def chunk_best(codes, codes_t, nbins, iscat, stats, fsel_c, loc, w_slots):
        """Best split per slot for one W-wide slot chunk.

        codes (N, F) i32; codes_t: codes in the fused kernel's (F, 1, Np)
        layout (pallas/interpret without sampling), else None; stats
        (K, N, S) f32; fsel_c (K, W, kf) i32; loc (K, N) i32 local slot in
        [-1, W). Returns per-(K, W): gain f32, feature i32 (original column),
        split_bin i32, iscat bool, and the (K, W, B) go-right-by-code table.
        """
        K, N = loc.shape
        act = loc >= 0
        locc = jnp.maximum(loc, 0)
        if cfg.sample:
            # per-example candidate codes: codes[i, fsel_c[k, loc[k,i], j]]
            with jax.named_scope("candidate_gather"):
                fex = jnp.take_along_axis(
                    fsel_c, locc[:, :, None], axis=1)             # (K, N, kf)
                cex = codes[jnp.arange(N)[None, :, None], fex]    # (K, N, kf)
            column = lambda j: cex[:, :, j]                       # (K, N)
        else:
            # every slot's candidates are features 0..F-1 in order, so the
            # candidate codes are the table itself, shared by all K trees
            column = lambda j: codes[None, :, j]                  # (1, N)

        if cfg.impl in ("pallas", "interpret"):
            # fused kernel: hist + numerical scan + argmax fully in VMEM
            with jax.named_scope("histogram"):
                gains, js, sbins = [], [], []
                for k in range(K):
                    if cfg.sample:
                        gk, jk, bk = fused_split_pallas(
                            cex[k], stats[k], loc[k], w_slots,
                            _B, kind=kind, l2=l2, min_examples=min_ex,
                            interpret=(cfg.impl == "interpret"))
                    else:
                        gk, jk, bk = fused_split_lane_major(
                            codes_t, stats[k], loc[k], w_slots,
                            _B, kind=kind, l2=l2, min_examples=min_ex,
                            interpret=(cfg.impl == "interpret"))
                    gains.append(gk), js.append(jk), sbins.append(bk)
            with jax.named_scope("gain_scan"):
                gain = jnp.stack(gains)                           # (K, W)
                jwin = jnp.maximum(jnp.stack(js), 0)
                sbin = jnp.stack(sbins)
                feat = jnp.take_along_axis(
                    fsel_c, jwin[:, :, None], axis=2)[:, :, 0]
                tbl = (jnp.arange(_B)[None, None, :] >= sbin[:, :, None])
                iscat_w = jnp.zeros(gain.shape, bool)
                seg = jnp.where(act, loc, w_slots)
                pstats = jax.vmap(lambda s, v: jax.ops.segment_sum(
                    v, s, num_segments=w_slots + 1))(
                        seg, jnp.where(act[:, :, None], stats, 0.0))
                ps = score_stats(pstats[:, :w_slots], kind, l2)   # (K, W)
            return gain, feat, sbin, iscat_w, tbl, ps

        # ---- jnp path: explicit histogram + both scans under the same jit
        with jax.named_scope("histogram"):
            ws = jnp.where(act[:, :, None], stats, 0.0)           # (K, N, S)
            hists = []
            for j in range(kf):
                seg = jnp.where(act, locc * _B + column(j), w_slots * _B)
                h = jax.vmap(lambda s, v: jax.ops.segment_sum(
                    v, s, num_segments=w_slots * _B + 1))(seg, ws)
                hists.append(h[:, :w_slots * _B].reshape(K, w_slots, _B, S))
            hist = jnp.stack(hists, axis=2)                  # (K, W, kf, B, S)
        with jax.named_scope("gain_scan"):
            return scan_best(hist, nbins, iscat, fsel_c)

    def scan_best(hist, nbins, iscat, fsel_c):
        """Both gain scans over one chunk's (K, W, kf, B, S) histograms:
        the best split per slot and its go-right-by-code table."""
        K, w_slots = hist.shape[:2]
        parent = hist.sum(axis=3)                             # (K, W, kf, S)

        g_num = _numerical_gains(hist, parent, kind, l2, min_ex)
        pos = jnp.arange(_B)[None, None, None, :]
        if cfg.cat_mode == "none":
            g = g_num
            order = None
        else:
            nb_sel = nbins[fsel_c][..., None]                 # (K, W, kf, 1)
            iscat_sel = iscat[fsel_c]                         # (K, W, kf)
            if cfg.cat_mode == "cart":
                key = jnp.where(pos >= nb_sel, jnp.inf, order_key(hist))
                order = jnp.argsort(key, axis=3, stable=True)
                hs = jnp.take_along_axis(hist, order[..., None], axis=3)
                cum = jnp.cumsum(hs, axis=3)
                right = parent[:, :, :, None, :] - cum
                g_cat = (score_stats(cum, kind, l2)
                         + score_stats(right, kind, l2)
                         - score_stats(parent, kind, l2)[..., None])
                ok = ((cum[..., -1] >= min_ex) & (right[..., -1] >= min_ex)
                      & (pos < nb_sel - 1))
                g_cat = jnp.where(ok, g_cat, NEG_INF)
            else:  # one category vs rest
                order = None
                rest = parent[:, :, :, None, :] - hist
                g_cat = (score_stats(hist, kind, l2)
                         + score_stats(rest, kind, l2)
                         - score_stats(parent, kind, l2)[..., None])
                ok = ((hist[..., -1] >= min_ex) & (rest[..., -1] >= min_ex)
                      & (pos < nb_sel))
                g_cat = jnp.where(ok, g_cat, NEG_INF)
            g = jnp.where(iscat_sel[..., None], g_cat, g_num)

        flat = g.reshape(K, w_slots, kf * _B)
        fi = jnp.argmax(flat, axis=2)                         # lowest (j, b)
        gain = jnp.max(flat, axis=2)
        ps = score_stats(parent[:, :, 0], kind, l2)           # (K, W)
        jwin = (fi // _B).astype(jnp.int32)
        bwin = (fi % _B).astype(jnp.int32)
        feat = jnp.take_along_axis(fsel_c, jwin[:, :, None], axis=2)[:, :, 0]
        if cfg.cat_mode == "none":
            iscat_w = jnp.zeros(gain.shape, bool)
        else:
            iscat_w = jnp.take_along_axis(
                iscat[fsel_c], jwin[:, :, None], axis=2)[:, :, 0]
        sbin = jnp.where(iscat_w, 0, bwin + 1)

        # go-right-by-code table: the forest's category mask, which also
        # routes the examples of categorical steps
        bins = jnp.arange(_B)[None, None, :]
        tbl_num = bins >= sbin[:, :, None]
        if cfg.cat_mode == "none":
            return gain, feat, sbin, iscat_w, tbl_num, ps
        nb_win = jnp.take_along_axis(
            nbins[fsel_c], jwin[:, :, None], axis=2)[:, :, 0]
        if cfg.cat_mode == "cart":
            owin = jnp.take_along_axis(
                order, jwin[:, :, None, None],
                axis=2)[:, :, 0]                              # (K, W, B)
            rank = jnp.argsort(owin, axis=2, stable=True)     # inverse perm
            tbl_cat = (rank > bwin[:, :, None]) & (bins < nb_win[:, :, None])
        else:
            tbl_cat = bins == bwin[:, :, None]
        tbl = jnp.where(iscat_w[:, :, None], tbl_cat, tbl_num)
        return gain, feat, sbin, iscat_w, tbl, ps

    @jax.jit
    def step(codes, codes_t, nbins, iscat, stats, tree_ids, slot_of, slot_node,
             feat_a, sbin_a, catm_a, left_a, gain_a, lstats_a, nn, node_of,
             depth):
        K, P = slot_node.shape
        karange = jnp.arange(K)[:, None]

        # 1. candidate features per (tree, slot), keyed by (tree, node id)
        with jax.named_scope("candidate_gather"):
            if cfg.sample:
                fsel = keyed_feature_select_jnp(
                    cfg.sampling_key, tree_ids[:, None],
                    jnp.maximum(slot_node, 0), cfg.F, kf)         # (K, P, kf)
            else:
                fsel = jnp.broadcast_to(jnp.arange(cfg.F, dtype=jnp.int32),
                                        (K, P, cfg.F))

        # 2. best split per slot, W slots at a time (bounds hist scratch)
        W = min(P, _W_CAP)
        outs = []
        for g0 in range(0, P, W):
            loc = jnp.where((slot_of >= g0) & (slot_of < g0 + W),
                            slot_of - g0, -1)
            outs.append(chunk_best(codes, codes_t, nbins, iscat, stats,
                                   fsel[:, g0:g0 + W], loc, W))
        gain, feat_w, sbin_w, iscat_w, tbl, ps = (
            jnp.concatenate([o[i] for o in outs], axis=1) if len(outs) > 1
            else outs[0][i] for i in range(6))

        # 3. validity + child allocation (frontier-order, budget-capped).
        # The gain floor is scale-aware (splitters.REL_GAIN_EPS): f32 noise
        # around a true gain of 0 must not read as a valid split.
        with jax.named_scope("forest_write"):
            floor = jnp.maximum(cfg.min_gain, _REL_EPS * jnp.abs(ps))
            valid = (gain > floor) & jnp.isfinite(gain) & (slot_node >= 0)
            vi = valid.astype(jnp.int32)
            rank = jnp.cumsum(vi, axis=1) - vi                    # exclusive
            valid &= nn[:, None] + 2 * (rank + 1) <= cfg.max_nodes
            left_id = jnp.where(valid, nn[:, None] + 2 * rank, -1)
            nv = valid.sum(axis=1).astype(jnp.int32)
            first, nn = nn, nn + 2 * nv                       # first child ids
            depth = depth + (nv > 0)

            # 4. write the chosen conditions into the device forest arrays
            pidx = jnp.where(valid, slot_node, M)                 # M drops
            feat_a = feat_a.at[karange, pidx].set(feat_w, mode="drop")
            sbin_a = sbin_a.at[karange, pidx].set(sbin_w, mode="drop")
            left_a = left_a.at[karange, pidx].set(left_id, mode="drop")
            gain_a = gain_a.at[karange, pidx].set(jnp.maximum(gain, 0.0),
                                                  mode="drop")
            bits = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
            packed = (tbl.reshape(K, P, MASK_WORDS, 32).astype(jnp.uint32)
                      * bits).sum(axis=3, dtype=jnp.uint32)
            cidx = jnp.where(valid & iscat_w, slot_node, M)
            catm_a = catm_a.at[karange, cidx].set(packed, mode="drop")

        # 5. route every example of a split slot to its child
        with jax.named_scope("routing"):
            node_of, slot_of = _route(
                slot_of, node_of, first, valid, rank, feat_w, sbin_w,
                None if cfg.cat_mode == "none" else packed, codes, codes_t)

        # 6. child stats in one segment-sum; new frontier = compacted children
        with jax.named_scope("child_stats"):
            seg = jnp.where(slot_of >= 0, slot_of, 2 * P)
            csum = jax.vmap(lambda s, v: jax.ops.segment_sum(
                v, s, num_segments=2 * P + 1))(
                    seg, jnp.where(slot_of[:, :, None] >= 0, stats, 0.0))
            csum = csum[:, :2 * P]                                # (K, 2P, S)
            child_node = jnp.full((K, 2 * P), -1, jnp.int32)
            lidx = jnp.where(valid, 2 * rank, 2 * P)
            child_node = child_node.at[karange, lidx].set(left_id, mode="drop")
            child_node = child_node.at[karange, lidx + 1].set(left_id + 1,
                                                              mode="drop")
            nidx = jnp.where(child_node >= 0, child_node, M)
            lstats_a = lstats_a.at[karange, nidx].set(csum, mode="drop")

        return (slot_of, child_node, feat_a, sbin_a, catm_a, left_a, gain_a,
                lstats_a, nn, node_of, depth, nv)

    return step


def _routing(P: int, cat: bool) -> str:
    """How the level step reads each example's per-slot values at frontier
    width P, with category masks when ``cat``: "dense" (a one-hot over the P
    slots) or "gather"."""
    limit = _DENSE_ROUTING_MAX_P_CAT if cat else _DENSE_ROUTING_MAX_P
    return "dense" if P <= limit else "gather"


def _route(slot_of, node_of, first, valid, rank, feat, sbin, masks, codes,
           codes_t):
    """Route every example of a split slot to its child, with no gather of
    per-example values up to frontier width ``_DENSE_ROUTING_MAX_P``
    (``_DENSE_ROUTING_MAX_P_CAT`` with category masks).

    slot_of, node_of (K, N) i32: each example's frontier slot (-1: none) and
    node. Per slot (K, P): valid bool, rank i32 (order among the split
    slots), feat and sbin i32. first (K,) i32: the id of the level's first
    child, so slot p splits into first + 2 * rank[p] and the node after.
    masks (K, P, MASK_WORDS) u32: bit b of word w says whether code
    32 w + b goes right; None when every split is numerical (code >= sbin
    goes right). codes (N, F) i32, or codes_t in the (F, 1, Np) lane-major
    layout when the step has it. Returns the new node_of and slot_of
    (2 * rank + go; -1 for examples whose slot did not split).
    """
    import jax.numpy as jnp

    K, P = valid.shape
    N, F = codes.shape
    dense = _routing(P, masks is not None) == "dense"
    if dense:
        hit = slot_of[:, None, :] == jnp.arange(P, dtype=jnp.int32)[:, None]

        def at_slot(table):                          # (K, P) -> (K, N)
            return jnp.where(hit, table[..., None], 0).sum(axis=1,
                                                         dtype=table.dtype)
    else:
        slotc = jnp.maximum(slot_of, 0)

        def at_slot(table):
            got = jnp.take_along_axis(table, slotc, axis=1)
            return jnp.where(slot_of >= 0, got, 0)

    key = at_slot(jnp.where(valid, rank + 1, 0))  # 0: the example stays
    # split feature and bin in one value (sbin <= _B)
    split = at_slot(feat * (2 * _B) + sbin)
    f_ex = split // (2 * _B)
    # the example's code at its split feature: one pass over the table
    fs = jnp.arange(F, dtype=jnp.int32)
    if codes_t is not None:
        code = jnp.where(f_ex[:, None, :] == fs[:, None],
                         codes_t[None, :, 0, :N], 0).sum(axis=1)
    else:
        code = jnp.where(f_ex[:, :, None] == fs, codes[None], 0).sum(axis=2)
    if masks is None:
        go = (code >= split % (2 * _B)).astype(jnp.int32)
    else:
        word_of = code >> 5
        if dense:
            # one (K, P, N) select per word: selecting all words at once
            # keeps the (K, P, N) one-hot in memory on the v5e (P bytes per
            # example from P = 128)
            word = jnp.zeros(code.shape, masks.dtype)
            for w in range(masks.shape[2]):
                word = jnp.where(word_of == w, at_slot(masks[:, :, w]), word)
        else:
            word = masks[jnp.arange(K)[:, None], slotc, word_of]
        go = ((word >> (code & 31).astype(jnp.uint32)) & 1).astype(jnp.int32)
    child = 2 * (key - 1) + go
    return (jnp.where(key > 0, first[:, None] + child, node_of),
            jnp.where(key > 0, child, -1))


def _next_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def _device_codes(binned: BinnedFeatures, lane_major: bool = False):
    """codes as a device int32 array, n_bins, is_cat and, once asked for
    with ``lane_major``, the codes in the fused kernel's (F, 1, Np) layout
    (else None), which the unsampled fused level step reads as is. Cached
    on the BinnedFeatures instance (shared across trees, blocks, and
    boosting iterations); the layout is built on the device."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.histogram.histogram import lane_major_codes
    cached = getattr(binned, "_device_codes", None)
    if cached is None or (lane_major and cached[3] is None):
        with trace.span("grower_device/codes", rows=binned.codes.shape[0],
                        lane_major=lane_major):
            if cached is None:
                cached = (jnp.asarray(binned.codes.astype(np.int32)),
                          jnp.asarray(binned.n_bins.astype(np.int32)),
                          jnp.asarray(binned.is_cat), None)
            if lane_major:
                cached = cached[:3] + (jax.block_until_ready(
                    lane_major_codes(cached[0])),)
        binned._device_codes = cached
    return cached


def grow_trees_device(forest: Forest, ts, binned: BinnedFeatures,
                      stats_list, actives, leaf_fn, params,
                      block: int | None = None) -> np.ndarray:
    """Grow trees ``ts`` of ``forest`` in device-resident lockstep. The block
    is padded to ``block`` trees so every block reuses one compiled program.
    Returns the final ``node_of`` routing, (len(ts), N) int32."""
    import jax
    import jax.numpy as jnp

    sp = params.splitter
    Kr = len(ts)
    K = max(Kr, block or Kr)
    N, F = binned.codes.shape
    S = stats_list[0].shape[1]
    M = min(forest.max_nodes, params.max_nodes)
    has_cat = bool(binned.is_cat.any())
    impl, _ = resolve_impl(getattr(params, "device_impl", "auto"), has_cat)
    one_hot = sp.categorical_algorithm == "ONE_HOT" or (
        sp.stat_kind == "class" and S > 3)
    cfg = _StepConfig(
        kind=sp.stat_kind, l2=float(sp.l2), min_examples=int(sp.min_examples),
        min_gain=float(sp.min_gain),
        cat_mode=("none" if not has_cat else
                  "onehot" if one_hot else "cart"),
        sample=sp.num_candidate_ratio < 1.0,
        sampling_key=int(params.sampling_key),
        kf=(sample_size(sp.num_candidate_ratio, F)
            if sp.num_candidate_ratio < 1.0 else F),
        F=F, S=S, M=M, max_nodes=int(params.max_nodes), impl=impl)
    step = _level_step(cfg)

    codes, nbins, iscat, codes_t = _device_codes(
        binned, lane_major=impl != "jnp" and not cfg.sample)
    with trace.span("grower_device/upload", trees=Kr, rows=N):
        stats_np = np.zeros((K, N, S), np.float32)
        act_np = np.zeros((K, N), bool)
        for b in range(Kr):
            stats_np[b] = stats_list[b].astype(np.float32)
            act_np[b] = actives[b]
        stats = jnp.asarray(stats_np)
        node_of = jnp.asarray(np.where(act_np, 0, -1).astype(np.int32))
    slot_of = node_of
    slot_node = jnp.zeros((K, 1), jnp.int32)
    tree_ids = jnp.asarray(np.asarray(
        [int(t) for t in ts] + [0] * (K - Kr), np.int32))
    feat_a = jnp.full((K, M), -1, jnp.int32)
    sbin_a = jnp.zeros((K, M), jnp.int32)
    catm_a = jnp.zeros((K, M, MASK_WORDS), jnp.uint32)
    left_a = jnp.full((K, M), -1, jnp.int32)
    gain_a = jnp.zeros((K, M), jnp.float32)
    lstats_a = jnp.zeros((K, M, S), jnp.float32)
    lstats_a = lstats_a.at[:, 0].set(stats.sum(axis=1))
    nn = jnp.ones((K,), jnp.int32)
    depth = jnp.zeros((K,), jnp.int32)

    # Every frontier width a tree of this shape can reach (P = 2, 4, ...,
    # 2^(max_depth - 1), within the node capacity) compiles with the first
    # tree: a width that a later tree reached first would compile there, a
    # stall of seconds on the chip between two trees. The warm-up runs the
    # step once per width on the root's state and drops what it returns.
    widths = [P for P in (1 << i for i in range(1, params.max_depth))
              if 2 * P <= M and (cfg, K, N, P) not in _stepped_shapes]
    if widths:
        with trace.span("grower_device/warm_up", widths=widths):
            for P in widths:
                _stepped_shapes.add((cfg, K, N, P))
                jax.block_until_ready(step(
                    codes, codes_t, nbins, iscat, stats, tree_ids, slot_of,
                    jnp.zeros((K, P), jnp.int32), feat_a, sbin_a, catm_a,
                    left_a, gain_a, lstats_a, nn, node_of, depth))

    for _level in range(params.max_depth):
        # Tracing splits compile time from execute time per (cfg, shape
        # bucket): the first call at a new frontier bucket pays the jit
        # trace+compile, later calls replay the cached executable. The
        # block_until_ready sync only happens while a tracer is active —
        # the untraced path keeps the async dispatch pipeline intact.
        shape_key = (cfg, K, N, int(slot_node.shape[1]))
        first = shape_key not in _stepped_shapes
        _stepped_shapes.add(shape_key)
        if trace.enabled():
            with trace.span("grower_device/level_step", level=_level,
                            P=int(slot_node.shape[1]), compile=first,
                            candidates="sampled" if cfg.sample else "all",
                            routing=_routing(int(slot_node.shape[1]),
                                             cfg.cat_mode != "none")):
                out = step(
                    codes, codes_t, nbins, iscat, stats, tree_ids, slot_of,
                    slot_node, feat_a, sbin_a, catm_a, left_a, gain_a,
                    lstats_a, nn, node_of, depth)
                jax.block_until_ready(out)
        else:
            out = step(
                codes, codes_t, nbins, iscat, stats, tree_ids, slot_of,
                slot_node, feat_a, sbin_a, catm_a, left_a, gain_a, lstats_a,
                nn, node_of, depth)
        (slot_of, slot_node, feat_a, sbin_a, catm_a, left_a, gain_a,
         lstats_a, nn, node_of, depth, nv) = out
        # the single per-level host sync: the compacted frontier width,
        # used to choose the next power-of-two shape bucket
        nv_max = int(nv.max())
        if nv_max == 0:
            break
        P_next = _next_pow2(2 * nv_max)
        slot_node = slot_node[:, :P_next]

    # one fetch per block: the device arrays copied to the host
    with trace.span("grower_device/fetch", trees=Kr):
        (feat_h, sbin_h, catm_h, left_h, gain_h, lstats_h, nn_h, node_h,
         depth_h) = tuple(np.asarray(a) for a in
                          (feat_a, sbin_a, catm_a, left_a, gain_a, lstats_a,
                           nn, node_of, depth))
    # decode: leaves and thresholds written into the host Forest
    with trace.span("grower_device/decode", trees=Kr):
        for b, t in enumerate(ts):
            n_t = int(nn_h[b])
            forest.n_nodes[t] = n_t
            forest.feature[t, :M] = feat_h[b]
            forest.left_child[t, :M] = left_h[b]
            forest.cat_mask[t, :M] = catm_h[b]
            forest.split_bin[t, :M] = np.maximum(sbin_h[b],
                                                 0).astype(np.uint16)
            if forest.split_gain is not None:
                forest.split_gain[t, :M] = gain_h[b]
            for n in range(1, n_t):
                forest.leaf_value[t, n] = leaf_fn(
                    lstats_h[b, n].astype(np.float64))
            num = (feat_h[b, :n_t] >= 0) & ~binned.is_cat[
                np.maximum(feat_h[b, :n_t], 0)]
            for n in np.where(num)[0]:
                f, sb = int(feat_h[b, n]), int(sbin_h[b, n])
                sb = min(sb, len(binned.boundaries[f]))
                forest.threshold[t, n] = binned.threshold_value(f, sb)
            forest.depth = max(forest.depth, int(depth_h[b]))
    return node_h[:Kr]
