"""Fused Pallas TPU kernel: histogram accumulation + gain scan + argmax.

PR 1's training path built the full ``(nodes, F, B, S)`` histogram on device,
shipped it to the host, and scanned gains in numpy — the kernel was off the
critical path because the transfer dwarfed the accumulation (DESIGN.md §6).
This kernel keeps the whole per-feature pipeline in VMEM:

    1. accumulate hist[s, w, b] as one exact bfloat16 one-hot MXU matmul
       per example tile (histogram.py's ``accumulate_tile``),
    2. cumulative-sum the bins with an upper-triangular MXU matmul,
    3. score left/right partitions per split position (gh / class / moment
       stat layouts, §3.8), mask by min_examples,
    4. take the first maximum over bins, then fold into the running per-slot
       best across features (grid-sequential read-modify-write, strict ``>``
       so ties keep the lowest feature index — numpy argmax semantics).

Only the ``(n_slots, 1)`` best gain / feature / split_bin columns ever leave
the kernel; the histogram lives and dies in VMEM scratch.

Numerical (ordered-bin) conditions only: categorical splitters need a
Fisher-order argsort, which the device engine runs as jnp inside the same jit
(grower_device.py). Gain math lives in ``score_columns`` and is shared with
the jnp reference path so kernel and oracle stay formula-identical.

Grid: (kf, N // TN) — feature-major, example tiles inner (sequential on TPU,
so the scratch accumulator and the cross-feature running best are
well-defined read-modify-write). Examples run along lanes, as in
histogram.py.

VMEM per step (TN=512, W=512, B=256, S=4): hist scratch (S, W, B) 2 MiB,
onehot_bin (B, TN) bf16 256 KB, and per MXU pass over a block of slots
(histogram.py's ``slot_block``) a stacked LHS of three bfloat16 parts per
statistic of at most ``LHS_ROWS`` = 768 rows, 1.5 MiB in f32 and 768 KB in
bf16, and its product, 768 KB. The scan works on
128-slot row blocks (~1.5 MiB of temporaries). The slot axis is
padded to a multiple of 8 sublanes; the bin axis is the lane axis, so no
minor axis is padded to 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.histogram.histogram import (
    TILE_N,
    _round_up,
    accumulate_tile,
    lane_major_codes,
    transpose_rows,
)

NEG_INF = -1e30  # matches splitters.NEG_INF

# The scan's cumulative-sum matmul multiplies f32 histogram sums, which the
# MXU's default precision would round to bfloat16; HIGHEST keeps its
# products exact. It runs once per feature per level, so its six passes cost
# little. The per-tile accumulation splits its f32 operand exactly instead
# (``histogram.split_bf16x3``) and runs at the default precision.
_HIGHEST = jax.lax.Precision.HIGHEST

# cephes logf: log(1 + f) = f - f^2 / 2 + f^3 * P(f) for f in
# [sqrt(1/2) - 1, sqrt(2) - 1]; P's coefficients, highest power first
_LOGF_P = (7.0376836292e-2, -1.1514610310e-1, 1.1676998740e-1,
           -1.2420140846e-1, 1.4249322787e-1, -1.6668057665e-1,
           2.0000714765e-1, -2.4999993993e-1, 3.3333331174e-1)


def log_f32(x):
    """Natural log of positive normal float32 ``x`` to about an ulp, from
    bit operations, adds and multiplies only. The TPU's own float32 log is
    off by up to ~1e-4 (thousands of ulp near 1), which flips near-tied
    class gains against the host's float64 scan."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = (bits >> 23) - 127
    m = jax.lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000,
                                     jnp.float32)              # [1, 2)
    big = m > 1.4142135623730951
    f = jnp.where(big, m * 0.5, m) - 1.0
    e = jnp.where(big, e + 1, e).astype(jnp.float32)
    z = f * f
    y = functools.reduce(lambda acc, c: acc * f + c, _LOGF_P[1:],
                         jnp.full_like(f, _LOGF_P[0]))
    y = f * z * y + e * -2.12194440e-4 - 0.5 * z
    return f + y + e * 0.693359375


def score_columns(cols, kind: str, l2: float):
    """jnp mirror of splitters._score on a sequence of per-stat arrays
    (``cols[s]`` holds stat s). Gain of a split = score(L) + score(R) -
    score(P)."""
    if kind == "gh":
        g, h = cols[0], cols[1]
        return 0.5 * jnp.square(g) / (h + l2 + 1e-12)
    if kind == "class":
        n = cols[-1]
        tot = jnp.maximum(n, 1e-12)
        # A class holding the whole node (p = 1) adds exactly 0. The TPU's
        # f32 division is not correctly rounded, and c / c != 1 would give
        # every split of a pure node a spurious positive gain.
        plogp = [jnp.where(c >= n, 0.0, p * log_f32(jnp.maximum(p, 1e-12)))
                 for c, p in ((c, c / tot) for c in cols[:-1])]
        return n * functools.reduce(jnp.add, plogp)
    if kind == "moment":
        sy, n = cols[0], cols[-1]
        return jnp.square(sy) / jnp.maximum(n, 1e-12)
    raise ValueError(kind)


def score_stats(stats, kind: str, l2: float):
    """``score_columns`` on (..., S) stat vectors."""
    return score_columns([stats[..., s] for s in range(stats.shape[-1])],
                         kind, l2)


def _numerical_gains(hist, parent, kind: str, l2: float, min_examples: int):
    """Split-position gains for ordered bins. hist: (..., B, S); parent:
    (..., S). Position b means 'bins <= b go left' i.e. split_bin = b + 1;
    the last position (nothing right) is masked. Returns (..., B) gains."""
    B = hist.shape[-2]
    left = jnp.cumsum(hist, axis=-2)                       # (..., B, S)
    right = parent[..., None, :] - left
    g = (score_stats(left, kind, l2) + score_stats(right, kind, l2)
         - score_stats(parent, kind, l2)[..., None])
    ok = ((left[..., -1] >= min_examples)
          & (right[..., -1] >= min_examples)
          & (jax.lax.broadcasted_iota(jnp.int32, g.shape, g.ndim - 1) < B - 1))
    return jnp.where(ok, g, NEG_INF)


def _fused_kernel(codes_ref, stats_ref, slot_ref, gain_ref, feat_ref, bin_ref,
                  hist_ref, *, n_slots: int, n_bins: int, n_tiles: int,
                  kind: str, l2: float, min_examples: int):
    j = pl.program_id(0)      # feature index (outer)
    i = pl.program_id(1)      # example-tile index (inner, sequential)

    @pl.when((j == 0) & (i == 0))
    def _init_best():
        gain_ref[...] = jnp.full(gain_ref.shape, NEG_INF, jnp.float32)
        feat_ref[...] = jnp.full(feat_ref.shape, -1, jnp.int32)
        bin_ref[...] = jnp.zeros(bin_ref.shape, jnp.int32)

    @pl.when(i == 0)
    def _init_hist():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    accumulate_tile(codes_ref[...], slot_ref[...], stats_ref[...], hist_ref,
                    n_slots=n_slots, n_bins=n_bins)

    @pl.when(i == n_tiles - 1)
    def _scan():
        n_stats = hist_ref.shape[0]
        # cumulative sum over bins as an upper-triangular MXU matmul:
        # left[w, b] = sum_{b' <= b} hist[w, b']
        r = jax.lax.broadcasted_iota(jnp.int32, (n_bins, n_bins), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (n_bins, n_bins), 1)
        tri = (r <= c).astype(jnp.float32)                      # (B, B)
        R = min(n_slots, 128)                                   # row block
        for r0 in range(0, n_slots, R):
            rows = slice(r0, r0 + R)
            hist = [hist_ref[s, rows, :] for s in range(n_stats)]  # (R, B)
            parent = [h.sum(axis=1, keepdims=True) for h in hist]  # (R, 1)
            left = [jnp.dot(h, tri, precision=_HIGHEST,
                            preferred_element_type=jnp.float32) for h in hist]
            right = [p - lf for p, lf in zip(parent, left)]
            g = (score_columns(left, kind, l2) + score_columns(right, kind, l2)
                 - score_columns(parent, kind, l2))             # (R, B)
            pos = jax.lax.broadcasted_iota(jnp.int32, (R, n_bins), 1)
            ok = ((left[-1] >= min_examples) & (right[-1] >= min_examples)
                  & (pos < n_bins - 1))
            g = jnp.where(ok, g, NEG_INF)
            gb = jnp.max(g, axis=1, keepdims=True)              # (R, 1)
            bi = jnp.min(jnp.where(g == gb, pos, n_bins), axis=1,
                         keepdims=True)                         # first max
            prev = gain_ref[rows, :]
            better = gb > prev  # strict: ties keep the lowest feature index
            gain_ref[rows, :] = jnp.where(better, gb, prev)
            feat_ref[rows, :] = jnp.where(better, j, feat_ref[rows, :])
            bin_ref[rows, :] = jnp.where(better, bi + 1, bin_ref[rows, :])


@functools.partial(jax.jit, static_argnames=(
    "n_slots", "n_bins", "kind", "l2", "min_examples", "tile_n", "interpret"))
def fused_split_lane_major(codes_t: jax.Array, stats: jax.Array,
                           slot_of: jax.Array, n_slots: int,
                           n_bins: int = 256, *, kind: str = "gh",
                           l2: float = 0.0, min_examples: int = 5,
                           tile_n: int = TILE_N, interpret: bool = False):
    """``fused_split_pallas`` on codes already in the kernel's layout:
    codes_t (kf, 1, Np) int32 from ``lane_major_codes(codes, tile_n)``,
    taken as is; only the stats and slot ids are transposed here."""
    kf = codes_t.shape[0]
    S = stats.shape[1]
    # sublane-aligned slot axis, in whole 128-row scan blocks past 128
    Wp = _round_up(n_slots, 8 if n_slots <= 128 else 128)
    stats_t, slot_t, TN = transpose_rows(stats, slot_of, tile_n)
    if codes_t.shape[1:] != (1, stats_t.shape[-1]):
        raise ValueError(
            f"codes_t {codes_t.shape} is not lane_major_codes of "
            f"{stats.shape[0]} rows at tile_n={tile_n}")
    n_tiles = stats_t.shape[-1] // TN
    kernel = functools.partial(
        _fused_kernel, n_slots=Wp, n_bins=n_bins, n_tiles=n_tiles, kind=kind,
        l2=float(l2), min_examples=int(min_examples))
    out_shape = [jax.ShapeDtypeStruct((Wp, 1), dt)
                 for dt in (jnp.float32, jnp.int32, jnp.int32)]
    out_spec = pl.BlockSpec((Wp, 1), lambda j, i: (0, 0))
    gain, feat, sbin = pl.pallas_call(
        kernel,
        grid=(kf, n_tiles),
        in_specs=[
            pl.BlockSpec((None, 1, TN), lambda j, i: (j, 0, i)),  # one feature
            pl.BlockSpec((S, TN), lambda j, i: (0, i)),
            pl.BlockSpec((1, TN), lambda j, i: (0, i)),
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((S, Wp, n_bins), jnp.float32)],
        interpret=interpret,
        name="fused_split_pallas",
    )(codes_t, stats_t, slot_t)
    return gain[:n_slots, 0], feat[:n_slots, 0], sbin[:n_slots, 0]


@functools.partial(jax.jit, static_argnames=(
    "n_slots", "n_bins", "kind", "l2", "min_examples", "tile_n", "interpret"))
def fused_split_pallas(codes: jax.Array, stats: jax.Array, slot_of: jax.Array,
                       n_slots: int, n_bins: int = 256, *, kind: str = "gh",
                       l2: float = 0.0, min_examples: int = 5,
                       tile_n: int = TILE_N, interpret: bool = False):
    """codes: (N, kf) integer numerical bin codes, one column per candidate
    feature; stats: (N, S) f32; slot_of: (N,) int32 in [-1, n_slots).
    -> (gain (n_slots,) f32, feature-column (n_slots,) i32, split_bin
    (n_slots,) i32). feature == -1 when no position was scoreable."""
    return fused_split_lane_major(
        lane_major_codes(codes, tile_n), stats, slot_of, n_slots, n_bins,
        kind=kind, l2=l2, min_examples=min_examples, tile_n=tile_n,
        interpret=interpret)
