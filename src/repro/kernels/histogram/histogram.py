"""Pallas TPU kernel: per-node gradient histograms as one-hot MXU matmuls.

The CPU/GPU formulation of histogram building is a scatter-add; TPUs have no
fast scatter, but they have a 128x128 systolic MXU. The TPU-native insight
(DESIGN.md §2.1): express the histogram as

    hist[s, n, b] = sum_i onehot_node[n, i] * stats[s, i] * onehot_bin[b, i]
                  = ((onehot_node * stats_s) @ onehot_bin^T)[n, b]

i.e. a (W, TN) @ (TN, B) matmul per statistic and feature, fully
MXU-resident. All S of them run as one bfloat16 pass (``accumulate_tile``):
the one-hot is exact in bfloat16, and each f32 statistic splits exactly into
three bfloat16 parts (``split_bf16x3``), so one default-precision matmul with
the 3·S·W part rows stacked as its LHS, accumulated in f32, forms the same
exact products as a six-pass ``Precision.HIGHEST`` f32 dot per statistic.
The MXU's stationary operand is then the (TN, B) one-hot, pushed once per
tile instead of 6·S times. Where 3·S·W exceeds ``LHS_ROWS``, the pass runs
once per block of slots (``slot_block``), which bounds its VMEM.

Layout: examples run along the 128-wide lane axis. Codes, stats and node ids
enter transposed — ``(F, 1, N)``, ``(S, N)``, ``(1, N)`` — so every block is
a lane-dense ``(rows, TN)`` tile, and the accumulator is ``(S, nodes, B)``
with the bin axis on lanes. A ``(..., B, S)`` accumulator would pad its
S <= 4 minor axis to 128 lanes (32x the VMEM); see DESIGN.md §6.1.

Grid: (F, N // TN). Example tiles accumulate into the same per-feature output
block (revisited across the trailing grid dim; TPU grid steps are sequential,
so read-modify-write on out_ref is well-defined).

VMEM per step (TN=512, B=256, S=4, n_nodes=32): codes + slots 4KB, stats
8KB, onehot_bin (B, TN) bf16 256KB, the stacked LHS (3·S·nodes, TN) 768KB
in f32 and 384KB in bf16, its product (3·S·nodes, B) 384KB, out block
(S, nodes, B) 128KB double-buffered -> ~2 MB. A pass's LHS and product stay
under ~3 MB at any width; the double-buffered out block grows with S·nodes
and alone fills the 16 MiB of scoped VMEM at 2048 nodes and S=4.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def split_bf16x3(x):
    """f32 ``x`` -> bfloat16 ``(hi, mid, lo)`` with hi + mid + lo == x
    exactly: each part rounds what the ones before it leave, and three
    8-bit significands hold the 24 of a float32. Products of the parts with
    a 0/1 operand are exact in the MXU's f32 accumulator."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


# Rows of the stacked bfloat16 LHS in one MXU pass. A wide frontier or many
# statistics run as several passes, each over a block of slots, so that one
# pass's LHS (in f32 and in bf16) and its f32 product take about 3 MiB of
# VMEM at TN=512, B=256, whatever S and W are.
LHS_ROWS = 768


def slot_block(n_stats: int, n_slots: int) -> int:
    """Slots per MXU pass: a multiple of 8 with 3·S·block <= LHS_ROWS (8 at
    the least), or all ``n_slots`` where they fit."""
    return min(n_slots, max(8, LHS_ROWS // (3 * n_stats) // 8 * 8))


def accumulate_tile(codes, slot, stats, acc_ref, *, n_slots: int,
                    n_bins: int):
    """acc_ref[s, w, b] += sum of stats[s, i] over the tile's examples i with
    slot[i] == w and codes[i] == b. codes/slot: (1, TN) int32 (slot -1 =
    inactive, never matches); stats: (S, TN) f32; acc_ref: (S, W, B), W a
    multiple of 8.

    One MXU pass per block of ``slot_block(S, W)`` slots, for all S
    statistics: the LHS stacks, for each of the three bfloat16 parts of each
    statistic, the (block, TN) rows that hold the part where the example's
    slot is w and 0 elsewhere; the RHS is the exact bfloat16 one-hot of the
    bins. The products are exact and accumulate in f32, as a
    ``Precision.HIGHEST`` f32 dot's do; only the order of the f32 additions
    differs."""
    TN = codes.shape[-1]
    n_stats = acc_ref.shape[0]
    onehot_bin = (jax.lax.broadcasted_iota(jnp.int32, (n_bins, TN), 0)
                  == codes).astype(jnp.bfloat16)                # (B, TN)
    parts = [p.astype(jnp.float32) for p in split_bf16x3(stats)]  # (S, TN)
    Wb = slot_block(n_stats, n_slots)
    for w0 in range(0, n_slots, Wb):
        wb = min(Wb, n_slots - w0)
        in_slot = (jax.lax.broadcasted_iota(jnp.int32, (wb, TN), 0)
                   == slot - w0)                                # (wb, TN)
        lhs = jnp.concatenate(
            [jnp.where(in_slot, p[s:s + 1, :], 0.0)
             for s in range(n_stats) for p in parts],
            axis=0).astype(jnp.bfloat16)                       # (3S·wb, TN)
        out = jax.lax.dot_general(
            lhs, onehot_bin, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (3S·wb, B)
        block = [out[r:r + wb] for r in range(0, out.shape[0], wb)]
        for s in range(n_stats):
            acc_ref[s, w0:w0 + wb] += ((block[3 * s] + block[3 * s + 1])
                                       + block[3 * s + 2])


TILE_N = 512      # example tile (lanes) of both kernels


def _example_tiling(n: int, tile_n: int) -> tuple[int, int]:
    """The example tile TN (a multiple of 128) for ``n`` examples, and the
    rows padded to whole tiles."""
    TN = min(_round_up(tile_n, 128), _round_up(max(n, 1), 128))
    return TN, _round_up(max(n, 1), TN)


@functools.partial(jax.jit, static_argnames=("tile_n",))
def lane_major_codes(codes, tile_n: int = TILE_N):
    """(N, F) codes -> the kernels' lane-major (F, 1, Np) int32 layout, rows
    padded with code 0 to whole example tiles. The device grower caches this
    once per table; the kernels' (N, F) entry points build it per call."""
    N = codes.shape[0]
    _, Np = _example_tiling(N, tile_n)
    return jnp.pad(codes.astype(jnp.int32).T, ((0, 0), (0, Np - N)))[:, None]


def transpose_rows(stats, slot_of, tile_n: int = TILE_N):
    """(N, S) stats, (N,) slots -> lane-major, tile-padded (S, Np) f32 and
    (1, Np) int32 (padding rows get slot -1, which never matches), plus the
    example tile TN."""
    N = stats.shape[0]
    TN, Np = _example_tiling(N, tile_n)
    stats_t = jnp.pad(stats.astype(jnp.float32).T, ((0, 0), (0, Np - N)))
    slot_t = jnp.pad(slot_of.astype(jnp.int32), (0, Np - N),
                     constant_values=-1)
    return stats_t, slot_t[None, :], TN


def _hist_kernel(codes_ref, stats_ref, node_ref, out_ref, *, n_nodes: int,
                 n_bins: int):
    @pl.when(pl.program_id(1) == 0)   # first example tile of this feature
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    accumulate_tile(codes_ref[...], node_ref[...], stats_ref[...], out_ref,
                    n_slots=n_nodes, n_bins=n_bins)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "tile_n",
                                             "interpret"))
def histogram_pallas(codes: jax.Array, stats: jax.Array, node_of: jax.Array,
                     n_nodes: int, n_bins: int = 256, tile_n: int = TILE_N,
                     interpret: bool = False) -> jax.Array:
    """codes: (N, F) integer bin codes; stats: (N, S) f32; node_of: (N,)
    int32 (-1 = inactive). -> (n_nodes, F, B, S) f32."""
    F = codes.shape[1]
    S = stats.shape[1]
    Wn = _round_up(n_nodes, 8)          # sublane-aligned node axis
    codes_t = lane_major_codes(codes, tile_n)
    stats_t, node_t, TN = transpose_rows(stats, node_of, tile_n)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_nodes=Wn, n_bins=n_bins),
        grid=(F, codes_t.shape[-1] // TN),
        in_specs=[
            pl.BlockSpec((None, 1, TN), lambda f, i: (f, 0, i)),  # codes row
            pl.BlockSpec((S, TN), lambda f, i: (0, i)),           # stats tile
            pl.BlockSpec((1, TN), lambda f, i: (0, i)),           # node tile
        ],
        out_specs=pl.BlockSpec((None, S, Wn, n_bins),
                               lambda f, i: (f, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, S, Wn, n_bins), jnp.float32),
        interpret=interpret,
        name="histogram_pallas",
    )(codes_t, stats_t, node_t)
    return out.transpose(2, 0, 3, 1)[:n_nodes]            # (nodes, F, B, S)
