"""Pallas TPU kernel: per-node gradient histograms as one-hot MXU matmuls.

The CPU/GPU formulation of histogram building is a scatter-add; TPUs have no
fast scatter, but they have a 128x128 systolic MXU. The TPU-native insight
(DESIGN.md §2.1): express the histogram as

    hist[s, n, b] = sum_i onehot_node[n, i] * stats[s, i] * onehot_bin[b, i]
                  = ((onehot_node * stats_s) @ onehot_bin^T)[n, b]

i.e. S matmuls of (n_nodes, TN) @ (TN, B) per feature — fully MXU-resident.

Layout: examples run along the 128-wide lane axis. Codes, stats and node ids
enter transposed — ``(F, 1, N)``, ``(S, N)``, ``(1, N)`` — so every block is
a lane-dense ``(rows, TN)`` tile, and the accumulator is ``(S, nodes, B)``
with the bin axis on lanes. A ``(..., B, S)`` accumulator would pad its
S <= 4 minor axis to 128 lanes (32x the VMEM); see DESIGN.md §6.1.

Grid: (F, N // TN). Example tiles accumulate into the same per-feature output
block (revisited across the trailing grid dim; TPU grid steps are sequential,
so read-modify-write on out_ref is well-defined).

VMEM per step (TN=512, B=256, S=4, n_nodes=32): codes + slots 4KB, stats
8KB, onehot_bin (B, TN) 512KB, onehot_node (nodes, TN) 64KB, out block
(S, nodes, B) 128KB double-buffered -> ~1 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The stats operand is f32 data (gradients, weights): the MXU's default
# precision would round it to bfloat16. HIGHEST keeps the products exact
# (the one-hot operand is exact in any precision).
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def accumulate_tile(codes, slot, stats, acc_ref, *, n_slots: int,
                    n_bins: int):
    """acc_ref[s, w, b] += sum of stats[s, i] over the tile's examples i with
    slot[i] == w and codes[i] == b. codes/slot: (1, TN) int32 (slot -1 =
    inactive, never matches); stats: (S, TN) f32; acc_ref: (S, W, B)."""
    TN = codes.shape[-1]
    onehot_bin = (jax.lax.broadcasted_iota(jnp.int32, (n_bins, TN), 0)
                  == codes).astype(jnp.float32)                 # (B, TN)
    onehot_slot = (jax.lax.broadcasted_iota(jnp.int32, (n_slots, TN), 0)
                   == slot).astype(jnp.float32)                 # (W, TN)
    for s in range(acc_ref.shape[0]):
        acc_ref[s] += jax.lax.dot_general(
            onehot_slot * stats[s:s + 1, :], onehot_bin,
            (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)                 # (W, B) MXU


def transpose_inputs(codes, stats, slot_of, tile_n: int):
    """(N, F) codes, (N, S) stats, (N,) slots -> lane-major, tile-padded
    (F, 1, Np) int32, (S, Np) f32, (1, Np) int32 (padding rows get slot -1),
    plus the example tile TN (a multiple of 128)."""
    N = codes.shape[0]
    TN = min(_round_up(tile_n, 128), _round_up(max(N, 1), 128))
    pad = _round_up(max(N, 1), TN) - N
    codes_t = jnp.pad(codes.astype(jnp.int32).T, ((0, 0), (0, pad)))
    stats_t = jnp.pad(stats.astype(jnp.float32).T, ((0, 0), (0, pad)))
    slot_t = jnp.pad(slot_of.astype(jnp.int32), (0, pad), constant_values=-1)
    return codes_t[:, None, :], stats_t, slot_t[None, :], TN


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
                     n_nodes: int, n_bins: int = 256, tile_n: int = 512,
                     interpret: bool = False) -> jax.Array:
    """codes: (N, F) integer bin codes; stats: (N, S) f32; node_of: (N,)
    int32 (-1 = inactive). -> (n_nodes, F, B, S) f32."""
    F = codes.shape[1]
    S = stats.shape[1]
    Wn = _round_up(n_nodes, 8)          # sublane-aligned node axis
    codes_t, stats_t, node_t, TN = transpose_inputs(codes, stats, node_of,
                                                    tile_n)
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
    )(codes_t, stats_t, node_t)
    return out.transpose(2, 0, 3, 1)[:n_nodes]            # (nodes, F, B, S)
