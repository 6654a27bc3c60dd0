"""Pallas TPU kernel: branch-free forest inference with VMEM-resident trees.

QuickScorer's insight (eliminate branch misprediction + random memory access)
restated for the TPU (DESIGN.md §2.2): all examples traverse all trees in
lockstep for `depth` rounds; per round, the per-lane "pointer chase" becomes
one one-hot matmul against the node table, which the MXU executes at full
tilt — no gathers, no branches. Examples run along the 128-wide lane axis:

    cols   = table (C, M) @ onehot(node) (M, TN)    every node field at once
    x      = sum(X^T * onehot(feature), axis=0)     row-select on the VPU
    go     = x >= threshold (or category bit test)
    node   = left_child + go

``forest_predict_pallas_tiled`` is the serving kernel (DESIGN.md §5.2). Grid
is (example_tile, tree_block) over a depth-packed forest
(``core.tree.pack_by_depth``): each step holds a *block* of trees and the
per-round one-hot is tiled over node chunks of ``node_tile``, so arbitrarily
large node tables compile — the per-step VMEM high-water is (node_tile, TN)
plus the block's (trimmed) node tables, independent of total forest size.
The traversal loop is a ``fori_loop`` bounded by the *block's* max depth
(§5.3), read from SMEM: ragged forests pay max-depth-per-block, not global
max depth. Categorical mask words travel as exact 16-bit halves (float32
carries < 2^24 exactly) and the bit test runs on int32 — the TPU has no
unsigned vector casts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_WORDS = 8

# Node-table rows: the mask words' low and high 16-bit halves first, so both
# 8-row groups are sublane-aligned, then the scalar fields, padded to 24.
_LO, _HI = 0, MASK_WORDS
_FEAT, _THR, _LEFT, _IS_CAT = 2 * MASK_WORDS + 0, 2 * MASK_WORDS + 1, \
    2 * MASK_WORDS + 2, 2 * MASK_WORDS + 3
NODE_ROWS = 24

# The gather matmuls carry INTEGER payloads (feature ids, child indices,
# 16-bit mask halves) and f32 thresholds: the MXU's default precision would
# round them to bfloat16 (exact only to 256) and silently corrupt traversal —
# pin the highest precision so f32 operands survive intact.
_dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _infer_tiled_kernel(depth_ref, x_ref, tbl_ref, leaf_ref, out_ref, *,
                        node_tile: int):
    X = x_ref[...]                                    # (F, TN)
    F, TN = X.shape
    TB, _, M = tbl_ref.shape
    n_chunks = M // node_tile
    d = depth_ref[pl.program_id(1)]                   # this block's max depth
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (F, TN), 0)
    m_iota = jax.lax.broadcasted_iota(jnp.int32, (node_tile, TN), 0)
    w_iota = jax.lax.broadcasted_iota(jnp.int32, (MASK_WORDS, TN), 0)

    def chunk_oh(node, k):
        # one-hot over node chunk k — zero for nodes outside the chunk, so
        # summing chunk matmuls reconstructs the full-table gather
        return (m_iota + k * node_tile == node).astype(jnp.float32)

    def gather(ref, j, node):
        """ref[j] (R, M) gathered at each example's node -> (R, TN)."""
        acc = _dot(ref[j, :, 0:node_tile], chunk_oh(node, 0))
        for k in range(1, n_chunks):
            acc = acc + _dot(ref[j, :, k * node_tile:(k + 1) * node_tile],
                             chunk_oh(node, k))
        return acc

    for j in range(TB):
        def round_body(_, node, j=j):
            cols = gather(tbl_ref, j, node)           # (NODE_ROWS, TN)
            f = cols[_FEAT:_FEAT + 1].astype(jnp.int32)
            left = cols[_LEFT:_LEFT + 1].astype(jnp.int32)
            x = jnp.sum(jnp.where(f_iota == jnp.maximum(f, 0), X, 0.0),
                        axis=0, keepdims=True)        # (1, TN)
            go_num = (x >= cols[_THR:_THR + 1]).astype(jnp.int32)
            # categorical bit test: word = code >> 5, half = bit 4 of code
            code = jnp.clip(x, 0.0, MASK_WORDS * 32 - 1).astype(jnp.int32)
            halves = jnp.where(((code >> 4) & 1) == 1,
                               cols[_HI:_HI + MASK_WORDS],
                               cols[_LO:_LO + MASK_WORDS])  # (W, TN)
            half = jnp.sum(jnp.where(w_iota == (code >> 5), halves, 0.0),
                           axis=0, keepdims=True).astype(jnp.int32)
            go_cat = (half >> (code & 15)) & 1
            go = jnp.where(cols[_IS_CAT:_IS_CAT + 1] > 0, go_cat, go_num)
            return jnp.where(left >= 0, left + go, node)

        node = jax.lax.fori_loop(0, d, round_body,
                                 jnp.zeros((1, TN), jnp.int32))
        out_ref[j] = gather(leaf_ref, j, node)        # (O_pad, TN)


@jax.jit
def node_tables(feature, threshold, cat_mask, left_child, leaf_value):
    """Packed SoA (B, TB, M[, ...]) -> the kernel's f32 node tables:
    (B, TB, NODE_ROWS, M) fields and (B, TB, O_pad, M) leaf values, node
    axis on lanes. Runs as XLA outside the kernel."""
    B, TB, M = feature.shape
    O = leaf_value.shape[-1]
    lo = (cat_mask & jnp.uint32(0xFFFF)).astype(jnp.float32)
    hi = (cat_mask >> jnp.uint32(16)).astype(jnp.float32)
    scalars = jnp.stack([
        feature.astype(jnp.float32), threshold.astype(jnp.float32),
        left_child.astype(jnp.float32),
        (cat_mask != 0).any(axis=-1).astype(jnp.float32)], axis=2)
    pad = jnp.zeros((B, TB, NODE_ROWS - 2 * MASK_WORDS - 4, M), jnp.float32)
    tbl = jnp.concatenate([lo.transpose(0, 1, 3, 2), hi.transpose(0, 1, 3, 2),
                           scalars, pad], axis=2)
    leaf = jnp.pad(leaf_value.astype(jnp.float32).transpose(0, 1, 3, 2),
                   ((0, 0), (0, 0), (0, _round_up(O, 8) - O), (0, 0)))
    return tbl, leaf


@functools.partial(jax.jit, static_argnames=("out_dim", "node_tile",
                                             "tile_n", "interpret"))
def forest_predict_pallas_tiled(X, tbl, leaf, block_depth, *, out_dim: int,
                                node_tile: int = 128, tile_n: int = 256,
                                interpret: bool = False):
    """Tree-tiled lockstep traversal over a depth-packed forest (§5.2).

    X: (N, F) f32; ``tbl``/``leaf``: the forest's ``node_tables`` (the
    serving cache keeps them on device, built once per forest); block_depth
    (B, 1) i32; out_dim: the leaf values' true width. The node capacity M
    must be a multiple of ``node_tile`` (``pack_by_depth`` guarantees it).
    -> (N, B*TB, out_dim) in *packed* tree order; callers restore the
    original order with PackedForest.inv_order.
    """
    N, F = X.shape
    B, TB, _, M = tbl.shape
    Op = leaf.shape[2]
    mt = min(node_tile, M)
    if M % mt:
        raise ValueError(f"node capacity {M} is not a multiple of the node "
                         f"tile {mt}; pack the forest with pack_by_depth")
    TN = min(_round_up(tile_n, 128), _round_up(max(N, 1), 128))
    Np = _round_up(max(N, 1), TN)
    # examples on lanes; feature rows padded to whole sublane groups
    Xt = jnp.pad(X.astype(jnp.float32).T,
                 ((0, _round_up(F, 8) - F), (0, Np - N)))
    out = pl.pallas_call(
        functools.partial(_infer_tiled_kernel, node_tile=mt),
        grid=(Np // TN, B),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # (B,) block depths
            pl.BlockSpec((Xt.shape[0], TN), lambda i, b: (0, i)),
            pl.BlockSpec((None, TB, NODE_ROWS, M), lambda i, b: (b, 0, 0, 0)),
            pl.BlockSpec((None, TB, Op, M), lambda i, b: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((TB, Op, TN), lambda i, b: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B * TB, Op, Np), jnp.float32),
        interpret=interpret,
    )(block_depth.reshape(B).astype(jnp.int32), Xt, tbl, leaf)
    return out[:, :out_dim, :N].transpose(2, 0, 1)
