"""Compile the main path's three Pallas kernels for a TPU v5e at real sizes.

Interpret mode (the CPU correctness path the other tests use) accepts
kernels the TPU compiler refuses: unaligned block shapes, scatter-adds,
unsigned casts, 1-D refs, VMEM overruns. These tests compile each kernel
ahead of time for a described ``v5e:2x2`` topology — no chip attached — and
check that the program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
Compiles for a described chip cannot be read back from the persistent
cache, so each test turns the cache off around its compile.
"""
import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from forest_helpers import (_gather_sizes, _level_step_program,
                            _make_random_forest)

N_ROWS = 1 << 18           # HIGGS-shaped training set cut to one chip
N_FEATURES = 28


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _compile_for_chip(fn, *args, kernel=None):
    """Compile ``fn`` for the described chip; the Mosaic kernel's operation
    must carry the name ``kernel``, which the benchmark's trace reduction
    matches (``bench/lib/devtrace.py``)."""
    with _no_persistent_cache():
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    if kernel is not None:
        assert f"%{kernel}" in text, f"no operation named {kernel!r}"


@pytest.mark.parametrize("n_stats,kind", [(2, "gh"), (3, "class"),
                                           (4, "gh"), (11, "class")])
@pytest.mark.parametrize("n_slots", [8, 512])
def test_fused_split_kernel_compiles_for_v5e(one_chip, n_slots, n_stats,
                                              kind):
    """The device grower's split kernel at the frontier slot cap
    (grower_device._W_CAP) and the narrowest frontier, for 2 to 11 stats:
    GBT's 4-stat gh layout, and the class layout of 2 and of 10 classes,
    whose entropy score runs ``log_f32`` inside the kernel. At 11 stats and
    512 slots the per-tile MXU passes run over blocks of slots
    (``histogram.slot_block``); one pass over all 16,896 part rows would
    not fit the scoped VMEM."""
    from repro.kernels.histogram.fused import fused_split_pallas

    def split(codes, stats, slot_of):
        return fused_split_pallas(codes, stats, slot_of, n_slots, 256,
                                  kind=kind, l2=0.0, min_examples=5)

    _compile_for_chip(
        split,
        jax.ShapeDtypeStruct((N_ROWS, N_FEATURES), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((N_ROWS, n_stats), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip),
        kernel="fused_split_pallas")


def test_unsampled_level_step_compiles_for_v5e(one_chip):
    """The device grower's whole level step on the fused kernel, every
    feature a candidate (GBT's default): one tree, 32 frontier slots. The
    kernel reads the cached lane-major codes, and routing reads them by a
    select over features and each slot's values by a one-hot over the
    slots, so the program gathers nothing per example."""
    step, args = _level_step_program("pallas", False, N_ROWS, N_FEATURES,
                                     K=1, P=32, sharding=one_chip)
    with _no_persistent_cache():
        text = step.lower(*args).compile().as_text()
    assert "%fused_split_pallas" in text, "no fused split kernel"
    sizes = _gather_sizes(text)
    assert max(sizes, default=0) < N_ROWS, sizes


def test_histogram_kernel_compiles_for_v5e(one_chip):
    """The batched grower's histogram backend on a TPU: 32 frontier nodes."""
    from repro.kernels.histogram.histogram import histogram_pallas

    def hist(codes, stats, node_of):
        return histogram_pallas(codes, stats, node_of, 32, 256)

    _compile_for_chip(
        hist,
        jax.ShapeDtypeStruct((N_ROWS, N_FEATURES), jnp.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((N_ROWS, 4), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip),
        kernel="histogram_pallas")


@pytest.mark.parametrize("n_nodes,n_stats", [(1024, 3), (1024, 4),
                                             (2048, 3)])
def test_histogram_kernel_compiles_for_v5e_at_wide_frontiers(one_chip,
                                                             n_nodes,
                                                             n_stats):
    """The batched grower's histogram backend at the wide frontiers of deep
    trees (the backend pads the frontier to a power of two): the per-tile
    MXU passes run over blocks of nodes, so their VMEM stays ~3 MiB beside
    the double-buffered (S, nodes, B) output block. At 2048 nodes and 4
    stats that block alone takes the 16 MiB of scoped VMEM."""
    from repro.kernels.histogram.histogram import histogram_pallas

    def hist(codes, stats, node_of):
        return histogram_pallas(codes, stats, node_of, n_nodes, 256)

    _compile_for_chip(
        hist,
        jax.ShapeDtypeStruct((N_ROWS, N_FEATURES), jnp.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((N_ROWS, n_stats), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip),
        kernel="histogram_pallas")


def test_distributed_level_step_compiles_for_v5e_2x2(topo):
    """DistributedGBT's level step on a 2x2 (data x model) mesh of the four
    chips: the histogram kernel runs per shard, between the collectives."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import DistGBTConfig, make_level_step

    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))
    # the default "auto" sees this process's CPU backend; ask for the kernel
    cfg = DistGBTConfig(n_bins=64, hist_impl="pallas")
    step = make_level_step(mesh, cfg, 32, N_FEATURES // 2)

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    with _no_persistent_cache():
        text = step.lower(arg((N_ROWS, N_FEATURES), jnp.uint8,
                              P("data", "model")),
                          arg((N_ROWS, 3), jnp.float32, P("data", None)),
                          arg((N_ROWS,), jnp.int32, P("data"))
                          ).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    assert "all-reduce" in text and "all-gather" in text


def test_forest_infer_kernel_compiles_for_v5e(one_chip):
    """The serving kernel on the ``sklearn_import`` shape: 300 depth-12
    trees of ~500 nodes, 10 features, 2 classes, packed by depth, scoring a
    4096-row batch."""
    from repro.core.tree import pack_by_depth
    from repro.kernels.forest_infer.forest_infer import (
        forest_predict_pallas_tiled,
        node_tables,
    )

    forest = _make_random_forest(300, [250], 10, out_dim=2, seed=5,
                                 max_depth=12)
    assert forest.depth == 12
    p = pack_by_depth(forest)
    tbl, leaf = jax.eval_shape(node_tables, p.feature, p.threshold,
                               p.cat_mask, p.left_child, p.leaf_value)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def infer(X, tbl, leaf, block_depth):
        return forest_predict_pallas_tiled(X, tbl, leaf, block_depth,
                                           out_dim=2)

    _compile_for_chip(infer, on_chip(jax.ShapeDtypeStruct((4096, 10),
                                                          jnp.float32)),
                      on_chip(tbl), on_chip(leaf), on_chip(p.block_depth),
                      kernel="forest_predict_pallas_tiled")


# The ranking cell (MSLR-WEB30K cut to 2,400 queries): about 2^18 training
# rows of 136 numerical features, 2,160 training queries of heavy-tailed size.
MSLR_FEATURES = 136
MSLR_QUERIES = 2160


@pytest.mark.parametrize("n_slots", [8, 512])
def test_fused_split_kernel_compiles_for_v5e_at_136_features(one_chip,
                                                             n_slots):
    """The split kernel at MSLR-WEB30K's 136 features, GBT's 4-stat
    layout, at the narrowest frontier and the slot cap."""
    from repro.kernels.histogram.fused import fused_split_pallas

    def split(codes, stats, slot_of):
        return fused_split_pallas(codes, stats, slot_of, n_slots, 256,
                                  kind="gh", l2=0.0, min_examples=5)

    _compile_for_chip(
        split,
        jax.ShapeDtypeStruct((N_ROWS, MSLR_FEATURES), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((N_ROWS, 4), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip),
        kernel="fused_split_pallas")


def test_unsampled_level_step_compiles_for_v5e_at_136_features(one_chip):
    """The whole fused level step at 136 features, every feature a
    candidate: the kernel reads the cached lane-major codes in place, and
    routing gathers nothing per example."""
    step, args = _level_step_program("pallas", False, N_ROWS, MSLR_FEATURES,
                                     K=1, P=32, sharding=one_chip)
    with _no_persistent_cache():
        text = step.lower(*args).compile().as_text()
    assert "%fused_split_pallas" in text, "no fused split kernel"
    sizes = _gather_sizes(text)
    assert max(sizes, default=0) < N_ROWS, sizes


def test_lambda_program_compiles_for_v5e(one_chip):
    """The device lambda pass at the ranking cell's bucket ladder: query
    sizes drawn as the cell draws them (lognormal(4.58, 0.64), clipped to
    [1, 1251]) with one query at the published largest, 1,251 rows."""
    from repro.tasks.ranking import _lambda_program, group_layout

    rng = np.random.default_rng(0)
    sizes = np.clip(np.rint(rng.lognormal(4.58, 0.64, MSLR_QUERIES)), 1,
                    1251).astype(np.int64)
    sizes[0] = 1251
    layout = group_layout(np.repeat(np.arange(MSLR_QUERIES), sizes))
    assert layout.widths[-1] == 2048

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    buckets = tuple((arg(b.index.shape, jnp.int32),
                     arg(b.index.shape, jnp.bool_),
                     arg(b.index.shape, jnp.float32),
                     arg(b.index.shape[:1], jnp.float32))
                    for b in layout.buckets)
    n = layout.n_rows
    with _no_persistent_cache():
        compiled = _lambda_program().lower(
            arg((n,), jnp.float32), buckets, arg((n,), jnp.int32),
            k=5).compile()
    mem = compiled.memory_analysis()
    # the (G, k, m) pair blocks: tens of MB, where the (G, m, m) padded
    # pass needed tens of GB
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes
