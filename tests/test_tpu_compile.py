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

from conftest import _make_random_forest

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


def _compile_for_chip(fn, *args):
    with _no_persistent_cache():
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


@pytest.mark.parametrize("n_stats,kind", [(2, "gh"), (3, "class"),
                                           (4, "gh")])
@pytest.mark.parametrize("n_slots", [8, 512])
def test_fused_split_kernel_compiles_for_v5e(one_chip, n_slots, n_stats,
                                              kind):
    """The device grower's split kernel at the frontier slot cap
    (grower_device._W_CAP) and the narrowest frontier, for 2 to 4 stats:
    GBT's 4-stat gh layout, and the binary class layout, whose entropy
    score runs ``log_f32`` inside the kernel."""
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
        jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip))


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
        jax.ShapeDtypeStruct((N_ROWS,), jnp.int32, sharding=one_chip))


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
                      on_chip(tbl), on_chip(leaf), on_chip(p.block_depth))
