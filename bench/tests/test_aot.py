"""The cells' kernels compile for a described TPU v5e at the cells' real
shapes, with no chip attached: the fused split kernel of
``higgs_gbt.train`` at its training rows and each frontier width a depth-6
tree pads to, and the forest kernel at ``higgs_gbt.score``'s 500,000-row
calls and at every padding bucket ``adult_gbt.online`` dispatches.

The topology is described inside a fixture, never at import: only one
process may load the TPU library. Compiles for a described chip cannot be
read back from the persistent cache, so the cache is off around each."""
import contextlib
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import program
import tabular

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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


def _compile(fn, *args):
    with _no_persistent_cache():
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


@pytest.mark.parametrize("n_slots", [8, 16, 32])
def test_fused_split_kernel_at_higgs_train_shape(one_chip, n_slots):
    from repro.kernels.histogram.fused import fused_split_pallas
    cfg = _config("higgs_gbt")
    n = int(cfg["train_rows"])
    n -= int(round(n * cfg["learner"]["hparams"]["validation_ratio"]))
    f = len(cfg["dataset"]["columns"])

    def split(codes, stats, slot_of):
        return fused_split_pallas(codes, stats, slot_of, n_slots, 256,
                                  kind="gh", l2=0.0, min_examples=5)

    _compile(split,
             jax.ShapeDtypeStruct((n, f), jnp.int32, sharding=one_chip),
             jax.ShapeDtypeStruct((n, 4), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip))


def _forest_tables(cfg):
    from repro.core.tree import pack_by_depth
    from repro.kernels.forest_infer.forest_infer import node_tables
    sample = tabular.make_table(cfg["dataset"], 4000, 1)
    forest = tabular.build_forest(cfg["dataset"], cfg["serving_forest"], 2,
                                  sample)
    p = pack_by_depth(program.servable_model(cfg, forest).forest)
    tbl, leaf = jax.eval_shape(node_tables, p.feature, p.threshold,
                               p.cat_mask, p.left_child, p.leaf_value)
    return tbl, leaf, p.block_depth


def _infer_compiles(one_chip, cfg, rows):
    from repro.kernels.forest_infer.forest_infer import (
        forest_predict_pallas_tiled)
    tbl, leaf, block_depth = _forest_tables(cfg)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def infer(X, tbl, leaf, block_depth):
        return forest_predict_pallas_tiled(X, tbl, leaf, block_depth,
                                           out_dim=1)

    _compile(infer, on_chip(jax.ShapeDtypeStruct(
        (rows, len(cfg["dataset"]["columns"])), jnp.float32)),
        on_chip(tbl), on_chip(leaf), on_chip(block_depth))


def test_forest_kernel_at_higgs_score_shape(one_chip):
    _infer_compiles(one_chip, _config("higgs_gbt"), 500000)


@pytest.mark.parametrize("bucket", [32, 64, 128, 256, 512, 1024])
def test_forest_kernel_at_adult_online_buckets(one_chip, bucket):
    _infer_compiles(one_chip, _config("adult_gbt"), bucket)
