import os

# Tests must see the single real CPU device (the 512-device override is
# dryrun.py-local, never global).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def _make_random_forest(n_trees, n_splits_list, n_features, out_dim=1,
                        seed=0, cat_feats=(), chain=False, max_depth=None):
    """Synthetic valid Forest (random leaf-splitting order): n_splits_list
    cycles per tree, so mixed entries build ragged-depth forests; entries
    over 2048 build >4096-node trees. cat_feats get random category masks.
    A 0-splits entry yields a single-leaf stump (random root leaf value).
    ``chain=True`` always splits the DEEPEST open leaf, so a tree with k
    splits has depth exactly k — deterministic-depth forests for the
    depth-bucketing tests (tree.plan_depth_buckets)."""
    from repro.core.tree import empty_forest

    M = 2 * max(n_splits_list) + 1
    f = empty_forest(n_trees, M, out_dim)
    rng = np.random.default_rng(seed)
    maxd = 0
    for t in range(n_trees):
        f.leaf_value[t, 0] = rng.normal(size=out_dim)  # stump fallback
        leaves = [(0, 0)]
        n_nodes = 1
        for _ in range(n_splits_list[t % len(n_splits_list)]):
            open_ = [i for i in range(len(leaves))
                     if max_depth is None or leaves[i][1] < max_depth]
            pick = (max(open_, key=lambda i: leaves[i][1])
                    if chain else open_[int(rng.integers(len(open_)))])
            node, d = leaves.pop(pick)
            j = int(rng.integers(n_features))
            f.feature[t, node] = j
            if j in cat_feats:
                mask = rng.integers(0, 2 ** 32, size=f.cat_mask.shape[-1],
                                    dtype=np.uint64).astype(np.uint32)
                mask[0] |= 1  # never empty: empty mask means numerical
                f.cat_mask[t, node] = mask
            else:
                f.threshold[t, node] = rng.normal()
            f.left_child[t, node] = n_nodes
            f.leaf_value[t, n_nodes] = rng.normal(size=out_dim)
            f.leaf_value[t, n_nodes + 1] = rng.normal(size=out_dim)
            leaves += [(n_nodes, d + 1), (n_nodes + 1, d + 1)]
            n_nodes += 2
            maxd = max(maxd, d + 1)
        f.n_nodes[t] = n_nodes
    f.depth = maxd
    f.feature_names = [f"f{j}" for j in range(n_features)]
    return f


@pytest.fixture(scope="session")
def random_forest_factory():
    return _make_random_forest


# ----------------------------- forest zoo (traversal-strategy differentials)

@pytest.fixture(scope="session")
def depth_skewed_forest():
    """Mixed depth-2 / depth-12 chains: the shape the depth-bucketed engine
    exists for — shallow trees must stop early, deep trees must not."""
    return _make_random_forest(24, [2, 12], 6, seed=21, chain=True)


@pytest.fixture(scope="session")
def stump_forest():
    """Single-node trees only (boosted-stump shape): depth 0, the root IS
    the leaf. Exercises the scan's sentinel self-loop and leaf_path's
    empty-path scoring."""
    return _make_random_forest(17, [0], 4, seed=22)


@pytest.fixture(scope="session")
def all_categorical_forest():
    """Every split is a category-mask bit test (no numerical thresholds):
    the cat-code cast path with nothing to hide behind."""
    return _make_random_forest(12, [1, 3, 5], 4, seed=23,
                               cat_feats=(0, 1, 2, 3))


@pytest.fixture(scope="session")
def tiny_adult():
    """A small mixed-semantics training set shared by model-layer tests."""
    from repro.data.tabular import adult_like
    return adult_like(400, seed=3)


# ------------------------------- device grower's level step, lowered alone

def _level_step_program(impl, sample, N, F, K=1, P=32, sharding=None):
    """The device grower's jitted level step for a numerical GBT table of
    ``N`` x ``F`` codes, with ``K`` trees and ``P`` frontier slots, and the
    shapes of its arguments (placed on ``sharding`` when given). ``sample``
    keeps half the features per node."""
    import jax
    import jax.numpy as jnp

    from repro.core.grower_device import _level_step, _StepConfig
    from repro.core.sampling import sample_size
    from repro.core.tree import MASK_WORDS
    from repro.kernels.histogram.histogram import TILE_N, _example_tiling

    S, M = 4, 127
    cfg = _StepConfig(kind="gh", l2=0.0, min_examples=5, min_gain=0.0,
                      cat_mode="none", sample=sample, sampling_key=7,
                      kf=sample_size(0.5, F) if sample else F, F=F, S=S,
                      M=M, max_nodes=M, impl=impl)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    codes_t = None
    if impl != "jnp" and not sample:
        codes_t = arg((F, 1, _example_tiling(N, TILE_N)[1]), jnp.int32)
    i32, f32 = jnp.int32, jnp.float32
    args = (arg((N, F), i32), codes_t, arg((F,), i32), arg((F,), jnp.bool_),
            arg((K, N, S), f32), arg((K,), i32), arg((K, N), i32),
            arg((K, P), i32), arg((K, M), i32), arg((K, M), i32),
            arg((K, M, MASK_WORDS), jnp.uint32), arg((K, M), i32),
            arg((K, M), f32), arg((K, M, S), f32), arg((K,), i32),
            arg((K, N), i32), arg((K,), i32))
    return _level_step(cfg), args


def _gather_sizes(hlo_text):
    """Element count of each ``gather`` result in an HLO module's text."""
    import re
    sizes = []
    for m in re.finditer(r"= \w+\[([\d,]*)\][^=\n]* gather\(", hlo_text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        sizes.append(int(np.prod(dims)) if dims else 1)
    return sizes
