"""Dispatch + per-forest device caches for the inference kernels.

The serving contract (DESIGN.md §5.1) is that a compiled forest is uploaded
to the device ONCE: ``forest_predict`` keeps a small id-keyed cache mapping a
live Forest to its depth-packed device layout (tiled kernel, §5.2–§5.3) and
its depth-bucketed runners (§10), so repeat predictions do zero
host-to-device transfers and zero re-packing. Entries are validated against
a weakref (id reuse after GC cannot alias) and evicted LRU.

impls: "pallas" (tiled, compiled — needs a TPU) and "interpret" (the same
kernel in interpret mode — the CPU correctness path). With no impl given,
a TPU host runs "pallas" and any other host "interpret".
"""
from __future__ import annotations

import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.forest_infer.forest_infer import (
    forest_predict_pallas_tiled,
    node_tables,
)

_CACHE: "OrderedDict[int, tuple]" = OrderedDict()
_CACHE_CAP = 8


def _forest_cache(forest) -> dict:
    """Per-forest payload dict, id-keyed + weakref-validated, LRU-capped.
    A weakref finalizer evicts the entry the moment the forest is GC'd, so
    a retired model's device buffers free immediately instead of lingering
    until LRU pressure pushes them out."""
    key = id(forest)
    ent = _CACHE.get(key)
    if ent is not None and ent[0]() is forest:
        _CACHE.move_to_end(key)
        return ent[1]
    payload: dict = {}

    def _evict(_ref, key=key):
        _CACHE.pop(key, None)

    _CACHE[key] = (weakref.ref(forest, _evict), payload)
    _CACHE.move_to_end(key)
    while len(_CACHE) > _CACHE_CAP:
        _CACHE.popitem(last=False)
    return payload


def device_packed(forest) -> tuple:
    """Depth-packed device layout (pack_by_depth output) as the tiled
    kernel's node tables, built/uploaded once. Returns (node_table,
    leaf_table, block_depth, inv_order, out_dim), the first four on
    device."""
    c = _forest_cache(forest)
    if "packed" not in c:
        from repro.core.tree import pack_by_depth
        p = pack_by_depth(forest)
        tbl, leaf = node_tables(p.feature, p.threshold, p.cat_mask,
                                p.left_child, p.leaf_value)
        c["packed"] = (tbl, leaf, jnp.asarray(p.block_depth),
                       jnp.asarray(p.inv_order), p.out_dim)
    return c["packed"]


def bucketed_runner(forest, strategy: str | None = None):
    """Compiled depth-bucketed runner (DESIGN.md §10), built/uploaded once
    per (forest, strategy). ``strategy`` None lets the per-bucket cost model
    choose ("leaf_path" only where the matmul is ~free — an MXU backend);
    "scan"/"leaf_path" force one strategy for every bucket (benchmarks,
    differential tests)."""
    c = _forest_cache(forest)
    key = f"bucketed:{strategy or 'auto'}"
    if key not in c:
        from repro.core.tree import pack_depth_buckets
        from repro.kernels.forest_infer.bucketed import build_bucketed_runner
        bf = pack_depth_buckets(forest, strategy=strategy,
                                matmul_cheap=(jax.default_backend() == "tpu"))
        c[key] = build_bucketed_runner(bf)
    return c[key]


def forest_predict_bucketed(forest, X: np.ndarray,
                            strategy: str | None = None) -> np.ndarray:
    """Depth-bucketed prediction: (N, F) raw-value matrix ->
    (N, T, out_dim) numpy, original tree order. Same EngineFailure contract
    as ``forest_predict``."""
    from repro.core.api import EngineFailure
    try:
        return bucketed_runner(forest, strategy)(X)
    except (EngineFailure, KeyboardInterrupt):
        raise
    except Exception as e:
        name = "leaf_path" if strategy == "leaf_path" else "bucketed"
        raise EngineFailure(
            f"forest_infer impl {name!r} failed on a "
            f"({np.shape(X)[0] if np.ndim(X) else '?'}, ...) batch: "
            f"{type(e).__name__}: {e}", engine=name) from e


def forest_predict(forest, X: np.ndarray, impl: str | None = None):
    """forest: repro.core.tree.Forest; X: (N, F) raw-value matrix.
    -> (N, T, out_dim) per-tree outputs (original tree order).

    Kernel/dispatch errors surface as a typed ``EngineFailure`` naming the
    impl (DESIGN.md §9.1): a serving front-end must be able to tell "the
    pallas engine died on this batch" apart from a schema or caller error
    without parsing XLA tracebacks. Caller errors (unknown impl) stay
    ``ValueError``.
    """
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "interpret"
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    from repro.core.api import EngineFailure
    try:
        Xd = jnp.asarray(X, jnp.float32)
        tbl, leaf, bd, inv, out_dim = device_packed(forest)
        out = forest_predict_pallas_tiled(Xd, tbl, leaf, bd, out_dim=out_dim,
                                          interpret=(impl == "interpret"))
        return jnp.take(out, inv, axis=1)
    except (EngineFailure, KeyboardInterrupt):
        raise
    except Exception as e:
        raise EngineFailure(
            f"forest_infer impl {impl!r} failed on a "
            f"({np.shape(X)[0] if np.ndim(X) else '?'}, ...) batch: "
            f"{type(e).__name__}: {e}", engine=impl) from e
