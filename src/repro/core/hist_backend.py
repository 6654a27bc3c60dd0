"""Pluggable histogram-building backends (DESIGN.md §4).

Training spends most of its time accumulating per-node gradient histograms
(paper §3.8). Two implementations of the same contract:

  * "numpy"  — host path: one flattened ``np.bincount`` over
               (node, feature, bin, stat) buckets. Bit-compatible with the
               historical per-stat loop (identical per-bucket accumulation
               order), but a single pass with no per-stat broadcast copies.
  * "pallas" — device path: the one-hot-MXU kernel from
               ``repro/kernels/histogram`` (DESIGN.md §2.1), compiled for the
               TPU. "pallas_interpret" runs it in interpret mode (CPU
               correctness path, slow, explicit opt-in only).

``resolve_backend("auto")`` mirrors the lossy-compilation engine choice in
``engines.py``: hardware-aware, pallas only where it is the fast path.

Backends return float64 arrays; callers cast to float32 for the gain scan.
Backends that genuinely ACCUMULATE in float64 advertise
``exact_subtraction = True`` — only those may serve the parent-minus-sibling
subtraction trick (grower.py, DESIGN.md §4); the pallas kernel accumulates
in float32 on the MXU and returns upcast values, so the growers build both
children directly under it.
"""
from __future__ import annotations

import numpy as np

from repro.core.api import YdfError


class HistogramBackend:
    """Contract: ``build(codes, stats, node_of, n_nodes, max_bins)``.

    codes: (N, F) uint8; stats: (N, S) float; node_of: (N,) int32 in
    [-1, n_nodes) (-1 = inactive example). Returns (n_nodes, F, B, S) float64
    with ``out[n, f, b, s] = sum(stats[i, s] for active i in node n with
    codes[i, f] == b)``.
    """

    name = "?"
    # True when build() accumulates in float64, making parent-minus-sibling
    # subtraction (grower.py) safe: the f64 residual vanishes under the f32
    # cast of the gain scan. Backends that accumulate in float32 (pallas MXU)
    # must not be used for subtraction — residuals of f32-rounding scale can
    # leave derived buckets (e.g. hessians) slightly negative.
    exact_subtraction = False

    def build(self, codes: np.ndarray, stats: np.ndarray, node_of: np.ndarray,
              n_nodes: int, max_bins: int = 256) -> np.ndarray:
        raise NotImplementedError


class NumpyHistogramBackend(HistogramBackend):
    """Feature-major flattened bincount: one (examples,)-length scatter per
    (feature, unique stat) pair. Weight vectors are plain column views — no
    (N, F) broadcast copies — and each scatter touches a single
    (n_nodes * B) strip, so the working set stays cache-resident. Per-bucket
    accumulation order remains example-ascending, which keeps results
    bit-identical to the historical example-major per-stat pass."""

    name = "numpy"
    exact_subtraction = True

    def build(self, codes, stats, node_of, n_nodes, max_bins=256):
        F = codes.shape[1]
        S = stats.shape[1]
        B = max_bins
        act = node_of >= 0
        if not act.all():
            codes, stats, node_of = codes[act], stats[act], node_of[act]
        stats = np.ascontiguousarray(stats, np.float64)
        node = node_of.astype(np.int64) * B
        # Duplicate stat columns (e.g. GBT's hessian-gain-off layout repeats
        # the weight column) are accumulated once and copied to each alias.
        uniq, inv = _unique_stat_columns(stats)
        out = np.empty((n_nodes, F, B, S), np.float64)
        for f in range(F):
            flat = node + codes[:, f]
            strips = [np.bincount(flat, weights=stats[:, s],
                                  minlength=n_nodes * B).reshape(n_nodes, B)
                      for s in uniq]
            for s in range(S):
                out[:, f, :, s] = strips[inv[s]]
        return out


class SimpleHistogramBackend(HistogramBackend):
    """The historical example-major formulation: one bincount per stat over an
    (N, F)-shaped flat index, with per-stat broadcast weight copies. Kept as
    the readable ground-truth module (paper §2.3) — the oracle growth engine
    uses it, and the optimized backends are tested against it bit-for-bit."""

    name = "simple"
    exact_subtraction = True

    def build(self, codes, stats, node_of, n_nodes, max_bins=256):
        F = codes.shape[1]
        S = stats.shape[1]
        B = max_bins
        act = node_of >= 0
        codes_a = codes[act]
        stats_a = stats[act]
        node_a = node_of[act].astype(np.int64)
        out = np.zeros((n_nodes * F * B, S), np.float64)
        base = node_a[:, None] * (F * B) + np.arange(F)[None, :] * B  # (n, F)
        flat = (base + codes_a).ravel()
        for s in range(S):
            w = np.broadcast_to(stats_a[:, s:s + 1], (len(node_a), F)).ravel()
            out[:, s] = np.bincount(flat, weights=w, minlength=n_nodes * F * B)
        return out.reshape(n_nodes, F, B, S)


def _unique_stat_columns(stats: np.ndarray) -> tuple[list[int], np.ndarray]:
    """Indices of the first occurrence of each distinct stat column, plus the
    inverse map expanding unique columns back to the full layout."""
    S = stats.shape[1]
    uniq: list[int] = []
    inv = np.zeros(S, np.int64)
    for s in range(S):
        for k, u in enumerate(uniq):
            if np.array_equal(stats[:, s], stats[:, u]):
                inv[s] = k
                break
        else:
            inv[s] = len(uniq)
            uniq.append(s)
    return uniq, inv


class PallasHistogramBackend(HistogramBackend):
    """One-hot-MXU kernel (DESIGN.md §2.1) behind the host-side contract.

    ``n_nodes`` is padded to the next power of two so the jit cache sees a
    bounded set of shapes as the frontier grows (at most log2(max_nodes)
    compilations per feature count).
    """

    name = "pallas"

    def __init__(self, interpret: bool = False):
        # interpret mode is an explicit opt-in ("pallas_interpret"), never a
        # silent stand-in for the compiled kernel
        self.interpret = interpret

    def build(self, codes, stats, node_of, n_nodes, max_bins=256):
        from repro.kernels.histogram.ops import histogram
        n_pad = max(8, 1 << (int(n_nodes) - 1).bit_length())
        impl = "interpret" if self.interpret else "pallas"
        out = histogram(np.ascontiguousarray(codes),
                        np.ascontiguousarray(stats, np.float32),
                        np.ascontiguousarray(node_of, np.int32),
                        n_pad, max_bins, impl=impl)
        return np.asarray(out)[:n_nodes].astype(np.float64)


_CACHE: dict[str, HistogramBackend] = {}
_AUTO_NAME: str | None = None


def _auto_backend_name() -> str:
    """Hardware-aware default, computed once. Importing jax costs seconds, so
    a host that never loaded jax (and has no TPU runtime installed) resolves
    to numpy without paying for it."""
    global _AUTO_NAME
    if _AUTO_NAME is None:
        import importlib.util
        import sys
        if "jax" in sys.modules:
            _AUTO_NAME = ("pallas" if sys.modules["jax"].default_backend()
                          == "tpu" else "numpy")
        elif importlib.util.find_spec("libtpu") is not None:
            import jax
            _AUTO_NAME = ("pallas" if jax.default_backend() == "tpu"
                          else "numpy")
        else:
            _AUTO_NAME = "numpy"
    return _AUTO_NAME


def resolve_backend(name: str | HistogramBackend | None = "auto"
                    ) -> HistogramBackend:
    """Map a ``histogram_backend`` hparam value to a backend instance.

    "auto" is hardware-aware (mirrors engines.compile_model): the pallas
    kernel is only the fast path on TPU; on CPU hosts it would run in
    interpret mode, so numpy wins. Forcing "pallas" without a supporting
    device is an error — interpret mode is orders of magnitude slower than
    numpy and must never end up on the training hot path silently; tests
    and kernel debugging opt in explicitly with "pallas_interpret".
    """
    if isinstance(name, HistogramBackend):
        return name
    if name is None:
        name = "auto"
    if name == "auto":
        name = _auto_backend_name()
    if name == "pallas":
        import jax
        if jax.default_backend() != "tpu":
            raise YdfError(
                "histogram_backend='pallas' requires a TPU device; this host "
                f"has jax backend {jax.default_backend()!r}, where the kernel "
                "would run in interpret mode (orders of magnitude slower "
                "than numpy). Solutions: (1) use histogram_backend='auto' "
                "(hardware-aware), (2) use 'numpy', (3) opt into interpret "
                "mode explicitly with 'pallas_interpret' (tests/debugging "
                "only).")
    if name not in ("numpy", "pallas", "pallas_interpret", "simple"):
        raise YdfError(
            f"Unknown histogram_backend {name!r}. "
            "Expected one of: 'auto', 'numpy', 'pallas', 'pallas_interpret', "
            "'simple'.")
    if name not in _CACHE:
        _CACHE[name] = {
            "numpy": NumpyHistogramBackend,
            "pallas": PallasHistogramBackend,
            "pallas_interpret": lambda: PallasHistogramBackend(interpret=True),
            "simple": SimpleHistogramBackend}[name]()
    return _CACHE[name]
