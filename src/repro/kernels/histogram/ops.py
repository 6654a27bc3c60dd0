"""jit'd dispatch wrapper for the histogram op.

impl:
  * "pallas"    — compiled Pallas kernel (TPU target)
  * "interpret" — Pallas kernel body interpreted on CPU (correctness path)
  * "ref"       — pure-jnp oracle (segment_sum)
  * "auto"/None — pallas on TPU, ref elsewhere

Inputs may be numpy or jax arrays — the training path
(``repro.core.hist_backend.PallasHistogramBackend``) feeds host numpy arrays
straight in. ``n_nodes`` is a static shape argument: callers that invoke this
in a loop over growing frontiers should pad it (the training backend pads to
the next power of two) to bound jit recompilation.
"""
from __future__ import annotations

import jax

from repro.kernels.histogram.histogram import histogram_pallas
from repro.kernels.histogram.ref import histogram_ref


def resolve_impl(impl: str | None) -> str:
    """The implementation an ``impl`` request runs: "auto"/None is the
    compiled kernel on a TPU and the jnp oracle elsewhere."""
    if impl is None or impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def histogram(codes, stats, node_of, n_nodes: int, n_bins: int = 256,
              impl: str | None = None):
    impl = resolve_impl(impl)
    if impl == "ref":
        return histogram_ref(codes, stats, node_of, n_nodes, n_bins)
    if impl == "pallas":
        return histogram_pallas(codes, stats, node_of, n_nodes, n_bins)
    if impl == "interpret":
        return histogram_pallas(codes, stats, node_of, n_nodes, n_bins,
                                interpret=True)
    raise ValueError(f"unknown impl {impl!r}")


def fused_best_split(codes, stats, slot_of, n_slots: int, n_bins: int = 256,
                     *, kind: str = "gh", l2: float = 0.0,
                     min_examples: int = 5, impl: str | None = None):
    """Fused histogram + ordered-bin gain scan + per-slot argmax (DESIGN.md
    §6.1). codes: (N, kf) uint8 numerical bin codes; -> per-slot
    (gain, feature-column, split_bin), the tiny ``(nodes, 3)`` output that
    replaces the full ``(nodes, F, B, S)`` histogram on the training path."""
    from repro.kernels.histogram.fused import fused_split_pallas
    from repro.kernels.histogram.ref import fused_split_ref

    impl = resolve_impl(impl)
    if impl == "ref":
        return fused_split_ref(codes, stats, slot_of, n_slots, n_bins,
                               kind=kind, l2=l2, min_examples=min_examples)
    if impl in ("pallas", "interpret"):
        return fused_split_pallas(codes, stats, slot_of, n_slots, n_bins,
                                  kind=kind, l2=l2, min_examples=min_examples,
                                  interpret=(impl == "interpret"))
    raise ValueError(f"unknown impl {impl!r}")
