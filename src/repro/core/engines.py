"""Inference engines and the compiled serving stack (paper §3.7;
DESIGN.md §5, §10): a Model *compiles* — possibly lossily — to the fastest
engine compatible with its structure and the hardware.

Engines (ordered by preference):
  * "pallas"     — tree-tiled lockstep traversal over the depth-packed
                   layout (repro/kernels/forest_infer, §5.2–§5.3); requires
                   axis-aligned numerical/categorical conditions. Node count
                   is unbounded (the old 4096-node VMEM ceiling is gone —
                   large forests tile instead of raising). On CPU runs in
                   interpret mode (correctness path); TPU is the target.
  * "bucketed"   — depth-bucketed XLA traversal (§10): trees grouped by
                   actual depth, each bucket pays its own round count
                   (early exit for shallow trees) and picks its scoring
                   strategy per the §10.3 cost model. The CPU fast path.
  * "leaf_path"  — the bucketed engine with leaf-path flattening FORCED on
                   every bucket (predicate-matrix matmul scoring, §10.2);
                   only offered when every tree's path table fits the
                   LEAF_PATH_BUDGET. Explicit-request strategy, not a
                   default: on CPU the scan beats it at every depth.
  * "vectorized" — specialized numpy lockstep traversal
                   (tree.compile_predict_raw, §5.1). No jit trace, so it is
                   also the right engine for small forests / tiny batches.
  * "naive"      — Algorithm 1 of the paper: per-example while-loop. Readable
                   oracle; always compatible.

``compile_model(model)`` picks the best compatible engine —
hardware-aware: on CPU hosts ``select_cpu_engine`` weighs the bucketed
engine's one-off jit trace against forest size. Requesting an incompatible
engine by name raises with the reason (lossy-compilation made explicit,
§2.1).

``compile_predictor(model)`` builds the full serving artifact (§5.1): a
``CompiledPredictor`` bundles the engine closure with pre-compiled raw→code
encode tables (dataspec.BatchEncoder) and the model's output head, so a
request batch pays exactly one vectorized encode + one engine call + one
aggregation — no dataspec walk, no host round-trips, no re-upload.
``Model.predict`` caches one and reuses it across calls. The artifact
pickles: engines serialize as (name, forest) and recompile on load, so a
round-tripped predictor keeps its engine choice without shipping closures.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import numpy as np

from repro.obs import clock, trace
from repro.core.api import EngineFailure, YdfError
from repro.core.dataspec import BatchEncoder
from repro.core.tree import (
    Forest,
    LEAF_PATH_BUDGET,
    compile_predict_raw,
    leaf_path_sizes,
    predict_naive,
    tree_depths,
)

# Minimum n_trees * depth for the bucketed engine to win by default on CPU:
# below this, its one-off jit trace (~0.1 s per batch shape) dwarfs any
# steady-state gain over the numpy engine, which compiles in microseconds.
BUCKETED_MIN_WORK = 256


@dataclass
class Engine:
    name: str
    per_tree: Callable[[np.ndarray], np.ndarray]  # X (N,F) -> (N,T,out_dim)
    note: str = ""
    # the source forest rides along so the engine can pickle as (name,
    # forest) and rebuild its closure — device buffers and jit caches do not
    # serialize (CompiledPredictor round-trip, DESIGN.md §10.4)
    forest: Forest | None = None

    def __getstate__(self):
        return {"name": self.name, "note": self.note, "forest": self.forest}

    def __setstate__(self, state):
        rebuilt = _compile_forest_engine(state["forest"], state["name"])
        self.__dict__.update(rebuilt.__dict__)


def _compat_pallas(forest: Forest) -> str | None:
    if forest.has_oblique():
        return "oblique conditions are not supported by the pallas engine"
    return None


def _compat_bucketed(forest: Forest) -> str | None:
    if forest.has_oblique():
        return "oblique conditions are not supported by the bucketed engine"
    return None


def _compat_leaf_path(forest: Forest) -> str | None:
    if forest.has_oblique():
        return "oblique conditions are not supported by the leaf_path engine"
    n_internal, n_leaves = leaf_path_sizes(forest)
    if n_internal * n_leaves > LEAF_PATH_BUDGET:
        return (f"leaf-path flattening needs a {n_internal}x{n_leaves} "
                f"predicate matrix per tree (> {LEAF_PATH_BUDGET} budget); "
                f"the transform targets shallow trees")
    return None


def available_engines(forest: Forest) -> list[str]:
    out = []
    if _compat_pallas(forest) is None:
        out.append("pallas")
    if _compat_bucketed(forest) is None:
        out.append("bucketed")
    if _compat_leaf_path(forest) is None:
        out.append("leaf_path")
    out += ["vectorized", "naive"]
    return out


def select_cpu_engine(forest: Forest) -> str:
    """Size-aware CPU default between the two compiled traversals.

    Steady-state the bucketed XLA engine wins (~3x over the numpy engine on
    the §B.4 forests, ~2x over sklearn's C traversal), but it pays a jit
    trace per batch shape. ``n_trees * depth`` below BUCKETED_MIN_WORK means
    the forest is so small that the numpy engine is already in the tens of
    microseconds per batch — take it and skip the trace."""
    if _compat_bucketed(forest) is not None:
        return "vectorized"
    if forest.n_trees == 0:
        return "vectorized"
    depth = int(tree_depths(forest).max())
    if forest.n_trees * max(1, depth) >= BUCKETED_MIN_WORK:
        return "bucketed"
    return "vectorized"


def compile_model(model, engine: str | None = None) -> Engine:
    return _compile_forest_engine(model.forest, engine)


def _compile_forest_engine(forest: Forest, engine: str | None) -> Engine:
    if engine is None:
        engine = available_engines(forest)[0]
        # hardware-aware default (lossy-compilation choice, §3.7): pallas
        # targets TPU (interpret mode on CPU is a correctness path, not a
        # fast path); on CPU hosts pick between the XLA bucketed engine and
        # the trace-free numpy engine by forest size
        if engine in ("pallas", "bucketed"):
            import jax
            if jax.default_backend() == "cpu":
                engine = select_cpu_engine(forest)
    if engine == "naive":
        return Engine("naive", lambda X: predict_naive(forest, X),
                      forest=forest)
    if engine == "vectorized":
        return Engine("vectorized", compile_predict_raw(forest),
                      note="specialized flat-table traversal (§5.1)",
                      forest=forest)
    if engine in ("bucketed", "leaf_path"):
        compat = (_compat_bucketed if engine == "bucketed"
                  else _compat_leaf_path)
        reason = compat(forest)
        if reason:
            raise YdfError(
                f"Model is not compatible with the {engine!r} engine: "
                f"{reason}. Compatible engines: {available_engines(forest)}.")
        from repro.kernels.forest_infer.ops import bucketed_runner
        strategy = "leaf_path" if engine == "leaf_path" else None
        run = bucketed_runner(forest, strategy)  # pack + upload once, now
        note = ("predicate-matrix (leaf-path) scoring forced on every "
                "bucket (§10.2)" if engine == "leaf_path" else
                "depth-bucketed XLA traversal, per-bucket early exit and "
                "strategy choice (§10)")
        return Engine(engine, run, note=note, forest=forest)
    if engine == "pallas":
        reason = _compat_pallas(forest)
        if reason:
            raise YdfError(
                f"Model is not compatible with the 'pallas' engine: {reason}. "
                f"Compatible engines: {available_engines(forest)}.")
        from repro.kernels.forest_infer.ops import device_packed
        device_packed(forest)  # upload the depth-packed layout once, now
        return Engine("pallas", functools.partial(_pallas_per_tree, forest),
                      note="tree-tiled over depth-packed blocks (§5.2); "
                           "interpret-mode on CPU, compiled on TPU",
                      forest=forest)
    raise YdfError(f"Unknown engine {engine!r}. "
                   f"Available: {available_engines(forest)}.")


def _pallas_per_tree(forest: Forest, X: np.ndarray) -> np.ndarray:
    """The pallas engine's call: upload the batch, run the kernel and the
    reorder to the original tree order, copy the per-tree output back.
    While tracing, the upload and the kernel block until the device is
    done, so each span holds its own device time."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.forest_infer.ops import forest_predict
    with trace.span("engines/upload", rows=len(X)):
        Xd = jnp.asarray(X, jnp.float32)
        if trace.enabled():
            jax.block_until_ready(Xd)
    with trace.span("engines/kernel", rows=len(X)):
        out = forest_predict(forest, Xd)
        if trace.enabled():
            jax.block_until_ready(out)
    with trace.span("engines/to_host", bytes=out.nbytes):
        return np.asarray(out)


# engines whose first call at a new batch shape traces/compiles — the layer
# that knows its dispatch shapes (serving, benchmarks) warms these
JIT_ENGINES = ("pallas", "bucketed", "leaf_path")


# ------------------------------------------------- compiled predictor (§5.1)

@dataclass
class CompiledPredictor:
    """The reusable end-to-end serving artifact (DESIGN.md §5.1).

    Built once per model: ``encoder`` holds the vectorized raw→code tables,
    ``engine`` the traversal closure (device-resident forest for pallas),
    ``finalize`` the model's aggregation + activation head. ``predict`` is
    then a pure batch function with no per-call compilation, conversion, or
    host↔device forest traffic; ``encode``/``predict_encoded`` split the two
    halves so a micro-batcher (serving/forest.py, §5.4) can encode per
    request but dispatch per padded batch.

    Pickles as a whole (§10.4): Engine serializes to (name, forest) and
    recompiles on load, encoder/finalize are plain data — so a predictor
    saved after engine selection comes back with the SAME engine choice,
    not a re-run of the hardware heuristic.
    """
    engine: Engine
    encoder: BatchEncoder
    finalize: Callable[[np.ndarray], np.ndarray]
    compile_s: float = 0.0
    # trailing shape of one prediction — () for regression, (n_classes,) for
    # classification. Lets a zero-row dispatch return a correctly-shaped
    # empty array without running the engine (serving/forest.py).
    out_shape: tuple = ()

    @property
    def name(self) -> str:
        return self.engine.name

    def encode(self, dataset) -> np.ndarray:
        with trace.span("engines/encode"):
            return self.encoder.encode(dataset)

    def per_tree(self, X: np.ndarray) -> np.ndarray:
        # engine failures surface TYPED (DESIGN.md §9.1): the serving
        # front-end routes EngineFailure into retry / circuit-breaker logic,
        # while schema errors (encode) stay YdfError and reach the caller
        try:
            with trace.span("engines/dispatch", engine=self.name,
                            rows=len(X)):
                return self.engine.per_tree(X)
        except (EngineFailure, KeyboardInterrupt):
            raise
        except Exception as e:
            raise EngineFailure(
                f"engine {self.name!r} failed on a batch of "
                f"{len(X)} rows: {type(e).__name__}: {e}",
                engine=self.name) from e

    def predict_encoded(self, X: np.ndarray) -> np.ndarray:
        if len(X) == 0:
            return np.zeros((0,) + self.out_shape, np.float32)
        per_tree = np.asarray(self.per_tree(X))
        with trace.span("engines/finalize", rows=len(X)):
            return self.finalize(per_tree)

    def predict(self, dataset) -> np.ndarray:
        return self.predict_encoded(self.encode(dataset))


def compile_predictor(model, engine: str | None = None) -> CompiledPredictor:
    """Compile ``model`` into a CompiledPredictor. Jit'd engines retrace per
    batch shape, so shape warmup belongs to the layer that knows the
    dispatch sizes — serving/forest.py warms at its padding buckets."""
    t0 = clock.perf()
    with trace.span("engines/compile", engine=engine or "auto"):
        eng = compile_model(model, engine)
    encoder = BatchEncoder(model.spec, model.features)
    # _compile_finalize returns a picklable callable over the needed fields
    # only — a bound model method would cycle Model <-> predictor (models.py)
    finalize = model._compile_finalize()
    # probe the output head on a zero per-tree stack to learn the trailing
    # prediction shape — no engine call, so it is free even for jit'd engines
    probe = finalize(np.zeros(
        (1, model.forest.n_trees, model.forest.leaf_value.shape[-1]),
        np.float32))
    return CompiledPredictor(engine=eng, encoder=encoder,
                             finalize=finalize,
                             compile_s=clock.perf() - t0,
                             out_shape=tuple(np.asarray(probe).shape[1:]))


def benchmark_inference(model, dataset, *, repetitions: int = 5) -> str:
    """App. B.4 analogue: time every compatible engine on the dataset.

    Jit'd engines (JIT_ENGINES) warm up AT THE TIMED SHAPE — they retrace
    per batch shape, so a 64-row warmup would leave the retrace in the first
    timed rep — and that warmup is reported separately as compile time. It
    is an upper bound: the warmup call necessarily executes once after
    tracing (on TPU, XLA compiles during that first call; in interpret mode
    on CPU the execution dominates). Non-jit engines have no trace to warm:
    their compile time is the closure-specialization cost alone, and a
    tiny-slice warmup just touches the code path.
    """
    # the compiled encoder only needs the FEATURE columns, so imported /
    # built models benchmark on label-free request batches too (§5.1)
    X = BatchEncoder(model.spec, model.features).encode(dataset)
    lines = ["benchmark_inference (avg over %d reps, batch=%d):"
             % (repetitions, X.shape[0])]
    for name in available_engines(model.forest):
        t0 = clock.perf()
        eng = compile_model(model, name)
        if name in JIT_ENGINES:
            eng.per_tree(X)          # warmup / trace at the timed shape
            compile_s = clock.perf() - t0
        else:
            compile_s = clock.perf() - t0
            eng.per_tree(X[:min(64, len(X))])  # untimed code-path touch
        t0 = clock.perf()
        for _ in range(repetitions):
            eng.per_tree(X)
        dt = (clock.perf() - t0) / repetitions
        us = dt / max(1, X.shape[0]) * 1e6
        lines.append(f"  {name:<12s} {us:10.3f} us/example  "
                     f"({dt * 1e3:.2f} ms/batch, compile {compile_s * 1e3:.1f} ms)")
    return "\n".join(lines)
