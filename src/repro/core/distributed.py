"""Distributed decision-forest training (paper §3.9; Guillame-Bert & Teytaud
2018) mapped onto SPMD collectives (DESIGN.md §2.3).

The 2-D training grid composes both of the paper's distributions:
  * example-parallel over the 'data' mesh axis — histograms are psum'ed;
    traffic per level = histogram size, INDEPENDENT of the number of examples
    (the key scaling property of the 2018 paper);
  * feature-parallel over the 'model' mesh axis — each shard owns a slice of
    feature columns, exchanges only (gain, feature, bin) candidates
    (all_gather of 3 scalars per node) and the winning example partition as a
    BIT-PACKED uint32 bitmap (32x less traffic than a float mask — the
    delta-bit-encoding insight of §3.9 restated).

Trees grown here use a fixed-depth COMPLETE layout in level order (node n ->
children 2n+1/2n+2), fully jittable: nodes without a valid split emit a
degenerate all-left split with zero gain. The host converts to the pointer
SoA ``Forest`` for serving. Numerical (binned uint8) features only — the
categorical path stays on the host learner (documented scope split).

A third backend — the paper's single-process SIMULATION backend for
development/debugging/fault-injection — lives in ``SimulatedCluster``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import build_training_logs, trace, validate_training_logs
from repro.core.tree import Forest, empty_forest


# =====================================================================
# jnp gh-gain machinery (device-side mirror of splitters.best_splits)
# =====================================================================

def _gh_score(g, h, l2):
    return 0.5 * jnp.square(g) / (h + l2 + 1e-12)


def split_gain_tensor(hist: jax.Array, min_examples: int, l2: float):
    """hist: (nodes, F, B, 3) [g, h, n] -> full gain tensor (nodes, F, B-1),
    invalid splits = -inf. Per-feature columns are independent, so a
    feature's gain values do not depend on which other features share the
    histogram batch (the property the fault-recovery merge relies on)."""
    parent = hist.sum(2)                              # (nodes, F, 3)
    ps = _gh_score(parent[..., 0], parent[..., 1], l2)
    cum = jnp.cumsum(hist, axis=2)[:, :, :-1]         # (nodes, F, B-1, 3)
    right = parent[:, :, None] - cum
    gain = (_gh_score(cum[..., 0], cum[..., 1], l2)
            + _gh_score(right[..., 0], right[..., 1], l2) - ps[..., None])
    ok = (cum[..., 2] >= min_examples) & (right[..., 2] >= min_examples)
    return jnp.where(ok, gain, -jnp.inf)


def best_split_gh(hist: jax.Array, min_examples: int, l2: float):
    """hist: (nodes, F, B, 3) [g, h, n] -> (gain, feat, bin) per node (local
    feature indices; bin = first right bin)."""
    gain = split_gain_tensor(hist, min_examples, l2)
    flat = gain.reshape(gain.shape[0], -1)            # (nodes, F*(B-1))
    idx = jnp.argmax(flat, axis=1)
    best = jnp.take_along_axis(flat, idx[:, None], 1)[:, 0]
    feat = idx // (hist.shape[2] - 1)
    bin_ = idx % (hist.shape[2] - 1) + 1
    return best, feat.astype(jnp.int32), bin_.astype(jnp.int32)


def _pack_bits(bits: jax.Array) -> jax.Array:
    """(N,) {0,1} int32 -> (N/32,) uint32 (N must be a multiple of 32)."""
    b = bits.reshape(-1, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (b << shifts[None, :]).sum(1, dtype=jnp.uint32)


def _unpack_bits(words: jax.Array) -> jax.Array:
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return ((words[:, None] >> shifts[None, :]) & 1).astype(jnp.int32).reshape(-1)


# =====================================================================
# shard_map level step
# =====================================================================

@dataclass(frozen=True)
class DistGBTConfig:
    max_depth: int = 5
    n_bins: int = 64
    min_examples: int = 2
    l2: float = 0.0
    shrinkage: float = 0.1
    num_trees: int = 20
    data_axis: str = "data"
    model_axis: str = "model"
    # kernels/histogram impl: "auto" is the kernel on a TPU and the jnp
    # reference elsewhere; "pallas" | "interpret" | "ref" force one
    hist_impl: str = "auto"


def make_level_step(mesh: Mesh, cfg: DistGBTConfig, n_nodes: int, F_local: int):
    """Returns jitted fn(codes_l, stats_l, node_of_l) ->
    (feat_global, bin, gain, go_bits_l, hist) executing one tree level on the
    2-D grid. All inputs/outputs are per-shard (shard_map)."""
    from repro.kernels.histogram.ops import histogram

    da, ma = cfg.data_axis, cfg.model_axis

    def level(codes, stats, node_of):
        # codes: (N_l, F_l) uint8; stats: (N_l, 3); node_of: (N_l,)
        hist = histogram(codes, stats, node_of, n_nodes, cfg.n_bins,
                         impl=cfg.hist_impl)
        hist = jax.lax.psum(hist, da)                 # example-parallel reduce
        gain, feat_l, bin_ = best_split_gh(hist, cfg.min_examples, cfg.l2)
        # feature-parallel candidate exchange: 3 scalars per node per shard
        gains = jax.lax.all_gather(gain, ma)          # (W, nodes)
        feats = jax.lax.all_gather(feat_l, ma)
        bins = jax.lax.all_gather(bin_, ma)
        winner = jnp.argmax(jnp.where(jnp.isfinite(gains), gains, -jnp.inf), 0)
        nid = jnp.arange(n_nodes)
        w_gain = gains[winner, nid]
        w_feat_local = feats[winner, nid]
        w_bin = bins[winner, nid]
        me = jax.lax.axis_index(ma)
        owner_feat = jnp.where(winner == me, w_feat_local, 0)
        valid = jnp.isfinite(w_gain)
        # owner computes the partition for ITS example rows; psum over the
        # model axis broadcasts it (others contribute zeros); bit-packed.
        my_codes = jnp.take_along_axis(
            codes, owner_feat[node_of.clip(0)][:, None], axis=1)[:, 0]
        thr = w_bin[node_of.clip(0)]
        go = ((winner[node_of.clip(0)] == me)
              & (my_codes >= thr.astype(codes.dtype))
              & (node_of >= 0)).astype(jnp.int32)
        packed = _pack_bits(go)
        packed = jax.lax.psum(packed, ma)
        go_all = _unpack_bits(packed)
        w_feat_global = w_feat_local + winner * F_local
        return (w_feat_global, w_bin, jnp.where(valid, w_gain, -jnp.inf),
                go_all, hist)

    specs_in = (P(cfg.data_axis, cfg.model_axis), P(cfg.data_axis, None),
                P(cfg.data_axis))
    specs_out = (P(), P(), P(), P(cfg.data_axis), P())
    return jax.jit(jax.shard_map(level, mesh=mesh, in_specs=specs_in,
                                 out_specs=specs_out, check_vma=False))


@jax.jit
def _route(valid, go, node_of):
    """Descend one level: examples of a validly split node take their go
    bit, the rest go left. The per-node ``valid`` table is looked up by a
    one-hot compare rather than a gather, so the result keeps the
    data-sharded layout of ``node_of`` on any mesh."""
    own = node_of[:, None] == jnp.arange(valid.shape[0])[None, :]
    go = jnp.where((own & valid[None, :]).any(axis=1), go, 0)
    return jnp.where(node_of >= 0, node_of * 2 + go, node_of)


# =====================================================================
# Distributed GBT boosting loop (host-orchestrated, device-stepped)
# =====================================================================

def grow_tree_complete(level_fns, codes_sh, stats_sh, node_of0, cfg: DistGBTConfig):
    """Grow one fixed-depth complete tree. Returns (feat, bin, gain) arrays in
    level order (2^D - 1 internal nodes) + final per-leaf [g, h, n]."""
    D = cfg.max_depth
    feats, bins, gains = [], [], []
    node_of = node_of0
    for d in range(D):
        n_nodes = 2 ** d
        f, b, g, go, hist = level_fns[d](codes_sh, stats_sh, node_of)
        feats.append(np.asarray(f))
        bins.append(np.asarray(b))
        gains.append(np.asarray(g))
        node_of = _route(jnp.isfinite(g), go, node_of)
    # final per-leaf [g, h, n]: one more psum'd histogram at leaf granularity.
    # hist is per-model-shard (its own features); summing the BINS of any one
    # feature column yields the per-node stat totals, identical on all shards.
    _, _, _, _, hist = level_fns[D](codes_sh, stats_sh, node_of)
    leaf_stats = np.asarray(hist[:, 0].sum(axis=1))
    return (np.concatenate(feats), np.concatenate(bins), np.concatenate(gains),
            leaf_stats, node_of)


# ---- shared boosting-state helpers (host side, backend-agnostic) ----

def _init_pred(y: np.ndarray, task: str) -> float:
    if task == "binary":
        p0 = np.clip(y.mean(), 1e-6, 1 - 1e-6)
        return float(np.log(p0 / (1 - p0)))
    return float(y.mean())


def _grad_hess(pred: np.ndarray, y: np.ndarray, task: str):
    if task == "binary":
        p = 1 / (1 + np.exp(-pred))
        return p - y, np.maximum(p * (1 - p), 1e-12)
    return pred - y, np.ones(len(y))


def predict_scores_complete(trees: list[dict], init_pred: float, D: int,
                            codes: np.ndarray) -> np.ndarray:
    """Score complete-layout trees (shared by both distributed backends)."""
    s = np.full(codes.shape[0], init_pred, np.float64)
    for tree in trees:
        node = np.zeros(codes.shape[0], np.int64)
        off = 0
        for d in range(D):
            nid = off + node
            f, b = tree["feat"][nid], tree["bin"][nid]
            go = (codes[np.arange(len(codes)), f] >= b) \
                & np.isfinite(tree["gain"][nid])
            node = node * 2 + go
            off += 2 ** d
        s += tree["leaf"][node]
    return s


def complete_trees_to_forest(trees: list[dict], init_pred: float, D: int,
                             feature_names: list[str] | None = None) -> Forest:
    """Convert complete-layout trees to the pointer SoA for the engines."""
    T = len(trees)
    M = 2 ** (D + 1)
    forest = empty_forest(T, M, 1, feature_names=feature_names)
    forest.depth = D
    forest.init_pred = np.array([init_pred], np.float32)
    for t, tree in enumerate(trees):
        # complete level order -> pointer layout (children in pairs).
        # Invalid (degenerate) splits become always-false conditions so
        # inference routes everything left, matching training.
        nxt = 1
        ptr = {0: 0}  # complete-id -> pointer-id
        off = 0
        for d in range(D):
            for i in range(2 ** d):
                cid = off + i
                pid = ptr[cid]
                valid = bool(np.isfinite(tree["gain"][cid]))
                forest.feature[t, pid] = max(int(tree["feat"][cid]), 0)
                if valid:
                    forest.split_bin[t, pid] = tree["bin"][cid]
                    forest.threshold[t, pid] = float(tree["bin"][cid]) - 0.5
                    forest.split_gain[t, pid] = max(
                        float(tree["gain"][cid]), 0.0)
                else:
                    forest.split_bin[t, pid] = 65535
                    forest.threshold[t, pid] = np.float32(3e38)
                forest.left_child[t, pid] = nxt
                left_cid = off + 2 ** d + 2 * i  # = 2^(d+1)-1 + 2i
                ptr[left_cid] = nxt
                ptr[left_cid + 1] = nxt + 1
                nxt += 2
            off += 2 ** d
        for i in range(2 ** D):  # off == 2^D - 1 here
            pid = ptr[off + i]
            forest.left_child[t, pid] = -1
            forest.feature[t, pid] = -1
            forest.leaf_value[t, pid, 0] = tree["leaf"][i]
        forest.n_nodes[t] = nxt
    return forest


class DistributedGBT:
    """Boosted trees on the (data x model) mesh. Binary classification /
    regression on pre-binned numerical features (uint8 codes).

    Fault tolerance rides the DESIGN.md §11 checkpoint layer:
    ``fit(..., checkpoint=CheckpointPolicy(dir))`` writes atomic tree-boundary
    checkpoints and resumes bit-identically — the same serialization path the
    host learners use (the bespoke ``state_dict`` is gone). The stored config
    excludes the mesh shape on purpose: trees are numerically equivalent
    across mesh placements (tested at 1e-4), so a run checkpointed on one
    grid may resume on another.
    """

    def __init__(self, cfg: DistGBTConfig, mesh: Mesh):
        self.cfg = cfg
        self.mesh = mesh
        self.trees: list[dict] = []
        self.training_logs: dict = {}
        self._level_fns: dict[int, list] = {}

    def _fns(self, F_local: int):
        if F_local not in self._level_fns:
            self._level_fns[F_local] = [
                make_level_step(self.mesh, self.cfg, 2 ** d, F_local)
                for d in range(self.cfg.max_depth + 1)]
        return self._level_fns[F_local]

    def _train_config(self, task: str) -> dict:
        import dataclasses as dc
        return {"trainer": "DistributedGBT", "task": task,
                "cfg": dc.asdict(self.cfg)}

    def fit(self, codes: np.ndarray, y: np.ndarray, *, task: str = "binary",
            checkpoint=None):
        cfg = self.cfg
        N, F = codes.shape
        da = self.mesh.shape[cfg.data_axis]
        ma = self.mesh.shape[cfg.model_axis]
        assert N % (da * 32) == 0, f"N={N} must be divisible by 32*data={32 * da}"
        assert F % ma == 0, f"F={F} must divide model axis {ma}"
        F_local = F // ma
        fns = self._fns(F_local)

        sh = NamedSharding(self.mesh, P(cfg.data_axis, cfg.model_axis))
        codes_d = jax.device_put(jnp.asarray(codes), sh)
        pred = np.zeros(N, np.float64)
        self.init_pred = _init_pred(y, task)
        pred[:] = self.init_pred
        self.trees = []

        from repro.core.rf import training_data_fingerprint
        from repro.kernels.histogram import ops as hist_ops
        from repro.train.checkpoint import open_session
        sess = open_session(checkpoint, self._train_config(task),
                            training_data_fingerprint(codes, y))
        interrupted = False
        if sess is not None:
            state = sess.resume()
            if state is not None:
                self.trees = list(state["trees"])
                pred = np.copy(state["pred"])
                self.init_pred = float(state["init_pred"])

        import contextlib
        rep = NamedSharding(self.mesh, P(cfg.data_axis))
        with (sess if sess is not None else contextlib.nullcontext()):
            for it in range(len(self.trees), cfg.num_trees):
                g, h = _grad_hess(pred, y, task)
                stats = np.stack([g, h, np.ones(N)], 1).astype(np.float32)
                stats_d = jax.device_put(jnp.asarray(stats),
                                         NamedSharding(self.mesh, P(cfg.data_axis, None)))
                node0 = jax.device_put(jnp.zeros(N, jnp.int32), rep)
                with trace.span("distributed/tree", tree=it):
                    feat, bin_, gain, leaf_stats, node_of = grow_tree_complete(
                        fns, codes_d, stats_d, node0, cfg)
                leaf = -cfg.shrinkage * leaf_stats[:, 0] / (leaf_stats[:, 1]
                                                            + cfg.l2 + 1e-12)
                tree = {"feat": feat, "bin": bin_, "gain": gain,
                        "leaf": leaf.astype(np.float32)}
                self.trees.append(tree)
                # node_of is in leaf-level space [0, 2^D) after D split rounds
                pred += leaf[np.asarray(node_of)]
                if sess is not None:
                    done = len(self.trees) == cfg.num_trees
                    if not done and sess.should_stop():
                        interrupted = True
                    sess.save(len(self.trees),
                              {"kind": "dist_gbt", "trees": list(self.trees),
                               "pred": np.copy(pred),
                               "init_pred": self.init_pred},
                              done=done, force=done or interrupted)
                    if interrupted:
                        break
        self.training_logs = build_training_logs(
            learner="distributed_gbt", num_trees=len(self.trees),
            resilience=sess.events if sess is not None else None,
            interrupted=interrupted,
            extra={"devices": sorted(d.id for d in
                                     codes_d.sharding.device_set),
                   "hist_impl": hist_ops.resolve_impl(cfg.hist_impl)})
        return self

    def predict_scores(self, codes: np.ndarray) -> np.ndarray:
        return predict_scores_complete(self.trees, self.init_pred,
                                       self.cfg.max_depth, codes)

    def to_forest(self, feature_names: list[str] | None = None) -> Forest:
        return complete_trees_to_forest(self.trees, self.init_pred,
                                        self.cfg.max_depth, feature_names)


# =====================================================================
# Simulation backend (paper §3.9's third implementation) + fault tolerance
# =====================================================================

@dataclass(frozen=True)
class WorkerFaultPlan:
    """A deterministic worker-death schedule for the simulation backend,
    mirroring ``serving/faults.py``: explicit ``(tree, level, worker)``
    triples for targeted tier-1 scenarios plus a seeded per-(tree, level,
    worker) Bernoulli ``death_rate`` for soak runs. Pure counter-hash — no
    wall-clock — so every fault run is exactly reproducible.
    """
    seed: int = 0
    deaths: tuple = ()           # ((tree, level, worker), ...)
    death_rate: float = 0.0

    def deaths_at(self, tree: int, level: int,
                  worker_ids: list[int]) -> list[int]:
        out = [w for (t, l, w) in self.deaths
               if t == tree and l == level and w in worker_ids]
        if self.death_rate > 0.0:
            for w in worker_ids:
                if w in out:
                    continue
                u = np.random.default_rng(
                    (self.seed & 0xFFFFFFFF, 7919, tree, level, w)).random()
                if u < self.death_rate:
                    out.append(w)
        return sorted(out)


class SimulatedWorker:
    """A training worker owning a set of feature columns."""

    def __init__(self, wid: int, codes: np.ndarray, feature_ids: list[int]):
        self.wid = wid
        self.feature_ids = list(feature_ids)
        self.codes = codes  # full matrix; worker only READS its columns
        self.alive = True

    def local_best(self, stats, node_of, n_nodes, cfg) -> list[tuple]:
        from repro.core.splitters import build_histogram
        if not self.feature_ids:
            return [(-np.inf, -1, 0)] * n_nodes
        # scan features in GLOBAL-id order so the within-worker tie-break
        # (first max = smallest feature id, then smallest bin) is a property
        # of the features themselves, not of the assignment order — after a
        # death reassigns features, the surviving workers still propose the
        # exact same candidates (fault runs stay bit-identical to clean)
        fids = sorted(self.feature_ids)
        sub = self.codes[:, fids]
        hist = build_histogram(sub, stats, node_of, n_nodes, cfg.n_bins)
        gain = np.asarray(split_gain_tensor(jnp.asarray(hist),
                                            cfg.min_examples, cfg.l2))
        B1 = gain.shape[2]
        flat = gain.reshape(n_nodes, -1)
        idx = flat.argmax(1)
        return [(float(flat[i, idx[i]]), fids[int(idx[i]) // B1],
                 int(idx[i]) % B1 + 1) for i in range(n_nodes)]

    def partition(self, feature: int, bin_: int) -> np.ndarray:
        return self.codes[:, feature] >= bin_


class SimulatedCluster:
    """Single-process multi-worker simulation: breakpoint-able, step-wise,
    with worker-failure injection and dynamic feature reassignment (§3.9).

    Fault-tolerant by construction (DESIGN.md §11.3):

    * a ``WorkerFaultPlan`` kills workers at scheduled ``(tree, level)``
      points — candidates computed in that level pass are treated as LOST
      and the level RESTARTS against the surviving workers after dynamic
      feature reassignment;
    * candidate merge uses a total order — (highest gain, then smallest
      feature id, then smallest bin) — so the chosen split is independent of
      which worker proposed it. That makes a faulted run's forest
      BIT-IDENTICAL to the clean run (the invariant the recovery tests pin);
    * ``fit(..., checkpoint=CheckpointPolicy(dir))`` writes the same atomic
      tree-boundary checkpoints as every other trainer, so a full cluster
      crash resumes mid-forest.

    Every death / reassignment / restart is recorded in
    ``training_logs["resilience"]``.
    """

    def __init__(self, codes: np.ndarray, n_workers: int, cfg: DistGBTConfig,
                 seed: int = 0, fault_plan: WorkerFaultPlan | None = None):
        self.cfg = cfg
        self.codes = codes
        self.seed = seed
        F = codes.shape[1]
        rng = np.random.default_rng(seed)
        assign = np.array_split(rng.permutation(F), n_workers)
        self.workers = [SimulatedWorker(w, codes, list(a))
                        for w, a in enumerate(assign)]
        self.traffic_bytes = 0
        self.fault_plan = fault_plan if fault_plan is not None else WorkerFaultPlan()
        self.trees: list[dict] = []
        self.init_pred = 0.0
        self.resilience: list[dict] = []
        # pre-fit logs hold a LIVE reference to the resilience list so
        # direct grow_tree() users see deaths as they happen; fit() rebuilds
        # the dict through the same §13.4 schema with final values
        self.training_logs: dict = validate_training_logs({
            "schema_version": 1, "learner": "simulated_cluster",
            "num_trees": 0, "growth_engine": None, "engine_fallback": None,
            "resilience": self.resilience, "interrupted": False})
        self._tree_counter = 0

    def kill_worker(self, wid: int, *, tree: int | None = None,
                    level: int | None = None) -> None:
        """Fault injection: reassign the dead worker's features round-robin
        (the paper's dynamic feature re-allocation)."""
        dead = self.workers[wid]
        dead.alive = False
        alive = [w for w in self.workers if w.alive]
        if not alive:
            raise RuntimeError("all workers failed")
        n_feats = len(dead.feature_ids)
        for i, f in enumerate(dead.feature_ids):
            alive[i % len(alive)].feature_ids.append(f)
        dead.feature_ids = []
        self.resilience.append(
            {"event": "worker_death", "worker": wid, "tree": tree,
             "level": level, "features_reassigned": n_feats,
             "workers_alive": len(alive)})
        trace.event("distributed/worker_death", worker=wid, tree=tree,
                    level=level, features_reassigned=n_feats)

    def _train_config(self, task: str) -> dict:
        import dataclasses as dc
        return {"trainer": "SimulatedCluster", "task": task,
                "cfg": dc.asdict(self.cfg)}

    def grow_tree(self, stats: np.ndarray, tree_index: int | None = None) -> dict:
        t = self._tree_counter if tree_index is None else tree_index
        self._tree_counter = t + 1
        cfg = self.cfg
        N = self.codes.shape[0]
        node_of = np.zeros(N, np.int32)
        feats, bins, gains = [], [], []
        for d in range(cfg.max_depth):
            n_nodes = 2 ** d
            level_ctx = trace.span("distributed/level", tree=t, level=d,
                                   nodes=n_nodes)
            level_ctx.__enter__()
            while True:
                cands = []
                for w in self.workers:
                    if not w.alive:
                        continue
                    with trace.span("distributed/worker_best", worker=w.wid,
                                    tree=t, level=d,
                                    features=len(w.feature_ids)):
                        cands.append(w.local_best(stats, node_of, n_nodes,
                                                  cfg))
                self.traffic_bytes += sum(len(c) for c in cands) * 12  # 3 scalars
                dead = self.fault_plan.deaths_at(
                    t, d, [w.wid for w in self.workers if w.alive])
                if not dead:
                    break
                # deaths mid-level: the level pass's candidates are lost.
                # Reassign the dead workers' features, restart the level.
                # Histograms are pure functions of (data, node_of), and the
                # merge order is total, so the restarted level is
                # bit-identical to a clean level over the same partition.
                for wid in dead:
                    self.kill_worker(wid, tree=t, level=d)
                self.resilience.append(
                    {"event": "level_restart", "tree": t, "level": d,
                     "deaths": list(dead)})
                trace.event("distributed/level_restart", tree=t, level=d,
                            deaths=len(dead))
            for i in range(n_nodes):
                # assignment-independent merge: gain desc, feature id asc,
                # bin asc — a worker death can never change the winner
                g, f, b = max((c[i] for c in cands),
                              key=lambda x: (x[0], -x[1], -x[2]))
                feats.append(f if np.isfinite(g) else 0)
                bins.append(b)
                gains.append(g)
            level = np.array(gains[-n_nodes:])
            go = np.zeros(N, bool)
            for i in range(n_nodes):
                if np.isfinite(level[i]):
                    f, b = feats[-n_nodes + i], bins[-n_nodes + i]
                    owner = next(w for w in self.workers
                                 if w.alive and f in w.feature_ids)
                    sel = node_of == i
                    go[sel] = owner.partition(f, b)[sel]
            self.traffic_bytes += (N + 7) // 8  # bit-packed partition
            node_of = node_of * 2 + go
            level_ctx.__exit__(None, None, None)
        # leaves
        leaf = np.zeros(2 ** cfg.max_depth, np.float32)
        for i in range(2 ** cfg.max_depth):
            sel = node_of == i
            G, H = stats[sel, 0].sum(), stats[sel, 1].sum()
            leaf[i] = -cfg.shrinkage * G / (H + cfg.l2 + 1e-12)
        return {"feat": np.array(feats), "bin": np.array(bins),
                "gain": np.array(gains), "leaf": leaf, "node_of": node_of}

    # ---- boosting driver (same loop shape as DistributedGBT.fit) ----
    def fit(self, y: np.ndarray, *, task: str = "binary", checkpoint=None):
        cfg = self.cfg
        N = self.codes.shape[0]
        pred = np.zeros(N, np.float64)
        self.init_pred = _init_pred(y, task)
        pred[:] = self.init_pred
        self.trees = []

        from repro.core.rf import training_data_fingerprint
        from repro.train.checkpoint import open_session
        sess = open_session(checkpoint, self._train_config(task),
                            training_data_fingerprint(self.codes, y))
        interrupted = False
        if sess is not None:
            state = sess.resume()
            if state is not None:
                self.trees = list(state["trees"])
                pred = np.copy(state["pred"])
                self.init_pred = float(state["init_pred"])

        import contextlib
        with (sess if sess is not None else contextlib.nullcontext()):
            for it in range(len(self.trees), cfg.num_trees):
                g, h = _grad_hess(pred, y, task)
                stats = np.stack([g, h, np.ones(N)], 1)
                tree = self.grow_tree(stats, tree_index=it)
                self.trees.append(
                    {k: tree[k] for k in ("feat", "bin", "gain", "leaf")})
                pred += tree["leaf"][tree["node_of"]]
                if sess is not None:
                    done = len(self.trees) == cfg.num_trees
                    if not done and sess.should_stop():
                        interrupted = True
                    sess.save(len(self.trees),
                              {"kind": "sim_gbt", "trees": list(self.trees),
                               "pred": np.copy(pred),
                               "init_pred": self.init_pred},
                              done=done, force=done or interrupted)
                    if interrupted:
                        break
        self.training_logs = build_training_logs(
            learner="simulated_cluster", num_trees=len(self.trees),
            resilience=self.resilience, interrupted=interrupted,
            extra={"checkpoint":
                   sess.events if sess is not None else []})
        return self

    def predict_scores(self, codes: np.ndarray) -> np.ndarray:
        return predict_scores_complete(self.trees, self.init_pred,
                                       self.cfg.max_depth, codes)

    def to_forest(self, feature_names: list[str] | None = None) -> Forest:
        return complete_trees_to_forest(self.trees, self.init_pred,
                                        self.cfg.max_depth, feature_names)
