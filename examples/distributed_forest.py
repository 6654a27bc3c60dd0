"""Distributed GBT training on a (data x model) device grid (paper §3.9):
example-parallel histogram psums + feature-parallel split exchange with
bit-packed partition broadcast, plus the single-process simulation backend
with a mid-training worker failure.

    PYTHONPATH=src python examples/distributed_forest.py

The mesh is built from the devices that are present: the four chips of a
TPU host give a 2x2 grid. On a CPU host, give it placeholder devices with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import jax
import numpy as np

from repro.core.distributed import DistGBTConfig, DistributedGBT, SimulatedCluster

rng = np.random.default_rng(0)
N, F = 4096, 16
codes = rng.integers(0, 64, (N, F)).astype(np.uint8)
logit = (0.9 * (codes[:, 0] > 30) - 1.1 * (codes[:, 3] > 45)
         + 0.6 * (codes[:, 5] > 10) * (codes[:, 8] > 20) - 0.2)
y = (rng.random(N) < 1 / (1 + np.exp(-logit))).astype(np.float64)

cfg = DistGBTConfig(max_depth=5, n_bins=64, num_trees=20)

n_dev = len(jax.devices())
n_model = 2 if n_dev % 2 == 0 else 1       # F=16 splits over 1 or 2 shards
print(f"== 2-D grid training ({n_dev // n_model} 'data' x {n_model} 'model' "
      "workers) ==")
mesh = jax.make_mesh((n_dev // n_model, n_model), ("data", "model"))
model = DistributedGBT(cfg, mesh).fit(codes, y)
acc = ((model.predict_scores(codes) > 0) == y).mean()
print(f"train accuracy: {acc:.4f} over {len(model.trees)} trees")

print("\n== equivalence with a single-worker run ==")
m1 = DistributedGBT(cfg, jax.make_mesh((1, 1), ("data", "model"))).fit(codes, y)
print("max |score diff|:",
      np.abs(m1.predict_scores(codes) - model.predict_scores(codes)).max())

print("\n== fault tolerance: checkpoint, interrupt mid-forest, resume ==")
import tempfile

from repro.train.checkpoint import CheckpointPolicy

ckdir = tempfile.mkdtemp()
calls = {"n": 0}
def _cancel():                      # simulate an interruption after 10 trees
    calls["n"] += 1
    return calls["n"] >= 10
half = DistributedGBT(cfg, mesh).fit(
    codes, y, checkpoint=CheckpointPolicy(ckdir, every_n_trees=5, cancel=_cancel))
print(f"interrupted at {len(half.trees)} trees "
      f"(servable: acc={((half.predict_scores(codes) > 0) == y).mean():.4f})")
resumed = DistributedGBT(cfg, mesh).fit(codes, y,
                                        checkpoint=CheckpointPolicy(ckdir))
print("resume == straight run:",
      np.allclose(resumed.predict_scores(codes), model.predict_scores(codes),
                  atol=1e-5))

print("\n== simulation backend (paper's third backend) + worker deaths ==")
from repro.core.distributed import WorkerFaultPlan

sim_clean = SimulatedCluster(codes, n_workers=8, cfg=cfg, seed=0).fit(y)
plan = WorkerFaultPlan(deaths=((2, 1, 3), (7, 0, 5)))  # die mid-level
sim_fault = SimulatedCluster(codes, n_workers=8, cfg=cfg, seed=0,
                             fault_plan=plan).fit(y)
same = all(np.array_equal(a[k], b[k])
           for a, b in zip(sim_clean.trees, sim_fault.trees) for k in a)
print("forest bit-identical despite 2 mid-level deaths:", same)
for ev in sim_fault.training_logs["resilience"]:
    print("  recovery event:", ev)
print(f"communication: {sim_fault.traffic_bytes} bytes "
      f"(candidates + 32x bit-packed partitions)")

print("\n== serve through the engine stack ==")
forest = model.to_forest([f"f{i}" for i in range(F)])
from repro.core.tree import aggregate_gbt, predict_raw
scores = aggregate_gbt(predict_raw(forest, codes[:8].astype(np.float32)), forest)
print("first scores:", np.round(scores[:, 0], 3))
