"""Distributed DF training benchmark (paper §3.9 / Guillame-Bert & Teytaud):
per-level communication volume vs N (the key claim: candidate traffic is
independent of the number of examples; partitions are bit-packed), using the
single-process simulation backend."""
from __future__ import annotations

import numpy as np

from repro.core.distributed import DistGBTConfig, SimulatedCluster


def run(verbose: bool = True) -> dict:
    cfg = DistGBTConfig(max_depth=4, n_bins=64)
    rows = {}
    for N in (512, 2048, 8192):
        rng = np.random.default_rng(0)
        codes = rng.integers(0, 64, (N, 16)).astype(np.uint8)
        stats = np.stack([rng.normal(size=N), np.ones(N), np.ones(N)], 1)
        sim = SimulatedCluster(codes, 8, cfg, seed=0)
        sim.grow_tree(stats)
        bitmap = N // 8 * cfg.max_depth
        candidates = sim.traffic_bytes - bitmap
        rows[N] = {"total_bytes": sim.traffic_bytes,
                   "candidate_bytes": candidates,
                   "bitmap_bytes": bitmap,
                   "float_mask_bytes": N * 4 * cfg.max_depth}
        if verbose:
            r = rows[N]
            print(f"  N={N:6d}: candidates={r['candidate_bytes']:7d}B "
                  f"(N-independent)  bitmap={r['bitmap_bytes']:7d}B "
                  f"(vs {r['float_mask_bytes']}B unpacked = "
                  f"{r['float_mask_bytes'] / r['bitmap_bytes']:.0f}x)", flush=True)
    return rows


def main():
    out = run(verbose=False)
    print("n_examples,candidate_bytes,bitmap_bytes,float_mask_bytes")
    for n, r in out.items():
        print(f"{n},{r['candidate_bytes']},{r['bitmap_bytes']},{r['float_mask_bytes']}")


if __name__ == "__main__":
    from repro.jax_cache import configure_compile_cache
    configure_compile_cache()
    main()
