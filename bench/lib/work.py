"""The work the algorithms need, counted from shapes, and the least time
the chip could take for it.

These counts are what the roofline and ``mfu`` shares divide by. They count
the same work whatever implements it, and only work that no implementation
of the algorithm can skip, so a share computed from them is at most 100%
of a chip that runs at its published peaks.

* A histogram level of level-wise tree growth reads, for every row in a
  node that splits at that depth, its F one-byte bin codes (255 bins need
  8 bits), its S float32 statistics and its int32 node id, and does
  F * S accumulates. Binary GBT with YDF's default gain needs S = 3
  statistics: gradient, hessian (for the leaf value) and count (for the
  gain). The gain scan and routing are left out, so the count stays below
  what any implementation does.
* Scoring a row reads its F one-byte codes and writes ``out_dim`` float32
  values, and each tree costs one compare per level of its depth. The
  forest's node tables are read once per call: a feature byte and a
  threshold byte for each numerical node, a 256-bit mask for each
  categorical one, and a float32 value per leaf.

The least time is the larger of operations over peak operations per second
and bytes over peak bytes per second; ``bound_by`` says which.
"""
from __future__ import annotations

from dataclasses import dataclass

GBT_STATS = 3          # gradient, hessian, count
CODE_BYTES = 1
F32 = 4
NODE_ID_BYTES = 4
MASK_BYTES = 32        # 256 categories, one bit each


@dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def least_s(self, peaks: dict) -> float:
        return max(self.ops / peaks["bf16_flops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])

    def bound_by(self, peaks: dict) -> str:
        return ("compute" if self.ops / peaks["bf16_flops_per_s"]
                >= self.bytes / peaks["hbm_bytes_per_s"] else "memory")


NOTHING = Work(0.0, 0.0)


def histogram_level(rows: int, features: int,
                    stats: int = GBT_STATS) -> Work:
    """One depth of level-wise growth over ``rows`` rows in splitting
    nodes."""
    return Work(ops=float(rows) * features * stats,
                bytes=float(rows) * (features * CODE_BYTES + stats * F32
                                     + NODE_ID_BYTES))


def tree(level_rows, features: int) -> Work:
    """A tree whose depth d splits ``level_rows[d]`` rows."""
    w = NOTHING
    for rows in level_rows:
        w = w + histogram_level(rows, features)
    return w


def scoring(rows: int, features: int, trees: int, depth: int,
            out_dim: int = 1, categorical_nodes: int = 0) -> Work:
    """One call that scores ``rows`` rows on ``trees`` complete trees of
    ``depth``; ``categorical_nodes`` of the forest's internal nodes test a
    category mask."""
    internal = trees * (2 ** depth - 1)
    leaves = trees * 2 ** depth
    table = ((internal - categorical_nodes) * 2 * CODE_BYTES
             + categorical_nodes * (CODE_BYTES + MASK_BYTES)
             + leaves * F32)
    return Work(ops=float(rows) * trees * depth,
                bytes=float(rows) * (features * CODE_BYTES + out_dim * F32)
                + table)


def share_pct(work: Work, seconds: float, peaks: dict) -> float | None:
    """The least time of ``work`` over ``seconds`` it took, in percent;
    None when nothing ran."""
    if seconds <= 0 or work.ops <= 0 and work.bytes <= 0:
        return None
    return 100.0 * work.least_s(peaks) / seconds
