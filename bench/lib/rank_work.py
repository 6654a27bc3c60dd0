"""The work a LambdaMART lambda pass needs, counted from shapes (see
``work.py`` for what such a count holds).

A pass at NDCG@k reads, for every row, its float32 score and its grade (0
to 4 fits a byte), and writes its float32 gradient and hessian. A pair
whose two members both rank below k has |d_i - d_j| = 0 and adds nothing,
so the pairs it must evaluate are those with a member in the top k: a
query of m rows has kk * m - kk (kk + 1) / 2 of them, kk = min(k, m). Each
costs at least one operation. Sorting by score and the pairs' own
arithmetic are left out, so the count stays below what any implementation
does.
"""
from __future__ import annotations

from work import F32, Work

GRADE_BYTES = 1


def lambda_pass(rows: int, pairs: int) -> Work:
    return Work(ops=float(pairs),
                bytes=float(rows) * (F32 + GRADE_BYTES + 2 * F32))


def top_k_pairs(sizes, k: int) -> int:
    """Pairs with a member in the top ``k`` over queries of ``sizes``."""
    total = 0
    for m in sizes:
        kk = min(k, int(m))
        total += kk * int(m) - kk * (kk + 1) // 2
    return total
