"""The plain reference for route tables: is each predecessor a witness?

It imports nothing of the program.  Distances come from host Dijkstra
(``reference.rows``) on the arc list the seed drew; arc weights from the
same list, the smallest weight per (k, j).  For a checked row i and every
column j:

* j reachable and j != i: k = pred[i][j] has an arc (k, j), and
  ref[i][k] + w(k, j) == ref[i][j] exactly;
* j unreachable: pred[i][j] == -1.

With every weight positive (>= 100 in the road configurations) each step
back from j lowers the distance, so the rule and exact distances prove
that walking the predecessors back from j reaches i along a shortest route.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def arc_table(n: int, src, dst, w) -> Tuple[np.ndarray, np.ndarray]:
    """Sorted arc keys ``k * n + j`` and the smallest weight of each."""
    key = np.asarray(src, np.int64) * n + np.asarray(dst, np.int64)
    order = np.argsort(key, kind="stable")
    key, ww = key[order], np.asarray(w, np.float64)[order]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    return key[first], np.minimum.reduceat(ww, first)


def arc_weights(table, k: np.ndarray, j: np.ndarray, n: int) -> np.ndarray:
    """w(k, j) for each pair, ``inf`` where there is no such arc."""
    keys, ww = table
    q = k.astype(np.int64) * n + j.astype(np.int64)
    at = np.minimum(np.searchsorted(keys, q), keys.size - 1)
    return np.where(keys[at] == q, ww[at], np.inf)


def wrong_routes(ref: np.ndarray, sources: Sequence[int], pred: np.ndarray,
                 table) -> int:
    """Entries of ``pred`` (one row per source) that break the witness rule
    against the exact distance rows ``ref``."""
    ref = np.asarray(ref, np.float64)
    pred = np.asarray(pred, np.int64)
    n = ref.shape[1]
    wrong = 0
    for r, i in enumerate(sources):
        d, p = ref[r], pred[r]
        reach = np.isfinite(d)
        reach[i] = False
        wrong += int(np.sum(~np.isfinite(d) & (p != -1)))
        j = np.flatnonzero(reach)
        k = p[j]
        ok = (k >= 0) & (k < n)
        kk = np.where(ok, k, 0)
        via = d[kk] + arc_weights(table, kk, j, n)
        wrong += int(np.sum(~ok | (via != d[j])))
    return wrong
