"""Path reconstruction from predecessor matrices (paper §2) + SPD features.

``pred[i, j]`` = last node before j on a shortest i->j path.  Reconstruction
walks backwards from j (paper: "backtrack along the path P starting at node
j").  Two implementations:

* ``reconstruct_path``      — host-side numpy walk, variable length.
* ``reconstruct_path_jit``  — fixed-max-length ``lax.while_loop`` version that
  stays inside jit (returns a padded path + length), for on-device serving.

``spd_features`` exposes the paper's solver as landmark
shortest-path-distance structural features (Graphormer-style) for GNNs.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "reconstruct_path",
    "reconstruct_path_jit",
    "path_cost",
    "validate_tree",
    "spd_features",
]


def reconstruct_path(pred: np.ndarray, i: int, j: int) -> Optional[List[int]]:
    """Walk pred backwards from j. Returns [i, ..., j] or None if unreachable."""
    pred = np.asarray(pred)
    if i == j:
        return [i]
    if pred[i, j] < 0:
        return None
    path = [j]
    guard = pred.shape[0] + 1
    cur = j
    while cur != i:
        cur = int(pred[i, cur])
        if cur < 0 or len(path) > guard:
            return None
        path.append(cur)
    return path[::-1]


def reconstruct_path_jit(pred: jax.Array, i, j, *, max_len: int) -> tuple:
    """Jit-compatible reconstruction: returns (path[max_len] padded with -1,
    length).  length == 0 means unreachable."""
    n = pred.shape[0]

    def cond(state):
        cur, t, _ = state
        return jnp.logical_and(cur != i, jnp.logical_and(cur >= 0, t < max_len))

    def body(state):
        cur, t, buf = state
        buf = buf.at[t].set(cur)
        return pred[i, cur], t + 1, buf

    buf0 = jnp.full((max_len,), -1, dtype=jnp.int32)
    cur, t, buf = jax.lax.while_loop(cond, body, (jnp.asarray(j, jnp.int32), 0, buf0))
    ok = cur == i
    buf = jnp.where(ok, buf.at[t].set(i), jnp.full_like(buf, -1))
    length = jnp.where(ok, t + 1, 0)
    # path is reversed (j ... i); flip the valid prefix.
    idx = jnp.arange(max_len)
    flipped = jnp.where(idx < length, buf[jnp.clip(length - 1 - idx, 0, max_len - 1)], -1)
    return flipped, length


_NP_MUL = {
    jnp.add: np.add,
    jnp.minimum: np.minimum,
    jnp.maximum: np.maximum,
    jnp.multiply: np.multiply,
}


def _np_mul(semiring):
    """Host-side ⊗ for a semiring, keyed on the instance's own ``mul`` (not
    its name, so a re-registered instance can't desync)."""
    from .semiring import get_semiring

    sr = get_semiring(semiring)
    mul = _NP_MUL.get(sr.mul)
    if mul is None:
        # custom ⊗ with no numpy twin: fall back to the jnp op (slower)
        mul = lambda a, b: np.asarray(sr.mul(a, b))
    return sr, mul


def path_cost(h: np.ndarray, path: List[int], semiring="tropical") -> float:
    """⊗-accumulated cost along an explicit path (tropical: sum of edges).

    The empty path (i == j) costs the semiring one (tropical: 0)."""
    sr, mul = _np_mul(semiring)
    cost = sr.one
    for a, b in zip(path[:-1], path[1:]):
        cost = mul(cost, h[a, b])
    return float(cost)


def validate_tree(
    h: np.ndarray, dist: np.ndarray, pred: np.ndarray, semiring="tropical"
) -> bool:
    """Invariant: every reachable dist[i,j] is witnessed by pred: walking back
    one hop satisfies dist[i,j] == dist[i,pred[i,j]] ⊗ h[pred[i,j], j]."""
    sr, mul = _np_mul(semiring)
    n = h.shape[0]
    reach = ~np.asarray(sr.is_zero(dist)) & ~np.eye(n, dtype=bool)
    ii, jj = np.nonzero(reach)
    p = pred[ii, jj]
    if np.any(p < 0):
        return False
    lhs = dist[ii, jj]
    rhs = mul(dist[ii, p], h[p, jj])
    return bool(np.allclose(lhs, rhs, rtol=1e-5, atol=1e-5))


def spd_features(h: jax.Array, landmarks: jax.Array, *, cap: float = 1e4) -> jax.Array:
    """Landmark SPD node features via the tropical solver.

    Iterates the fused one-hop min-plus relaxation ``d <- d ⊕ d ⊗ h`` over
    the landmark rows only, to fixpoint with early exit (cost
    O(L * n^2 * D) where D is the shortest-path hop diameter, <= n-1;
    full APSP would be O(n^3)).  An earlier revision ran a fixed
    ceil(log2 n) relaxations — each pass extends coverage by *one* hop, not
    doubling, so any graph with diameter > log2(n)+1 hops (e.g. a path
    graph) got wrong landmark distances.  Returns a (n, L) feature matrix
    with unreachable distances capped.
    """
    from repro.kernels import ops as _kops

    n = h.shape[0]
    d0 = h[landmarks, :]                     # (L, n) 1-hop seed distances

    def cond(state):
        _, changed, it = state
        return jnp.logical_and(changed, it < n - 1)

    def body(state):
        d, _, it = state
        z = _kops.minplus(d, h, d)           # fused relax step (one more hop)
        return z, jnp.any(z < d), it + 1

    d, _, _ = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), jnp.int32(0)))
    return jnp.minimum(d, cap).T  # lint: allow-unfused  # repro: allow-semiring-hardcode tropical-only SPD feature cap
