"""The paper's random graph generator ``G = f(V, rho, alpha)`` (paper §3.4).

Procedure (faithful): sample a probability matrix P ~ U[0,1]^{V×V}; scale by
the density knob rho; Bernoulli-threshold into an adjacency matrix A; assign
integer edge costs uniform in [1, alpha] (the paper writes [0, alpha] but
also stipulates "no edge with 0 cost, except for self-loops", so the live
range is [1, alpha]); zero the diagonal.  Non-edges get +inf in the cost
matrix H used by the solvers.

The paper samples rho uniformly from [0, 100] — we read that as a percentage
and use p_edge = clip(rho/100 * P, 0, 1), which reproduces the full density
sweep of paper Fig 9.

Two backends: a jax one (jit-able, used by tests/examples) and a numpy one
(used by the CPU benchmark harness so graph generation never touches the
device under test, mirroring the paper's methodology).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "GraphSample",
    "generate",
    "generate_batch",
    "generate_edge_updates",
    "generate_np",
    "paper_corpus",
    "graph_stats",
]

INF = np.inf


@dataclass
class GraphSample:
    """One generated graph: dense cost matrix + bookkeeping for Fig 9."""

    h: np.ndarray          # (V, V) float32 cost matrix, inf = no edge, diag 0
    adjacency: np.ndarray  # (V, V) bool
    n_nodes: int
    n_edges: int
    rho: float
    alpha: int

    @property
    def density(self) -> float:
        v = self.n_nodes
        max_edges = max(v * (v - 1), 1)
        return self.n_edges / max_edges


def generate(
    key: jax.Array,
    n_nodes: int,
    *,
    rho: Optional[float] = None,
    alpha: int = 100,
) -> Tuple[jax.Array, jax.Array]:
    """jax backend: returns (H, adjacency). rho=None samples rho ~ U[0,100]."""
    k_rho, k_p, k_bern, k_cost = jax.random.split(key, 4)
    if rho is None:
        rho = jax.random.uniform(k_rho, (), minval=0.0, maxval=100.0)
    p = jax.random.uniform(k_p, (n_nodes, n_nodes))
    p_edge = jnp.clip(rho / 100.0 * p, 0.0, 1.0)
    adj = jax.random.uniform(k_bern, (n_nodes, n_nodes)) < p_edge
    cost = jax.random.randint(k_cost, (n_nodes, n_nodes), 1, alpha + 1).astype(jnp.float32)
    h = jnp.where(adj, cost, jnp.inf)
    eye = jnp.eye(n_nodes, dtype=bool)
    h = jnp.where(eye, 0.0, h)
    adj = jnp.where(eye, False, adj)
    return h, adj


def generate_batch(
    key: jax.Array,
    sizes,
    *,
    n_max: Optional[int] = None,
    rho: Optional[float] = None,
    alpha: int = 100,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """jax backend, batched: a ragged corpus as one (G, N, N) stack.

    ``sizes`` lists each graph's true node count; graphs are generated at
    ``n_max`` (default: max(sizes)) and masked down, so the stack feeds
    ``apsp.solve_batch`` directly: entries outside a graph's (size, size)
    block are inf off-diagonal / 0 diagonal phantom nodes.  ``rho=None``
    samples an independent rho ~ U[0, 100] per graph (the paper's corpus
    recipe).  Returns (H, adjacency, sizes).
    """
    sizes = jnp.asarray(sizes, jnp.int32)
    g = sizes.shape[0]
    n = int(n_max) if n_max is not None else int(np.max(np.asarray(sizes)))
    keys = jax.random.split(key, g)
    h, adj = jax.vmap(lambda k: generate(k, n, rho=rho, alpha=alpha))(keys)
    node = jnp.arange(n)
    valid = (node[None, :, None] < sizes[:, None, None]) & (
        node[None, None, :] < sizes[:, None, None]
    )
    eye = jnp.eye(n, dtype=bool)[None]
    h = jnp.where(valid & ~eye, h, jnp.where(eye, 0.0, jnp.inf))
    adj = adj & valid
    return h, adj, sizes


def generate_np(
    rng: np.random.Generator,
    n_nodes: int,
    *,
    rho: Optional[float] = None,
    alpha: int = 100,
) -> GraphSample:
    """numpy backend (benchmark harness / NetworkX baseline feed)."""
    if rho is None:
        rho = float(rng.uniform(0.0, 100.0))
    p = rng.uniform(size=(n_nodes, n_nodes))
    p_edge = np.clip(rho / 100.0 * p, 0.0, 1.0)
    adj = rng.uniform(size=(n_nodes, n_nodes)) < p_edge
    np.fill_diagonal(adj, False)
    cost = rng.integers(1, alpha + 1, size=(n_nodes, n_nodes)).astype(np.float32)
    h = np.where(adj, cost, np.float32(INF)).astype(np.float32)
    np.fill_diagonal(h, 0.0)
    return GraphSample(
        h=h,
        adjacency=adj,
        n_nodes=n_nodes,
        n_edges=int(adj.sum()),
        rho=rho,
        alpha=alpha,
    )


def generate_edge_updates(
    rng: np.random.Generator,
    h: np.ndarray,
    k: int,
    *,
    worsen_frac: float = 0.0,
    alpha: int = 100,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """k random tropical edge updates ``(u, v, w)`` against cost matrix h.

    By default every update is guaranteed not-worsening — lower an existing
    edge (integer-valued, floor 1) or insert a new one with cost in
    [1, alpha) — i.e. the streaming load shape the dynamic engine's exact
    rank-k path covers.  ``worsen_frac`` > 0 additionally worsens that
    fraction of the batch: each such entry is redrawn onto an existing
    out-edge of its source (a "worsened" non-edge would be an insert) and
    gets cost + [100, 300), exercising the bounded re-solve path.  Shared
    by the dynamic differential tests, the incremental benchmark, and the
    serve mutate stream so all three stay on one load definition.  Never
    emits self-loops.
    """
    n = h.shape[0]
    u = rng.integers(0, n, k).astype(np.int32)
    v = ((u + rng.integers(1, n, k)) % n).astype(np.int32)
    old = h[u, v]
    w = np.where(
        np.isfinite(old),
        np.maximum(1.0, np.floor(old) - rng.integers(1, 20, k)),
        rng.integers(1, alpha, k),
    ).astype(np.float32)
    if worsen_frac > 0.0:
        worsen = rng.uniform(size=k) < worsen_frac
        for i in np.flatnonzero(worsen):
            out = np.flatnonzero(np.isfinite(h[u[i]]))
            out = out[out != u[i]]
            if out.size:
                v[i] = out[rng.integers(0, out.size)]
        old = h[u, v]
        w = np.where(
            worsen,
            np.where(np.isfinite(old), old, 1.0)
            + rng.integers(100, 300, k).astype(np.float32),
            w,
        ).astype(np.float32)
    return u, v, w


def paper_corpus(
    seed: int = 0,
    n_graphs: int = 1000,
    v_min: int = 4,
    v_max: int = 1000,
    alpha: int = 100,
):
    """The paper's benchmark corpus: ``n_graphs`` graphs, V ~ U[v_min, v_max],
    rho ~ U[0,100], alpha=100 — yielded sorted by edge count (paper §4)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(v_min, v_max + 1, size=n_graphs)
    graphs = [generate_np(rng, int(v), alpha=alpha) for v in sizes]
    graphs.sort(key=lambda g: g.n_edges)
    return graphs


def graph_stats(graphs) -> dict:
    """Fig 9 statistics: sqrt(edges), nodes, densities."""
    return {
        "n_nodes": np.array([g.n_nodes for g in graphs]),
        "sqrt_edges": np.sqrt(np.array([g.n_edges for g in graphs], dtype=np.float64)),
        "density": np.array([g.density for g in graphs]),
    }
