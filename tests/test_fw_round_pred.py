"""The fused predecessor round kernel (``kernels.fw_round.fw_round_pred``)
in interpret mode against the XLA path, its reference.

Distances must equal the XLA path's bit for bit (integer weights: every
candidate sum is exact) and NetworkX's Dijkstra; predecessors must form a
valid witness tree (``paths.validate_tree``) and read -1 exactly where no
path exists.  Ties may break differently from the XLA path's composed
argmin round, so predecessors are not compared entry by entry.  Graphs:
random integer weights, a unit-weight lattice full of equal-length routes,
and two components joined one way only (unreachable pairs).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oracle import assert_bit_equal, nx_tropical_closure

from repro.core import validate_tree
from repro.core.blocked_fw import blocked_fw, blocked_fw_batch
from repro.kernels import ops


def _random_int(rng, n):
    h = np.where(rng.uniform(size=(n, n)) < 4.0 / n,
                 rng.integers(1, 10, (n, n)), np.inf)
    np.fill_diagonal(h, 0.0)
    return h.astype(np.float32)


def _unit_lattice(rng, n):
    cols = 16
    idx = np.arange(n).reshape(-1, cols)
    h = np.full((n, n), np.inf, np.float32)
    for a, b in ((idx[:, :-1], idx[:, 1:]), (idx[:-1, :], idx[1:, :])):
        h[a.ravel(), b.ravel()] = 1.0
        h[b.ravel(), a.ravel()] = 1.0
    np.fill_diagonal(h, 0.0)
    return h


def _one_way_components(rng, n):
    """Nodes [0, n/2) and [n/2, n) strongly connected inside; arcs from the
    first half into the second only, so no second-half node reaches the
    first half."""
    h = _random_int(rng, n)
    m = n // 2
    ring = np.arange(n)
    h[ring, np.where(ring % m == m - 1, ring - m + 1, ring + 1)] = 5.0
    h[m:, :m] = np.inf
    np.fill_diagonal(h, 0.0)
    return h


GRAPHS = {"random_int": _random_int, "unit_lattice": _unit_lattice,
          "one_way_components": _one_way_components}


def _solve(fn, h, backend, monkeypatch, **kw):
    monkeypatch.setenv("REPRO_KERNELS", backend)
    jax.clear_caches()                   # the backend is read at trace time
    d, p = fn(jnp.asarray(h), with_pred=True, **kw)
    return np.asarray(d), np.asarray(p)


def _check_tree(h, d, p):
    """``validate_tree`` accepts pred[i, j] = j (the diagonal's 0 weighs
    nothing), so each witness must also be the tail of an arc into j."""
    assert validate_tree(h, d, p)
    off = ~np.eye(h.shape[0], dtype=bool)
    assert np.all(p[off & np.isinf(d)] == -1)
    ii, jj = np.nonzero(off & np.isfinite(d))
    k = p[ii, jj]
    assert np.all((k >= 0) & (k != jj)) and np.isfinite(h[k, jj]).all()


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("n,block", [(256, 64), (256, 128), (512, 64),
                                     (512, 128)])
def test_kernel_matches_xla_path(n, block, graph, monkeypatch):
    h = GRAPHS[graph](np.random.default_rng(n + block), n)
    d, p = _solve(blocked_fw, h, "interpret", monkeypatch, block_size=block)
    d_ref, _ = _solve(blocked_fw, h, "xla", monkeypatch, block_size=block)
    jax.clear_caches()
    assert_bit_equal(d, d_ref, "kernel vs xla distances")
    _check_tree(h, d, p)
    nx = nx_tropical_closure(h)
    if nx is not None:
        assert np.array_equal(d, nx.astype(np.float32))
    if graph == "one_way_components":
        assert np.all(p[n // 2:, :n // 2] == -1)


def test_kernel_batched_matches_xla_path(monkeypatch):
    rng = np.random.default_rng(3)
    hs = np.stack([GRAPHS[k](rng, 256) for k in sorted(GRAPHS)])   # G = 3
    d, p = _solve(blocked_fw_batch, hs, "interpret", monkeypatch, block_size=64)
    d_ref, _ = _solve(blocked_fw_batch, hs, "xla", monkeypatch, block_size=64)
    jax.clear_caches()
    assert_bit_equal(d, d_ref, "batched kernel vs xla distances")
    for g in range(hs.shape[0]):
        _check_tree(hs[g], d[g], p[g])


def test_one_round_distances_equal_fw_round(monkeypatch):
    """Every pivot offset of one round, on a state the rounds before left:
    the predecessor round's distances are ``fw_round``'s bit for bit."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    n, b = 256, 64
    h = _random_int(np.random.default_rng(7), n)
    d = jnp.asarray(h)
    p = jnp.where(jnp.isfinite(d), jnp.arange(n)[:, None], -1).astype(jnp.int32)
    for t in range(n // b):
        o = jnp.int32(t * b)
        want = ops.fw_round(d, o, block_size=b)
        d, p = ops.fw_round_pred(d, p, o, block_size=b)
        assert_bit_equal(np.asarray(d), np.asarray(want), f"pivot {t}")
    _check_tree(h, np.asarray(d), np.asarray(p))
