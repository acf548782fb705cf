"""solve_batch vs per-graph solve: exact equivalence on ragged batches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import np_floyd_warshall
from oracle import generate
from repro.core import (
    SEMIRINGS,
    generate_batch,
    generate_np,
    pad_batch,
    reconstruct_path,
    solve,
    solve_batch,
    validate_tree,
)
from repro.core.paths import path_cost

METHOD_KW = {
    "squaring": {},
    "squaring_3d": {},
    "classic": {},
    "blocked_fw": {"block_size": 16},
    "rkleene": {"base": 8},
}

RAGGED_SIZES = [4, 17, 33, 64, 100, 7, 50, 200]      # G=8, sizes 4..200


@pytest.fixture(scope="module")
def ragged_graphs():
    rng = np.random.default_rng(0)
    return [generate_np(rng, n) for n in RAGGED_SIZES]


@pytest.mark.parametrize("method", sorted(METHOD_KW))
def test_batch_matches_solve_bit_exact(method, ragged_graphs):
    res = solve_batch([g.h for g in ragged_graphs], method=method,
                      **METHOD_KW[method])
    assert res.dist.shape == (len(ragged_graphs), 200, 200)
    for i, g in enumerate(ragged_graphs):
        ref = solve(g.h, method=method, **METHOD_KW[method])
        got = np.asarray(res.unpadded(i).dist)
        assert np.array_equal(got, np.asarray(ref.dist)), (method, i)


@pytest.mark.parametrize("method", ["squaring", "classic", "blocked_fw", "rkleene"])
def test_batch_pred_matches_and_is_valid(method, ragged_graphs):
    graphs = ragged_graphs[:6]            # cap runtime; still ragged 4..100
    res = solve_batch([g.h for g in graphs], method=method, with_pred=True,
                      **METHOD_KW[method])
    for i, g in enumerate(graphs):
        ref = solve(g.h, method=method, with_pred=True, **METHOD_KW[method])
        u = res.unpadded(i)
        assert np.array_equal(np.asarray(u.dist), np.asarray(ref.dist))
        assert np.array_equal(np.asarray(u.pred), np.asarray(ref.pred))
        d, p = np.asarray(u.dist), np.asarray(u.pred)
        assert validate_tree(g.h, d, p), (method, i)
        fin = np.argwhere(np.isfinite(d) & (d > 0))
        for idx in fin[:: max(len(fin) // 5, 1)]:
            a, b = map(int, idx)
            path = reconstruct_path(p, a, b)
            assert path is not None
            assert abs(path_cost(g.h, path) - d[a, b]) < 1e-4


# edges 8, 16, 32, 32 and 64: four buckets, one of them shared
BUCKET_SIZES = [5, 9, 17, 30, 33]


@pytest.mark.parametrize("semiring", sorted(SEMIRINGS))
@pytest.mark.parametrize("method", sorted(METHOD_KW))
def test_bucketed_equals_single_stack(method, semiring):
    rng = np.random.default_rng(5)
    hs = [generate(rng, k, semiring) for k in BUCKET_SIZES]
    kw = dict(method=method, with_pred=True, semiring=semiring,
              **METHOD_KW[method])
    a = solve_batch(hs, **kw)
    b = solve_batch(hs, bucket_by_size=True, **kw)
    assert np.array_equal(np.asarray(a.dist), np.asarray(b.dist))
    assert np.array_equal(np.asarray(a.pred), np.asarray(b.pred))
    assert np.array_equal(a.sizes, b.sizes)


# the edge-32 bucket holds three graphs in four slots
FRAME_SIZES = [4, 17, 33, 9, 30, 12, 20]


@pytest.mark.parametrize("semiring,dtype,sizes,n_max", [
    ("tropical", None, FRAME_SIZES, None),
    ("bottleneck", None, FRAME_SIZES, None),
    ("tropical", jnp.bfloat16, FRAME_SIZES, None),
    ("tropical", None, [33, 5], None),        # bucket edge 64 > n = 33
    ("tropical", None, [6, 11, 19], 40),      # n_max above the largest graph
], ids=["tropical", "bottleneck", "bf16", "edge-past-n", "n_max-past-graphs"])
def test_bucketed_frame_bit_identical(semiring, dtype, sizes, n_max):
    """The bucketed frame, built and filled on the device, equals the
    single-stack result bit for bit, padding included."""
    rng = np.random.default_rng(11)
    hs = [generate(rng, k, semiring) for k in sizes]
    kw = dict(method="blocked_fw", block_size=8, with_pred=True,
              semiring=semiring, dtype=dtype, n_max=n_max)
    a = solve_batch(hs, **kw)
    b = solve_batch(hs, bucket_by_size=True, **kw)
    for r in (a, b):
        assert isinstance(r.dist, jax.Array) and isinstance(r.pred, jax.Array)
    n = max(sizes) if n_max is None else n_max
    assert b.dist.shape == b.pred.shape == (len(sizes), n, n)
    assert (b.dist.dtype, b.pred.dtype) == (a.dist.dtype, a.pred.dtype)
    assert np.array_equal(np.asarray(a.dist, np.float32),
                          np.asarray(b.dist, np.float32))
    assert np.array_equal(np.asarray(a.pred), np.asarray(b.pred))


def test_bucketed_follows_the_registry(monkeypatch):
    """A batched solver registered after a bucketed call of the same shapes
    is the one the next call runs: compiled bucket programs are keyed on
    the registry's entries, not on the method's name."""
    from repro.core import apsp

    hs = [generate(np.random.default_rng(5), k, "tropical") for k in (6, 11)]
    before = np.asarray(solve_batch(hs, bucket_by_size=True).dist)
    orig = apsp.BATCH_METHODS["blocked_fw"]

    def shifted(stack, with_pred, **kw):
        d, p = orig(stack, with_pred, **kw)
        return d + 1.0, p

    monkeypatch.setitem(apsp.BATCH_METHODS, "blocked_fw", shifted)
    after = np.asarray(solve_batch(hs, bucket_by_size=True).dist)
    assert np.array_equal(after[0, :6, :6], before[0, :6, :6] + 1.0)


def test_batch_matches_numpy_oracle(ragged_graphs):
    graphs = ragged_graphs[:5]
    res = solve_batch([g.h for g in graphs], method="classic")
    for i, g in enumerate(graphs):
        assert np.allclose(np.asarray(res.unpadded(i).dist),
                           np_floyd_warshall(g.h), equal_nan=True)


def test_pad_batch_shapes_and_padding():
    rng = np.random.default_rng(1)
    mats = [generate_np(rng, n).h for n in (3, 9, 5)]
    stack, sizes = pad_batch(mats, n_max=16)
    assert stack.shape == (3, 16, 16) and list(sizes) == [3, 9, 5]
    s = np.asarray(stack)
    assert np.array_equal(s[0, :3, :3], mats[0])
    assert np.isinf(s[0, 3:, :3]).all() and np.isinf(s[0, :3, 3:]).all()
    assert (np.diag(s[0]) == 0).all()
    # stacked input passes through
    stack2, sizes2 = pad_batch(np.stack([np.asarray(stack[i]) for i in range(3)]))
    assert stack2.shape == (3, 16, 16) and list(sizes2) == [16, 16, 16]
    with pytest.raises(ValueError):
        pad_batch(mats, n_max=8)


def test_pad_batch_reinertizes_poisoned_padding():
    """A pre-stacked input whose caller-managed padding region holds garbage
    (0.0 off-diagonal = free phantom shortcuts under tropical) must be
    re-inertized, not trusted — pre-fix this corrupted real distances."""
    rng = np.random.default_rng(3)
    n_true, edge = 6, 12
    graphs = [generate_np(rng, n_true) for _ in range(2)]
    stack = np.zeros((2, edge, edge), np.float32)      # deliberately poisoned
    for i, g in enumerate(graphs):
        stack[i, :n_true, :n_true] = g.h
    sizes = [n_true, n_true]

    packed, out_sizes = pad_batch(stack, sizes)
    s = np.asarray(packed)
    assert s.shape == (2, edge, edge) and list(out_sizes) == sizes
    assert np.isinf(s[:, n_true:, :n_true]).all()      # rows re-inertized
    assert np.isinf(s[:, :n_true, n_true:]).all()      # cols re-inertized
    assert (np.diagonal(s, axis1=1, axis2=2)[:, n_true:] == 0).all()

    res = solve_batch(stack, sizes, method="classic")
    for i, g in enumerate(graphs):
        ref = solve(g.h, method="classic")
        assert np.array_equal(np.asarray(res.unpadded(i).dist),
                              np.asarray(ref.dist)), i


def test_solve_batch_accepts_stack_and_sizes():
    rng = np.random.default_rng(2)
    mats = [generate_np(rng, n).h for n in (6, 11)]
    stack, sizes = pad_batch(mats, n_max=16)
    res = solve_batch(stack, sizes, method="squaring")
    for i, m in enumerate(mats):
        ref = solve(m, method="squaring")
        assert np.array_equal(np.asarray(res.unpadded(i).dist),
                              np.asarray(ref.dist))


def test_solve_batch_unknown_method():
    with pytest.raises(ValueError):
        solve_batch(np.zeros((2, 4, 4)), method="nope")


def test_generate_batch_invariants():
    key = jax.random.PRNGKey(3)
    sizes = [5, 12, 30]
    h, adj, out_sizes = generate_batch(key, sizes, alpha=10)
    h, adj = np.asarray(h), np.asarray(adj)
    assert h.shape == (3, 30, 30) and adj.shape == (3, 30, 30)
    assert list(np.asarray(out_sizes)) == sizes
    for i, n in enumerate(sizes):
        assert (np.diag(h[i]) == 0).all()
        assert not adj[i].diagonal().any()
        # outside the true block: phantom nodes, no edges
        assert np.isinf(h[i][n:, :][:, :n]).all() if n < 30 else True
        assert not adj[i][n:, :].any() and not adj[i][:, n:].any()
        # live entries: integer costs in [1, alpha]
        live = adj[i]
        if live.any():
            vals = h[i][live]
            assert ((vals >= 1) & (vals <= 10)).all()
            assert np.array_equal(vals, np.round(vals))
        # solver accepts the stack directly
    res = solve_batch(h, np.asarray(out_sizes), method="squaring")
    assert res.dist.shape == (3, 30, 30)
