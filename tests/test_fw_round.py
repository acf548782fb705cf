"""Fused multi-stage round (ISSUE 5 bandwidth-optimal core) differential
suite: fused vs legacy split round, Pallas fw_round kernel vs chunked-XLA
fallback, batched lowering, predecessor validity, and the R-Kleene
multiple-of-base pad/split rule.

Bit-exactness notes: graphgen weights are integer-valued floats, so every
candidate path sum is exact in f32 and any two correct ⊕-selections agree
bit-for-bit — which is what lets fused-vs-split and pallas-vs-xla assert
``array_equal`` rather than allclose (the established convention from the
PR 2/3 parity suites).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from oracle import assert_bit_equal, generate, np_closure

from repro.core import generate_np, solve, validate_tree
from repro.core.blocked_fw import blocked_fw, blocked_fw_batch
from repro.core.rkleene import padded_size, rkleene, split_point
from repro.kernels import ops
from repro.kernels.fw_round import (_col_tile, _round_tile, fw_round_pallas,
                                    fw_round_pred_pallas)
from repro.kernels.minplus_xla import fw_round_xla


def _with_backend(monkeypatch, name):
    monkeypatch.setenv("REPRO_KERNELS", name)
    assert ops.backend() == name


@pytest.mark.parametrize("n,block", [(24, 16), (53, 16), (64, 32)])
def test_fused_round_matches_split_and_oracle(n, block, rng):
    g = generate_np(rng, n)
    ref = np_closure(g.h).astype(np.float32)
    d_fused, _ = blocked_fw(jnp.asarray(g.h), block_size=block,
                            round_mode="fused")
    d_split, _ = blocked_fw(jnp.asarray(g.h), block_size=block,
                            round_mode="split")
    assert_bit_equal(np.asarray(d_fused), ref, "fused vs oracle")
    assert_bit_equal(np.asarray(d_fused), np.asarray(d_split),
                     "fused vs split")


@pytest.mark.parametrize("semiring", ["tropical", "bottleneck", "reliability",
                                      "boolean"])
def test_fused_round_semiring_sweep(semiring, rng):
    h = generate(rng, 40, semiring)
    ref = np_closure(h, semiring)
    d, _ = blocked_fw(jnp.asarray(h), block_size=16, round_mode="fused",
                      semiring=semiring)
    assert np.allclose(np.asarray(d), ref, equal_nan=True), semiring


def test_fused_round_pred_tree_valid(rng):
    g = generate_np(rng, 57)
    d, p = blocked_fw(jnp.asarray(g.h), block_size=16, round_mode="fused",
                      with_pred=True)
    ds, _ = blocked_fw(jnp.asarray(g.h), block_size=16, round_mode="split",
                       with_pred=True)
    assert_bit_equal(np.asarray(d), np.asarray(ds), "pred-mode dist")
    assert validate_tree(g.h, np.asarray(d), np.asarray(p))


def test_fused_round_batch_matches_per_graph(rng):
    hs = jnp.stack([jnp.asarray(generate_np(rng, 48).h) for _ in range(3)])
    db, _ = blocked_fw_batch(hs, block_size=16, round_mode="fused")
    for i in range(3):
        di, _ = blocked_fw(hs[i], block_size=16, round_mode="fused")
        assert_bit_equal(np.asarray(db[i]), np.asarray(di), f"graph {i}")


def test_fw_round_kernel_parity_interpret_vs_xla(rng, monkeypatch):
    """The Pallas fw_round kernel (one grid dispatch, scalar-prefetched
    pivot index) and the chunked-XLA fallback agree bit-for-bit — same
    candidate sums, selective ⊕ is order-insensitive — including on float
    (non-integer) weights and across every pivot offset."""
    n, b = 48, 16
    a = rng.uniform(1, 100, size=(n, n)).astype(np.float32)
    h = np.where(rng.uniform(size=(n, n)) < 0.4, np.inf, a).astype(np.float32)
    np.fill_diagonal(h, 0.0)
    d = jnp.asarray(h)
    for t in range(n // b):
        out = {}
        for bk in ("interpret", "xla"):
            _with_backend(monkeypatch, bk)
            out[bk] = np.asarray(
                ops.fw_round(d, jnp.int32(t * b), block_size=b)
            )
        assert_bit_equal(out["interpret"], out["xla"], f"pivot {t}")
        d = jnp.asarray(out["xla"])  # advance the round state


def test_fw_round_kernel_batched(rng, monkeypatch):
    _with_backend(monkeypatch, "interpret")
    hs = jnp.stack([jnp.asarray(generate_np(rng, 32).h) for _ in range(2)])
    got = np.asarray(ops.fw_round(hs, jnp.int32(16), block_size=16))
    _with_backend(monkeypatch, "xla")
    ref = np.asarray(ops.fw_round(hs, jnp.int32(16), block_size=16))
    assert_bit_equal(got, ref, "batched fw_round")


def _stripe_grids(fn, *specs):
    """Grids of the Pallas calls ``fn`` traces to, outermost first."""
    def calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield tuple(e.params["grid_mapping"].grid)
            for v in e.params.values():
                sub = getattr(v, "jaxpr", None)
                if sub is not None:
                    yield from calls(getattr(sub, "jaxpr", sub))
    args = [jax.ShapeDtypeStruct(s, d) for s, d in specs]
    return list(calls(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("n,b,want", [
    (8192, 256, 1024),                    # road8k.cold's own shape
    (16384, 128, 1024), (16384, 256, 1024), (16384, 512, 1024),
    (16384, 1024, 512),                   # the wide tiles would not fit VMEM
    (2048, 256, 1024), (1536, 256, 512), (384, 128, 128),
    (16, 16, 16), (32, 32, 32), (64, 64, 64),   # zinc's bucket edges
])
def test_round_tile_width(n, b, want):
    assert _round_tile(n, b, 4) == want
    # the stripe grid fw_round_pallas builds walks n // want column tiles
    f32, i32 = jnp.float32, jnp.int32
    grids = _stripe_grids(
        lambda d, o: fw_round_pallas(d, o, block_size=b),
        ((n, n), f32), ((), i32))
    assert grids[-1] == (1, n // b, n // want)


@pytest.mark.parametrize("n,b,want", [
    (8192, 256, 512), (16384, 256, 512), (2048, 256, 512),
    (16, 16, 16), (32, 32, 32), (64, 64, 64),
])
def test_route_round_keeps_its_tile(n, b, want):
    # fw_round_pred keeps the 512-lane tile: its witness plane halves the
    # band, and add, compare, min and select bound it, not the permutes
    assert _col_tile(n) == want
    f32, i32 = jnp.float32, jnp.int32
    grids = _stripe_grids(
        lambda d, p, o: fw_round_pred_pallas(d, p, o, block_size=b),
        ((n, n), f32), ((n, n), i32), ((), i32))
    assert grids[-1] == (1, n // b, n // want)


@pytest.mark.parametrize("g", [1, 2])
def test_fw_round_wide_tile_parity_interpret_vs_xla(g, rng):
    """At n=2048, B=256 the stripe grid takes two 1024-lane tiles a stripe
    and folds 32-row bands; the pivot (t=5) sits in the second tile.  The
    kernel agrees bit for bit with the chunked-XLA fallback on float
    weights, as a single matrix and as a stack."""
    n, b, o = 2048, 256, 5 * 256
    a = rng.uniform(1, 100, size=(g, n, n)).astype(np.float32)
    h = np.where(rng.uniform(size=(g, n, n)) < 0.99, np.inf, a)
    for x in h:
        np.fill_diagonal(x, 0.0)
    d = jnp.asarray(h if g > 1 else h[0])
    got = fw_round_pallas(d, jnp.int32(o), block_size=b, interpret=True)
    ref = jax.vmap(lambda x: fw_round_xla(x, jnp.int32(o), block_size=b))(
        jnp.asarray(h))
    assert_bit_equal(np.asarray(got), np.asarray(ref if g > 1 else ref[0]),
                     f"wide-tile fw_round, g={g}")


@pytest.mark.parametrize("g", [1, 2])
def test_blocked_fw_wide_tile_matches_oracle(g, rng, monkeypatch):
    # a whole solve on the Pallas kernels (interpret) at n=1024, B=256,
    # where each round's stripe is one 1024-lane tile: four rounds
    _with_backend(monkeypatch, "interpret")
    hs = [generate_np(rng, 1024).h for _ in range(g)]
    if g == 1:
        d, _ = blocked_fw(jnp.asarray(hs[0]), block_size=256,
                          round_mode="fused")
        got = [np.asarray(d)]
    else:
        d, _ = blocked_fw_batch(jnp.asarray(np.stack(hs)), block_size=256,
                                round_mode="fused")
        got = list(np.asarray(d))
    for i, (x, h) in enumerate(zip(got, hs)):
        assert_bit_equal(x, np_closure(h).astype(np.float32), f"graph {i}")


def test_blocked_fw_end_to_end_backend_parity(rng, monkeypatch):
    """Whole fused-round solves agree across backends (the PR 3 parity
    convention extended to the new hot loop)."""
    g = generate_np(rng, 41)
    out = {}
    for bk in ("interpret", "xla"):
        _with_backend(monkeypatch, bk)
        jax.clear_caches()
        out[bk] = np.asarray(
            blocked_fw(jnp.asarray(g.h), block_size=16, round_mode="fused")[0]
        )
    jax.clear_caches()
    assert_bit_equal(out["interpret"], out["xla"], "solve parity")


def test_round_mode_validation(rng):
    g = generate_np(rng, 16)
    with pytest.raises(ValueError, match="round_mode"):
        blocked_fw(jnp.asarray(g.h), block_size=8, round_mode="bogus")


# -- R-Kleene pad/split rule (the N=384 anomaly fix) ------------------------

def test_rkleene_pad_split_rule():
    assert padded_size(384, 64) == 384            # was 512 under pow-2
    assert padded_size(100, 64) == 128
    assert padded_size(63, 64) == 64
    assert split_point(384, 64) == 192
    assert split_point(320, 64) == 192            # uneven halves allowed
    assert split_point(128, 64) == 64


@pytest.mark.parametrize("n", [24, 96, 100, 160, 192])
def test_rkleene_non_pow2_sizes_vs_oracle(n, rng):
    h = generate(rng, n, "tropical")
    ref = np_closure(h)
    d, _ = rkleene(jnp.asarray(h), base=32)
    assert np.allclose(np.asarray(d), ref, equal_nan=True), n
    dp, pp = rkleene(jnp.asarray(h), base=32, with_pred=True)
    assert np.allclose(np.asarray(dp), ref, equal_nan=True), n
    assert validate_tree(h, np.asarray(dp), np.asarray(pp))


def test_rkleene_uneven_split_matches_solve(rng):
    """160 = 5 leaves of 32: recursion splits 96/64 then 64/32 — distances
    must match the blocked solver exactly (integer weights)."""
    g = generate_np(rng, 160)
    d_rk, _ = rkleene(jnp.asarray(g.h), base=32)
    d_bf = solve(g.h, method="blocked_fw", block_size=32).dist
    assert_bit_equal(np.asarray(d_rk), np.asarray(d_bf), "rkleene vs blocked")
