"""Donation aliasing contract (ISSUE 5): in-place state must never be
observable as mutated caller inputs or stale engine reads.

The contract under test:

  * donated and non-donated solves are **bit-identical** across methods x
    semirings (donation changes buffer lifetime, never values);
  * ``solve(h_numpy)`` auto-donates its private conversion copy — the
    caller's host array is untouched;
  * ``solve(h_jax, donate=True)`` consumes the input: subsequent reads
    raise (jax deleted-buffer error) rather than returning garbage, and
    ``donate=False`` (or the auto default) leaves it intact;
  * ``DynamicAPSP`` (donate=True default) never lets a pre-update ``dist``
    handle read stale data — it either still equals its snapshot (backend
    ignored donation) or raises on read (buffer consumed).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from oracle import assert_bit_equal, generate

from repro.core import solve, solve_batch
from repro.core.dynamic import DynamicAPSP
from repro.core.graphgen import generate_edge_updates, generate_np


def _deleted(arr) -> bool:
    try:
        np.asarray(arr)
        return False
    except RuntimeError:
        return True


@pytest.mark.parametrize("method,kw", [
    ("blocked_fw", {"block_size": 16}),
    ("rkleene", {"base": 16}),
])
@pytest.mark.parametrize("semiring", ["tropical", "bottleneck"])
@pytest.mark.parametrize("with_pred", [False, True])
def test_donated_solve_bit_equal(method, kw, semiring, with_pred, rng):
    h = generate(rng, 37, semiring)
    r0 = solve(h, method=method, with_pred=with_pred, semiring=semiring,
               donate=False, **kw)
    r1 = solve(h, method=method, with_pred=with_pred, semiring=semiring,
               donate=True, **kw)
    assert_bit_equal(np.asarray(r1.dist), np.asarray(r0.dist),
                     f"{method}/{semiring}")
    if with_pred:
        assert_bit_equal(np.asarray(r1.pred), np.asarray(r0.pred),
                         f"{method}/{semiring} pred")


def test_numpy_input_never_mutated(rng):
    h = generate(rng, 40, "tropical")
    pristine = h.copy()
    solve(h, method="blocked_fw", block_size=16)          # auto-donate path
    solve(h, method="blocked_fw", block_size=16, donate=True)
    solve(h, method="rkleene", base=16, donate=True)
    assert_bit_equal(h, pristine, "caller's numpy array")


def test_jax_input_donation_semantics(rng):
    h = generate(rng, 40, "tropical")
    hj = jnp.asarray(h)
    # auto (donate=None): jax input is NOT consumed
    solve(hj, method="blocked_fw", block_size=16)
    assert not _deleted(hj)
    assert_bit_equal(np.asarray(hj), h, "auto-donate left input intact")
    # forced donation consumes the buffer: reads raise, never stale data
    solve(hj, method="blocked_fw", block_size=16, donate=True)
    assert _deleted(hj), "donated input must be deleted, not silently alive"


def test_solve_batch_donation(rng):
    mats = [generate(rng, n, "tropical") for n in (17, 24, 31)]
    r0 = solve_batch(mats, method="blocked_fw", block_size=16, donate=False)
    r1 = solve_batch(mats, method="blocked_fw", block_size=16)  # auto
    assert_bit_equal(np.asarray(r1.dist), np.asarray(r0.dist), "batch")
    for i, m in enumerate(mats):
        # inputs are host arrays: packing copied them, nothing mutated
        assert np.isfinite(m).any() and m.shape == (r0.sizes[i],) * 2


@pytest.mark.parametrize("with_pred", [False, True])
def test_bucket_results_written_into_staging_in_place(with_pred):
    """Each bucket's program aliases the donated staging stacks to its
    outputs and consumes them; its rows land at the offset, the rest of
    the staging stays inert."""
    from repro.analysis.donation import parse_input_output_alias
    from repro.core.apsp import (BATCH_METHODS, METHODS, _solve_into,
                                 _staging)
    from repro.core.semiring import TROPICAL

    def staging():
        return _staging(8, 16, jnp.dtype(jnp.float32), TROPICAL, with_pred)

    h = np.full((2, 8, 8), np.inf, np.float32)
    h[:, 0, 1] = 3.0
    h[:, np.arange(8), np.arange(8)] = 0.0
    kw = dict(with_pred=with_pred, method="blocked_fw",
              solvers=(METHODS["blocked_fw"], BATCH_METHODS["blocked_fw"]),
              semiring=TROPICAL, dtype=jnp.dtype(jnp.float32),
              solver_kw=(("block_size", 8),))
    hlo = _solve_into.lower(staging(), jnp.asarray(h), 5, **kw).compile()
    assert set(parse_input_output_alias(hlo.as_text())) == set(
        range(1 + with_pred))
    before = staging()
    out = _solve_into(before, jnp.asarray(h), 5, **kw)
    assert all(_deleted(s) for s in before)
    ref = solve_batch(h, method="blocked_fw", block_size=8,
                      with_pred=with_pred)
    fresh = staging()
    for got, want, blank in zip(out, (ref.dist, ref.pred), fresh):
        got, blank = np.asarray(got), np.asarray(blank)
        assert_bit_equal(got[5:7, :8, :8], np.asarray(want), "bucket rows")
        assert_bit_equal(got[5:7, 8:], blank[5:7, 8:], "inert rows")
        assert_bit_equal(got[[0, 1, 2, 3, 4, 7]], blank[[0, 1, 2, 3, 4, 7]],
                         "other slots")


def test_dynamic_engine_no_stale_reads(rng):
    g = generate_np(rng, 36)
    eng = DynamicAPSP(g.h, with_pred=True, block_size=16)          # donate=True
    ref = DynamicAPSP(g.h, with_pred=True, block_size=16, donate=False)
    for _ in range(4):
        before = eng.dist
        snapshot = np.asarray(before).copy()
        u, v, w = generate_edge_updates(rng, eng._h, 5)
        eng.update(u, v, w)
        ref.update(u, v, w)
        # the pre-update handle either raises (consumed) or still shows the
        # exact pre-update values — never silently-mutated data
        if not _deleted(before):
            assert_bit_equal(np.asarray(before), snapshot, "stale handle")
        assert_bit_equal(np.asarray(eng.dist), np.asarray(ref.dist),
                         "donated vs non-donated dist")
        assert_bit_equal(np.asarray(eng.pred), np.asarray(ref.pred),
                         "donated vs non-donated pred")


def test_dynamic_engine_worsening_donation(rng):
    g = generate_np(rng, 32)
    eng = DynamicAPSP(g.h, with_pred=True, block_size=16)
    ref = DynamicAPSP(g.h, with_pred=True, block_size=16, donate=False)
    rng2 = np.random.default_rng(7)
    for _ in range(3):
        u, v, w = generate_edge_updates(rng2, eng._h, 4, worsen_frac=0.7)
        i1 = eng.update(u, v, w)
        i2 = ref.update(u, v, w)
        assert i1["path"] == i2["path"]
        assert_bit_equal(np.asarray(eng.dist), np.asarray(ref.dist),
                         i1["path"])
    r = solve(eng._h, method="blocked_fw", block_size=16, with_pred=True)
    assert_bit_equal(np.asarray(eng.dist), np.asarray(r.dist),
                     "vs full re-solve")
