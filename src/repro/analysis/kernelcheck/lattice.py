"""The canonical shape lattice the kernel verifier proves each kernel over.

Every kernel family gets the shapes that exercise its distinct grid
behaviours: block-aligned (multi-tile grid, no padding), non-aligned
(padding on every padded dim), batched (leading batch grid axis), the
scalar-prefetch pivot/gather paths, and non-tropical semirings (distinct
``zero`` fills prove padding inertness is generic, not an inf artifact).
Shapes are deliberately small — the simulator runs the real kernel body on
every grid point — but never degenerate: each case keeps at least one grid
axis > 1 so revisit/race structure actually exists.

``case_for_*_params`` build a :class:`Case` from an *autotuner candidate*,
so the consistency tests can prove every block size the tuner may propose
(``autotune.candidates`` / ``_row_close_candidates`` / ``_FW_ROUND_BLOCKS``)
lies inside the verified lattice.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import (
    BOTTLENECK,
    RELIABILITY,
    TROPICAL,
    Semiring,
)
from repro.kernels.ref import (
    fw_block_pred_ref,
    fw_block_ref,
    minplus_acc_argmin_ref,
    minplus_acc_ref,
    minplus_argmin_ref,
    minplus_ref,
)

__all__ = [
    "Case",
    "default_cases",
    "case_for_minplus_params",
    "case_for_fw_round_params",
    "case_for_fw_round_pred_params",
    "case_for_row_close_params",
]


@dataclass
class Case:
    """One (kernel builder, concrete invocation, oracle) triple.

    ``module``/``builder`` name an entry in that kernel module's
    ``PALLAS_BUILDERS`` (raw, unjitted); ``builder_fn`` overrides the lookup
    for synthetic builders (the mutation corpus).  ``run(fn)`` invokes the
    builder; ``expected()`` computes the oracle pytree.  ``padded`` marks
    cases that exercise padding — an oracle mismatch there is classified as
    a padding-soundness failure rather than a generic mismatch.
    """

    name: str
    module: str
    builder: str
    run: Callable
    expected: Callable
    padded: bool = False
    atol: float = 0.0
    builder_fn: Optional[Callable] = None


def _mat(rng: np.random.Generator, shape, sr: Semiring) -> jax.Array:
    """In-domain random matrix for ``sr`` (~25% "no edge" = semiring zero)."""
    no_edge = rng.uniform(size=shape) < 0.25
    if sr.name == "reliability":
        a = np.where(no_edge, 0.0, rng.uniform(0.05, 0.95, size=shape))
    elif sr.name == "bottleneck":
        a = np.where(no_edge, -np.inf, rng.uniform(1.0, 100.0, size=shape))
    elif sr.name == "boolean":
        a = np.where(no_edge, 0.0, 1.0)
    else:
        a = np.where(no_edge, np.inf, rng.uniform(1.0, 100.0, size=shape))
    return jnp.asarray(a, jnp.float32)


def _dist(rng: np.random.Generator, shape, sr: Semiring) -> jax.Array:
    """In-domain distance matrix: ``_mat`` with the ``one`` diagonal."""
    d = np.array(_mat(rng, shape, sr))  # copy: jnp views are read-only
    n = shape[-1]
    idx = np.arange(n)
    d[..., idx, idx] = sr.one
    return jnp.asarray(d)


# ---------------------------------------------------------------------------
# minplus family
# ---------------------------------------------------------------------------

def _minplus_case(
    name: str,
    m: int,
    k: int,
    n: int,
    *,
    params: dict,
    g: int = 0,
    accumulate: bool = False,
    argmin: bool = False,
    sr: Semiring = TROPICAL,
    seed: int = 0,
    padded: bool = False,
) -> Case:
    rng = np.random.default_rng(seed)
    xs = (g, m, k) if g else (m, k)
    ys = (g, k, n) if g else (k, n)
    zs = (g, m, n) if g else (m, n)
    x, y = _mat(rng, xs, sr), _mat(rng, ys, sr)
    a = _mat(rng, zs, sr) if accumulate else None
    builder = "minplus_argmin_pallas" if argmin else "minplus_pallas"

    def run(fn):
        kw = dict(params, interpret=False, semiring=sr)
        if accumulate:
            return fn(x, y, a, accumulate=True, **kw)
        return fn(x, y, **kw)

    def expected():
        if accumulate:
            ref = (minplus_acc_argmin_ref if argmin else minplus_acc_ref)
            f = lambda aa, xx, yy: ref(aa, xx, yy, sr)
            return jax.vmap(f)(a, x, y) if g else f(a, x, y)
        ref = minplus_argmin_ref if argmin else minplus_ref
        f = lambda xx, yy: ref(xx, yy, sr)
        return jax.vmap(f)(x, y) if g else f(x, y)

    return Case(
        name=name, module="minplus", builder=builder,
        run=run, expected=expected, padded=padded,
    )


def case_for_minplus_params(
    params: dict, m: int, k: int, n: int, *, g: int = 0, seed: int = 0
) -> Case:
    """Verification case for one autotune ``candidates()`` entry — the fused
    accumulate variant, the exact dispatch the tuner measures."""
    tag = ",".join(f"{key}={params[key]}" for key in sorted(params))
    return _minplus_case(
        f"minplus/autotune[{tag}]@m{m}k{k}n{n}g{g}",
        m, k, n, params=params, g=g, accumulate=True, seed=seed,
        padded=(m % params.get("bm", 8) or n % params.get("bn", 128)
                or k % params.get("bk", 8)) != 0,
    )


# ---------------------------------------------------------------------------
# fw_block family
# ---------------------------------------------------------------------------

def _fw_block_case(
    name: str, b: int, *, t: int = 0, pred: bool = False, seed: int = 0,
    sr: Semiring = TROPICAL,
) -> Case:
    rng = np.random.default_rng(seed)
    shape = (t, b, b) if t else (b, b)
    d = _dist(rng, shape, sr)
    if pred:
        # textbook init: pred[i, j] = i where an edge exists, else -1
        src = np.broadcast_to(np.arange(b)[:, None], (b, b))
        p = jnp.asarray(
            np.where(np.asarray(sr.is_zero(d)), -1, src), jnp.int32
        )

        def run(fn):
            return fn(d, p, interpret=False, semiring=sr)

        def expected():
            f = lambda dd, pp: fw_block_pred_ref(dd, pp, sr)
            return jax.vmap(f)(d, p) if t else f(d, p)

        return Case(
            name=name, module="fw_block", builder="fw_block_pred_pallas",
            run=run, expected=expected,
        )

    def run(fn):
        return fn(d, interpret=False, semiring=sr)

    def expected():
        f = lambda dd: fw_block_ref(dd, sr)
        return jax.vmap(f)(d) if t else f(d)

    return Case(
        name=name, module="fw_block", builder="fw_block_pallas",
        run=run, expected=expected,
    )


# ---------------------------------------------------------------------------
# fw_round family
# ---------------------------------------------------------------------------

def _fw_round_oracle(d: jax.Array, o: int, b: int, sr: Semiring):
    """Compose the fused round from the ref oracles, association-for-
    association with the kernel (pivot closure, then col' = col ⊗ A*, then
    stripe ⊕ col' ⊗ rowpanel) so the comparison is bit-exact."""
    dd = d if d.ndim == 3 else d[None]
    outs = []
    for gi in range(dd.shape[0]):
        D = dd[gi]
        piv = fw_block_ref(D[o:o + b, o:o + b], sr)
        stripes = []
        for i0 in range(0, D.shape[0], b):
            colp = minplus_ref(D[i0:i0 + b, o:o + b], piv, sr)
            stripes.append(minplus_acc_ref(D[i0:i0 + b, :], colp, D[o:o + b, :], sr))
        outs.append(jnp.concatenate(stripes, axis=0))
    out = jnp.stack(outs)
    return out if d.ndim == 3 else out[0]


def case_for_fw_round_params(
    block_size: int, n: int, *, o: Optional[int] = None, g: int = 0,
    seed: int = 0, sr: Semiring = TROPICAL,
) -> Case:
    """Verification case for one ``fwround|…`` block-size candidate (n must
    be a multiple of the block, as the solver guarantees by padding)."""
    assert n % block_size == 0, (n, block_size)
    b = block_size
    oo = (n - b) if o is None else o          # last pivot = worst offset
    rng = np.random.default_rng(seed)
    d = _dist(rng, (g, n, n) if g else (n, n), sr)

    def run(fn):
        return fn(d, jnp.int32(oo), block_size=b, interpret=False, semiring=sr)

    return Case(
        name=f"fw_round/b{b}@n{n}o{oo}g{g}",
        module="fw_round", builder="fw_round_pallas",
        run=run, expected=lambda: _fw_round_oracle(d, oo, b, sr),
    )


def _fw_round_pred_oracle(d, p, o: int, b: int, sr: Semiring):
    """The predecessor round from the ref oracles, witness for witness with
    the kernel: ties to the smallest k, strict improvement over the tile;
    an improving candidate takes the pivot row's P, or col′'s where k and j
    are the same node; col′'s own witnesses always come from A*."""
    dd = d if d.ndim == 3 else d[None]
    pp = p if p.ndim == 3 else p[None]
    n = dd.shape[-1]
    cols = jnp.arange(n)[None, :]
    outs, pouts = [], []
    for gi in range(dd.shape[0]):
        D, P = dd[gi], pp[gi]
        piv, ppiv = fw_block_pred_ref(D[o:o + b, o:o + b],
                                      P[o:o + b, o:o + b], sr)
        stripes, pstripes = [], []
        for i0 in range(0, n, b):
            colp, kc = minplus_argmin_ref(D[i0:i0 + b, o:o + b], piv, sr)
            pcolp = jnp.where(
                kc < 0, -1, ppiv[jnp.maximum(kc, 0), jnp.arange(b)[None, :]])
            z, ks = minplus_acc_argmin_ref(D[i0:i0 + b, :], colp,
                                           D[o:o + b, :], sr)
            k0 = jnp.maximum(ks, 0)
            via = jnp.where(o + k0 == cols,
                            jnp.take_along_axis(pcolp, k0, axis=1),
                            P[o + k0, cols])
            stripes.append(z)
            pstripes.append(jnp.where(ks < 0, P[i0:i0 + b, :], via))
        outs.append(jnp.concatenate(stripes, axis=0))
        pouts.append(jnp.concatenate(pstripes, axis=0))
    out, pout = jnp.stack(outs), jnp.stack(pouts)
    return (out, pout) if d.ndim == 3 else (out[0], pout[0])


def case_for_fw_round_pred_params(
    block_size: int, n: int, *, o: Optional[int] = None, g: int = 0,
    seed: int = 0, sr: Semiring = TROPICAL,
) -> Case:
    """Verification case for the predecessor round at one block size, with
    the solver's initial predecessors (pred[i, j] = i on an arc, -1 off)."""
    assert n % block_size == 0, (n, block_size)
    b = block_size
    oo = (n - b) if o is None else o
    rng = np.random.default_rng(seed)
    d = _dist(rng, (g, n, n) if g else (n, n), sr)
    src = jnp.broadcast_to(jnp.arange(n)[:, None], d.shape)
    p = jnp.where(sr.is_zero(d), -1, src).astype(jnp.int32)

    def run(fn):
        return fn(d, p, jnp.int32(oo), block_size=b, interpret=False,
                  semiring=sr)

    return Case(
        name=f"fw_round_pred/b{b}@n{n}o{oo}g{g}",
        module="fw_round", builder="fw_round_pred_pallas",
        run=run, expected=lambda: _fw_round_pred_oracle(d, p, oo, b, sr),
    )


# ---------------------------------------------------------------------------
# row_close family (scalar-prefetch gather)
# ---------------------------------------------------------------------------

def _gather_rows(r: int, n: int) -> np.ndarray:
    """r row ids spanning [0, n-1] — always includes both extremes (the
    bounds-critical gather indices) and a duplicate when r allows (padded
    affected-row lists repeat ids)."""
    rows = np.unique(np.linspace(0, n - 1, max(r - 1, 2)).astype(np.int32))
    while len(rows) < r:
        rows = np.append(rows, rows[len(rows) % max(len(rows), 1)])
    return rows[:r].astype(np.int32)


def case_for_row_close_params(
    params: dict, r: int, n: int, *, track: bool = False, seed: int = 0,
    sr: Semiring = TROPICAL,
) -> Case:
    """Verification case for one ``rowclose|…`` candidate (bn, bk, kc)."""
    rng = np.random.default_rng(seed)
    d = _dist(rng, (n, n), sr)
    rows = _gather_rows(r, n)
    rows_j = jnp.asarray(rows)
    tag = ",".join(f"{key}={params[key]}" for key in sorted(params))

    def run(fn):
        return fn(
            d, rows_j, track=track, interpret=False, semiring=sr, **params
        )

    def expected():
        dr = d[rows]
        if track:
            return minplus_acc_argmin_ref(dr, dr, d, sr)
        return (minplus_acc_ref(dr, dr, d, sr), None)

    return Case(
        name=f"row_close/[{tag}]@r{r}n{n}" + ("+track" if track else ""),
        module="row_close", builder="row_close_pallas",
        run=run, expected=expected, padded=True,  # bn=128 always pads cols
    )


# ---------------------------------------------------------------------------
# the default lattice (what `make analyze-kernels` proves)
# ---------------------------------------------------------------------------

def default_cases() -> List[Case]:
    small = dict(bm=8, bn=128, bk=16, kc=8)
    return [
        # -- minplus: aligned multi-tile, padded, batched, fused variants --
        _minplus_case("minplus/aligned", 16, 32, 256, params=small, seed=1),
        _minplus_case("minplus/padded", 13, 21, 130, params=small, seed=2,
                      padded=True),
        _minplus_case("minplus/batched", 16, 32, 256, params=small, g=2,
                      seed=3),
        _minplus_case("minplus/accumulate-padded", 13, 21, 130, params=small,
                      accumulate=True, seed=4, padded=True),
        _minplus_case("minplus_argmin/aligned", 16, 32, 256, params=small,
                      argmin=True, seed=5),
        _minplus_case("minplus_argmin/accumulate-padded", 13, 21, 130,
                      params=small, argmin=True, accumulate=True, seed=6,
                      padded=True),
        _minplus_case("minplus/bottleneck-padded", 13, 21, 130, params=small,
                      sr=BOTTLENECK, seed=7, padded=True),
        _minplus_case("minplus/reliability-padded", 13, 21, 130, params=small,
                      sr=RELIABILITY, seed=8, padded=True),
        # -- fw_block: single tile, tile batch, predecessor variant --
        _fw_block_case("fw_block/single", 8, seed=9),
        _fw_block_case("fw_block/batch", 8, t=3, seed=10),
        _fw_block_case("fw_block_pred/batch", 8, t=2, pred=True, seed=11),
        # -- fw_round: first and last pivot, batched, a multi-tile j sweep
        #    (three 128-wide column tiles carry the col' scratch) --
        case_for_fw_round_params(8, 16, o=0, seed=12),
        case_for_fw_round_params(8, 16, g=2, seed=13),
        case_for_fw_round_params(64, 384, o=128, g=2, seed=17),
        # -- fw_round_pred: the same points with the witness tiles --
        case_for_fw_round_pred_params(8, 16, o=0, seed=18),
        case_for_fw_round_pred_params(8, 16, g=2, seed=19),
        case_for_fw_round_pred_params(64, 384, o=128, g=2, seed=20),
        # -- row_close: gather incl. row n-1 + duplicates, track, unaligned --
        case_for_row_close_params(dict(bn=128, bk=8, kc=8), 4, 16, seed=14),
        case_for_row_close_params(dict(bn=128, bk=8, kc=8), 4, 16, track=True,
                                  seed=15),
        case_for_row_close_params(dict(bn=128, bk=8, kc=8), 5, 20, seed=16),
    ]
