"""Seeded kernel mutants — proof that the verifier has teeth.

Each mutant is a small, self-contained Pallas builder carrying exactly one
grid-level defect from the classes the verifier claims to catch; the
corpus test asserts every mutant is flagged with its expected kind and the
defect-free control verifies clean.  The mutants reuse the real kernel
arithmetic (``_minplus_body``) so the *only* deviation from a correct
kernel is the seeded one — a mutant that is wrong for a second, accidental
reason would let a regression in the intended theorem hide behind the
accidental finding.

Corpus (kind → seeded defect):

* ``race``     — the accumulation axis k declared ``"parallel"``; a
  shrunk output map ``(i, 0)`` that funnels every column block into one
  tile across a parallel axis.
* ``bounds``   — a flipped output map ``(j, i)`` on a non-square tile
  grid (also a coverage hole); an unchecked scalar-prefetch gather
  ``rows[i] + 1`` that walks off the end of the matrix.
* ``coverage`` — the flipped map's hole (the ``(1, 0)`` tile no grid
  point writes).
* ``uninit``   — the ``pl.when(program_id == 0)`` init dropped: the first
  k step accumulates into an uninitialized buffer.
* ``mismatch`` — the init left *ungated* (runs every k step, wiping the
  partial accumulation).
* ``padding``  — operands padded with ``0.0`` instead of the semiring
  zero on a non-aligned shape: padded candidates win and corrupt columns.
* ``bounds``   — an in-body ``pl.ds`` window that starts one lane past the
  block's first window and so runs off the block's end.
* ``race``     — the real ``fw_round`` builder with its column axis j
  declared ``"parallel"``: the col' scratch filled at j = 0 is carried
  across a parallel axis (the scratch-carry theorem; no output tile is
  written twice, so the write-race theorem alone would pass it).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import TROPICAL, Semiring
from repro.kernels.minplus import _fold, _pad, _rup
from repro.kernels.ref import minplus_ref

from .lattice import Case, _mat, case_for_fw_round_params

__all__ = ["Mutant", "mutant_cases", "control_case"]


@dataclass
class Mutant:
    case: Case
    expect: str     # the Problem kind that must appear
    match: str = ""  # ...with this text in its message


def _mini_minplus(
    x, y, *, bm, bn, bk, kc, sr,
    semantics: Optional[Tuple[str, ...]] = ("parallel", "parallel", "arbitrary"),
    out_map: Optional[Callable] = None,
    init: str = "gate",          # "gate" | "none" | "always"
    fill: Optional[float] = None,
    x_shift: int = 0,
):
    """A minimal, knowingly-mutable tiled ⊕⊗ builder (minplus arithmetic)."""
    fill = sr.zero if fill is None else fill
    xp = _pad(x, bm, bk, fill)
    yp = _pad(y, bk, bn, fill)
    mp, kp = xp.shape
    np_ = yp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    def kern(x_ref, y_ref, z_ref):
        def _init():
            z_ref[...] = jnp.full_like(z_ref[...], sr.zero)

        if init == "gate":
            pl.when(pl.program_id(2) == 0)(_init)
        elif init == "always":
            _init()
        if x_shift:
            # reads the x window one lane late: the last one leaves the block
            z_ref[...] = sr.add(
                z_ref[...],
                sr.mul(x_ref[:, pl.ds(bk - kc + x_shift, kc)][:, :1],
                       y_ref[pl.ds(0, 1), :]),
            )
        _fold(x_ref, y_ref, z_ref, k_base=pl.program_id(2) * bk, kc=kc, sr=sr)

    params = {}
    if semantics is not None:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=semantics
        )
    zp = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), out_map or (lambda i, j, kk: (i, j))),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=False,
        **params,
    )(xp, yp)
    return zp[: x.shape[0], : y.shape[1]]


def _mini_gather(d, rows, *, bn, bk, kc, sr, shift: int = 0):
    """A minimal row_close-style gather: Z = (d[rows+shift] ⊗ d)."""
    n = d.shape[-1]
    r = rows.shape[0]
    bn_ = min(bn, _rup(n, 128))
    kc = min(kc, _rup(n, 8))
    bk_ = min(_rup(bk, kc), _rup(n, kc))
    dy = _pad(d, bk_, bn_, sr.zero)
    kp, np_ = dy.shape
    dx = _pad(d, 1, bk_, sr.zero).reshape(n, 1, kp)

    def kern(rows_ref, x_ref, y_ref, z_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            z_ref[...] = jnp.full_like(z_ref[...], sr.zero)

        _fold(x_ref, y_ref, z_ref, k_base=pl.program_id(2) * bk_, kc=kc,
              sr=sr)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, np_ // bn_, kp // bk_),
        in_specs=[
            pl.BlockSpec((None, 1, bk_),
                         lambda i, j, kk, rows: (rows[i] + shift, 0, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk, rows: (kk, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, bn_),
                               lambda i, j, kk, rows: (i, 0, j)),
    )
    zp = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, 1, np_), d.dtype),
        interpret=False,
    )(rows.astype(jnp.int32), dx, dy)
    return zp[:, 0, :n]


def _fw_round_semantics(d, o, *, semantics: Tuple[str, ...], **kw):
    """The real ``fw_round`` builder, its row-stripe grid declared with
    ``semantics`` (the pivot closure's own grid keeps its declaration)."""
    from repro.kernels.fw_round import fw_round_pallas

    real = pltpu.CompilerParams

    def params(**p):
        if len(p.get("dimension_semantics", ())) == len(semantics):
            p["dimension_semantics"] = semantics
        return real(**p)

    pltpu.CompilerParams = params
    try:
        return fw_round_pallas.__wrapped__(d, o, **kw)
    finally:
        pltpu.CompilerParams = real


def _fw_round_case(name: str, seed: int, semantics: Tuple[str, ...]) -> Case:
    # n = 3 column tiles of 128, so the j sweep carries the scratch
    case = case_for_fw_round_params(64, 384, seed=seed)
    case.name, case.builder = name, "(mutant)"
    case.builder_fn = functools.partial(_fw_round_semantics, semantics=semantics)
    return case


def _mini_case(
    name: str, seed: int, *, padded: bool = False, shape=None, **mut
) -> Case:
    """Case over ``_mini_minplus`` at a shape with a (2, 2, 2) tile grid."""
    m, k, n = shape or ((13, 21, 130) if padded else (16, 32, 256))
    rng = np.random.default_rng(seed)
    sr = TROPICAL
    x, y = _mat(rng, (m, k), sr), _mat(rng, (k, n), sr)
    run = lambda fn: fn(x, y, bm=8, bn=128, bk=16, kc=8, sr=sr, **mut)
    return Case(
        name=name, module="minplus", builder="(mutant)",
        run=run, expected=lambda: minplus_ref(x, y, sr), padded=padded,
        builder_fn=_mini_minplus,
    )


def _gather_case(name: str, seed: int, *, shift: int) -> Case:
    n, r = 16, 4
    rng = np.random.default_rng(seed)
    sr = TROPICAL
    d = _mat(rng, (n, n), sr)
    rows = jnp.asarray([0, 7, n - 1, 7], jnp.int32)
    run = lambda fn: fn(d, rows, bn=128, bk=8, kc=8, sr=sr, shift=shift)
    return Case(
        name=name, module="row_close", builder="(mutant)",
        run=run,
        expected=lambda: minplus_ref(d[np.asarray(rows)], d, sr),
        padded=True,
        builder_fn=_mini_gather,
    )


def control_case() -> Case:
    """The unmutated mini builder — must verify clean (guards the corpus
    against defects the mutants did not intend to seed)."""
    return _mini_case("mutant-control/clean", seed=100)


def mutant_cases() -> List[Mutant]:
    return [
        Mutant(
            _mini_case("mutant/race-parallel-k", 101,
                       semantics=("parallel", "parallel", "parallel")),
            expect="race",
        ),
        Mutant(
            _mini_case("mutant/shrunk-out-map", 102,
                       out_map=lambda i, j, kk: (i, 0)),
            expect="race",
        ),
        Mutant(
            # non-square tile grid (2 row tiles x 1 col tile): the flipped
            # map writes an out-of-range tile AND leaves a hole
            _mini_case("mutant/flipped-out-map", 103, shape=(16, 32, 128),
                       out_map=lambda i, j, kk: (j, i)),
            expect="coverage",
        ),
        Mutant(
            _mini_case("mutant/dropped-init", 104, init="none"),
            expect="uninit",
        ),
        Mutant(
            _mini_case("mutant/ungated-init", 105, init="always"),
            expect="mismatch",
        ),
        Mutant(
            _mini_case("mutant/poisoned-padding", 106, padded=True, fill=0.0),
            expect="padding",
        ),
        Mutant(
            _gather_case("mutant/unchecked-gather", 107, shift=1),
            expect="bounds",
        ),
        Mutant(
            _mini_case("mutant/overrun-window", 108, x_shift=1),
            expect="bounds",
        ),
        Mutant(
            _fw_round_case("mutant/fw-round-parallel-j", 109,
                           ("parallel", "parallel", "parallel")),
            expect="race", match="scratch 0",
        ),
    ]
