"""Row-restricted panel relaxation — the dynamic engine's worsening pass.

After an edge worsens, only the rows of sources whose shortest-path tree
used that edge can change (the affected set R from ``_affected_mask``).
Re-closing the full matrix costs O(n³) per squaring; one pass of

    Z[R, :] = D[R, :] ⊕ ( D[R, :] ⊗ D )

costs O(|R|·n²) and, iterated to fixpoint against the exact remainder
(non-R rows of D are untouched and already closed), doubles the covered
R-prefix length per pass exactly like the squaring solver — Jing &
Meister's bounded-iteration relaxation restricted to the affected
sources.

The kernel is the fused-accumulate min-plus tile loop from
``kernels.minplus`` with one twist: the grid's row dimension walks the
*affected-row list*, not a contiguous stripe.  The row indices arrive via
scalar prefetch (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index
maps can gather row ``rows[i]`` of D for the X panel and ⊕-operand while
streaming the full matrix as Y — no host-side ``d[rows]`` materialization
and no second dispatch for the write-back panel.  The row block size is
pinned to 1 (a gather has no contiguous row tile), so only (bn, bk, kc)
are tunable — the ``rowclose|…`` autotune family.  The gathered operands
are viewed as (n, 1, cols): a (1, 1, cols) block whose second-minor dim
is the whole (unit) axis satisfies the TPU's (8, 128) block rule, which a
(1, cols) block of an (n, cols) matrix does not.

Because the X panel, ⊕-operand, and Y matrix need different padded
column counts (bk vs bn multiples) and a gathered row dim cannot be
padded, three differently-padded copies of D are passed as separate
inputs; XLA CSEs the underlying buffer where the pads coincide.

Bit-exactness: candidates and fold order match the chunked-XLA fallback
(``minplus_xla`` over the materialized ``d[rows]`` panel) — same kc
chunking, same strict ``better`` keep — so the two backends agree
bit-for-bit, witnesses included (K* = -1 where the ⊕-operand was kept,
else the smallest improving global k).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import TROPICAL, Semiring

from .minplus import DEFAULT_BK, DEFAULT_BN, DEFAULT_KC, _fold, _pad, _rup

__all__ = ["row_close_pallas", "PALLAS_BUILDERS"]


def _kernel(rows_ref, x_ref, y_ref, a_ref, z_ref, *i_ref, kc, bk, sr):
    i_ref = i_ref[0] if i_ref else None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        z_ref[...] = a_ref[...]
        if i_ref is not None:
            i_ref[...] = jnp.full(i_ref.shape, -1, jnp.int32)

    _fold(x_ref, y_ref, z_ref, i_ref, k_base=pl.program_id(2) * bk, kc=kc,
          sr=sr)


@functools.partial(
    jax.jit, static_argnames=("bn", "bk", "kc", "track", "interpret", "semiring")
)
def row_close_pallas(
    d: jax.Array,
    rows: jax.Array,
    *,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    kc: int = DEFAULT_KC,
    track: bool = False,
    interpret: bool = False,
    semiring: Semiring = TROPICAL,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One row-restricted relaxation pass on a (n, n) matrix.

    Returns the updated (r, n) panel ``d[rows, :] ⊕ (d[rows, :] ⊗ d)``
    (and, with ``track``, its (r, n) int32 witness panel).  ``rows`` is a
    traced int32 vector of row ids — duplicates are allowed (padded row
    lists repeat an id; every duplicate computes the identical panel row,
    so the caller's scatter is deterministic).  The caller owns the
    scatter back into the full matrix.
    """
    sr = semiring
    n = d.shape[-1]
    assert d.ndim == 2 and d.shape[0] == n, d.shape
    r = rows.shape[0]
    bn_ = min(bn, _rup(n, 128))
    kc = min(kc, _rup(n, 8))
    bk_ = min(_rup(bk, kc), _rup(n, kc))
    dy = _pad(d, bk_, bn_, sr.zero)                       # (kp, np) streamed Y
    kp, np_ = dy.shape
    dx = _pad(d, 1, bk_, sr.zero).reshape(n, 1, kp)       # X gather source
    da = _pad(d, 1, bn_, sr.zero).reshape(n, 1, np_)      # ⊕-operand source
    grid = (r, np_ // bn_, kp // bk_)
    out_spec = pl.BlockSpec((None, 1, bn_), lambda i, j, kk, rows: (i, 0, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, bk_), lambda i, j, kk, rows: (rows[i], 0, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk, rows: (kk, j)),
            pl.BlockSpec((None, 1, bn_), lambda i, j, kk, rows: (rows[i], 0, j)),
        ],
        out_specs=(out_spec, out_spec) if track else out_spec,
    )
    params = {}
    if not interpret:
        # row/col blocks are independent; k is a revisit-accumulate dim and
        # must stay sequential-innermost (same contract as minplus).
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    z_shape = jax.ShapeDtypeStruct((r, 1, np_), d.dtype)
    out_shape = (
        (z_shape, jax.ShapeDtypeStruct((r, 1, np_), jnp.int32))
        if track else z_shape
    )
    out = pl.pallas_call(
        functools.partial(_kernel, kc=kc, bk=bk_, sr=sr),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        **params,
    )(rows.astype(jnp.int32), dx, dy, da)
    if track:
        zp, ip = out
        return zp[:, 0, :n], ip[:, 0, :n]
    return out[:, 0, :n], None


# Raw (unjitted) builder for the kernel grid verifier — see
# ``repro.analysis.kernelcheck`` and the authoring checklist in
# COMPAT.md §Static analysis.
PALLAS_BUILDERS = {"row_close_pallas": row_close_pallas.__wrapped__}
