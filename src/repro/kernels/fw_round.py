"""Multi-stage fused blocked-FW k-round — one row-stripe grid per round.

The legacy blocked-FW round is four kernel launches (pivot closure, row
panel, col panel, phase-3 outer update) plus stripe copies; Lund & Smith's
multi-stage CUDA kernel shows the panel work fits in one launch.  This
kernel is that scheme on the Pallas grid, tiled so every block fits VMEM at
any n:

  stage 1: A* = FW(pivot)            own dispatch (``fw_block``), once per round
  grid = (G, N/B, N/bn); program (g, i, j) owns output tile (B, bn) of
  stripe i and receives, via the scalar-prefetched pivot index t:
    * its tile of D (the ⊕-accumulate operand),
    * the pivot row panel tile  D[o:o+B, j·bn:(j+1)·bn],
    * its col-panel tile        D[i·B:(i+1)·B, o:o+B]  (pre-sliced panel),
    * the closed pivot A*.
  body:  col' = col ⊗ A*                  (once per stripe, at j = 0, kept in
                                           a VMEM scratch across the j sweep)
         out  = tile ⊕ col' ⊗ rowpanel    (fused accumulate)

The stage-3 accumulate re-derives the row/col stripes and the pivot block
by subsumption (see ``core.blocked_fw``), so the round writes each output
element exactly once and no ``dynamic_update_slice`` pass exists.  The
pivot is closed once per round, not once per program: the closure adds no
redundant work (its B^3 ⊕⊗ steps per round are 2nB^2 in all, a B^2/n^2
share of the 2n^3 total).  col' costs n·B^2 per round, the col-panel
product itself.  The j axis is "arbitrary" because the col' scratch is
carried across it; g and i are "parallel".

Tiles and register bands.  The fold holds a band of 32 f32 accumulator
vregs (``minplus._band_rows``).  Each k lane-broadcasts one x column per
8-row group of the band, a permute through the cross-lane unit, and adds
it to every vreg of that group; a y row is sublane-replicated once per
128 lanes.  ``fw_round`` takes 1024-lane tiles where n allows it
(``_round_tile``): a 32 x 1024 band pays 4 lane broadcasts per k where
the 64 x 512 band of a 512-lane tile pays 8, for the same 64 VALU ops,
and the permutes set the fold's pace.  ``fw_round_pred`` keeps 512-lane
tiles (``_col_tile``): its witness plane halves the band to 32 x 512, and
its 4 VALU ops per relaxation (add, compare, min, select) bound it.
Where no 128-multiple divides n (zinc's 16-64-wide buckets) both take a
whole-row tile.

Bit-exactness: the candidate sums are identical to the chunked-XLA
fallback (``minplus_xla.fw_round_xla``) — same closure fold, same
``col ⊗ A*`` association — and a selective ⊕ over the same candidate set
is order-insensitive, so the two backends agree bit-for-bit (including
bf16 mixed mode, which rounds at the same three points: closed pivot,
col', output).

The predecessor-tracking round ``fw_round_pred_pallas`` is the same grid
carrying int32 witness tiles beside the distance tiles: P in and out, the
pivot row panel's P tile and the closed pivot's P (``fw_block_pred``), and
a (B, B) int32 scratch for col′'s witnesses.  A candidate that strictly
improves takes the row-side witness (``P[o+k, j]``), or the col-side one
(``P′[i, k]``) where the row side is the diagonal's ``one`` (global k =
global j): the rule of ``ops.pred_from_kstar``.  Only the tiles that hold
pivot columns can meet that case, so only they pay the extra select.
Distances are ``fw_round``'s bit for bit: the same candidate sums under
the same ⊕.

Scalar prefetch carries the pivot *tile index* so the solver can drive the
round from inside a ``fori_loop`` with a traced offset.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import TROPICAL, Semiring

from .fw_block import _closure_call, _window, close_tiles
from .minplus import _band_rows, _fold, _row_group, _windows

__all__ = ["fw_round_pallas", "fw_round_pred_pallas", "PALLAS_BUILDERS"]


_WIDE_TILE = 1024
# VMEM the stripe grid's three double-buffered (B, bn) tiles (row panel,
# accumulate operand, output), counted in the compute dtype, may take:
# 12 MiB of the 16 MiB a v5e kernel gets by default, the rest left to the
# (B, B) pivot, col-panel and col′ blocks.  At 1024 lanes that admits every
# B up to 512, in f32 and in bf16 storage alike.
_TILE_VMEM = 12 * 2**20


def _col_tile(n: int) -> int:
    """Output tile width of ``fw_round_pred``: the widest 128-multiple of
    at most 512 dividing n, else all of n (zinc's 16-64-wide buckets).

    The fold's row band holds 32 f32 vregs (16 with the witness plane,
    ``minplus._band_rows``), so at 512 lanes ``fw_round_pred``'s band is
    32 rows: 4 lane broadcasts of an x column per k for 16 accumulator
    vregs, beside 4 VALU ops per relaxation that bound it instead."""
    for bn in (512, 256, 128):
        if n % bn == 0:
            return bn
    return n


def _round_tile(n: int, b: int, itemsize: int) -> int:
    """Output tile width of ``fw_round``: 1024 lanes where n allows it and
    its tiles fit VMEM, else ``_col_tile``.

    Each k of the stripe fold lane-broadcasts one x column per 8-row group
    (a permute through the cross-lane unit) and adds it to every
    accumulator vreg of that group.  A (64, 512) band pays 8 broadcasts
    for 32 vregs, a (32, 1024) band 4: the same VALU work for half the
    permutes, which set the fold's pace, not the VALU."""
    if n % _WIDE_TILE == 0 and 6 * b * _WIDE_TILE * itemsize <= _TILE_VMEM:
        return _WIDE_TILE
    return _col_tile(n)


@functools.partial(
    jax.jit, static_argnames=("block_size", "interpret", "semiring")
)
def fw_round_pallas(
    d: jax.Array,
    o: jax.Array,
    *,
    block_size: int,
    interpret: bool = False,
    semiring: Semiring = TROPICAL,
) -> jax.Array:
    """One fused blocked-FW round on a (N, N) matrix or (G, N, N) stack.

    ``o`` is the (traced) element offset of the pivot block; N must be a
    multiple of ``block_size`` (the solver pads).  Returns the full updated
    matrix: the pivot closure dispatch plus one row-stripe grid.
    """
    sr = semiring
    b = block_size
    batched = d.ndim == 3
    dd = d if batched else d[None]
    g, n, n2 = dd.shape
    assert n == n2 and n % b == 0, (d.shape, b)
    storage = d.dtype
    cd = jnp.float32 if storage == jnp.bfloat16 else storage
    bn = _round_tile(n, b, jnp.dtype(cd).itemsize)
    lc = _window(b)

    pivot = jax.lax.dynamic_slice(dd, (0, o, o), (g, b, b))
    pivot = close_tiles(
        pivot.astype(cd), interpret=interpret, semiring=sr
    ).astype(storage)
    colpan = jax.lax.dynamic_slice(dd, (0, 0, o), (g, n, b))

    def kern(t_ref, piv_ref, col_ref, row_ref, acc_ref, o_ref, colp_ref):
        @pl.when(pl.program_id(2) == 0)
        def _col_panel():
            colp_ref[...] = jnp.full(colp_ref.shape, sr.zero, cd)
            _fold(col_ref, piv_ref, colp_ref, kc=lc, sr=sr, cd=cd)
            if storage != cd:                  # round col' like the fallback
                colp_ref[...] = colp_ref[...].astype(storage).astype(cd)

        _fold(colp_ref, row_ref, o_ref, a_ref=acc_ref, kc=lc, sr=sr, cd=cd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, n // b, n // bn),
        in_specs=[
            pl.BlockSpec((None, b, b), lambda gi, i, j, t: (gi, 0, 0)),
            pl.BlockSpec((None, b, b), lambda gi, i, j, t: (gi, i, 0)),
            pl.BlockSpec((None, b, bn), lambda gi, i, j, t: (gi, t[0], j)),
            pl.BlockSpec((None, b, bn), lambda gi, i, j, t: (gi, i, j)),
        ],
        out_specs=pl.BlockSpec((None, b, bn), lambda gi, i, j, t: (gi, i, j)),
        scratch_shapes=[pltpu.VMEM((b, b), cd)],
    )
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    t = jnp.reshape(o // b, (1,)).astype(jnp.int32)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, n, n), storage),
        interpret=interpret,
        name="fw_round",
        **params,
    )(t, pivot, colpan, dd, dd)
    return out if batched else out[0]


def _fold_pred(x_ref, y_ref, px_ref, py_ref, z_ref, pz_ref, *, a_ref=None,
               pa_ref=None, same=None, kc: int, sr: Semiring, cd):
    """``z = a ⊕ x ⊗ y`` with witnesses, one register-sized row band at a
    time (``a``/``pa`` default to ``z``/``pz`` themselves).

    A candidate that strictly improves takes ``py[k, j]``; with ``same =
    (col0, k0)`` (the global ids of z's column 0 and x's column 0) it takes
    ``px[i, k]`` instead where k and j are the same node.
    """
    bm, bn = z_ref.shape
    rows = _band_rows(bm, bn, True)
    src = z_ref if a_ref is None else a_ref
    psrc = pz_ref if pa_ref is None else pa_ref
    grp = _row_group(y_ref.dtype, kc)
    cols = None
    if same is not None:
        cols = same[0] + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)

    def band(r, carry):
        r0 = pl.multiple_of(r * rows, rows)

        def window(k0, c):
            acc, wit = c
            xs = x_ref[pl.ds(r0, rows), pl.ds(k0, kc)].astype(cd)
            pxs = None
            if same is not None:
                pxs = px_ref[pl.ds(r0, rows), pl.ds(k0, kc)]
            for q in range(0, kc, grp):
                ys = y_ref[pl.ds(k0 + q, grp), :].astype(cd)
                pys = py_ref[pl.ds(k0 + q, grp), :]
                for k in range(q, q + grp):
                    cand = sr.mul(xs[:, k:k + 1], ys[k - q:k - q + 1, :])
                    better = sr.better(cand, acc)
                    acc = sr.add(acc, cand)
                    w = pys[k - q:k - q + 1, :]
                    if same is not None:
                        w = jnp.where(cols == same[1] + k0 + k,
                                      pxs[:, k:k + 1], w)
                    wit = jnp.where(better, w, wit)
            return acc, wit

        init = (src[pl.ds(r0, rows), :].astype(cd), psrc[pl.ds(r0, rows), :])
        acc, wit = _windows(x_ref.shape[-1], kc, window, init)
        z_ref[pl.ds(r0, rows), :] = acc.astype(z_ref.dtype)
        pz_ref[pl.ds(r0, rows), :] = wit
        return carry

    jax.lax.fori_loop(0, bm // rows, band, 0)


@functools.partial(
    jax.jit, static_argnames=("block_size", "interpret", "semiring")
)
def fw_round_pred_pallas(
    d: jax.Array,
    p: jax.Array,
    o: jax.Array,
    *,
    block_size: int,
    interpret: bool = False,
    semiring: Semiring = TROPICAL,
):
    """One fused blocked-FW round with predecessors, (N, N) or (G, N, N).

    ``p`` holds global node ids (-1: no path).  Returns ``(d', p')``: the
    pivot closure dispatch (``fw_block_pred``) plus one row-stripe grid,
    ``fw_round_pallas``'s grid on ``_col_tile``'s tiles, with the int32
    witness tiles beside the distance tiles.
    """
    sr = semiring
    b = block_size
    batched = d.ndim == 3
    dd = d if batched else d[None]
    pp = p if batched else p[None]
    g, n, n2 = dd.shape
    assert n == n2 and n % b == 0 and pp.shape == dd.shape, (d.shape, p.shape, b)
    storage = d.dtype
    cd = jnp.float32 if storage == jnp.bfloat16 else storage
    bn = _col_tile(n)
    lc = _window(b)

    pivot, ppivot = _closure_call(
        jax.lax.dynamic_slice(dd, (0, o, o), (g, b, b)).astype(cd),
        jax.lax.dynamic_slice(pp, (0, o, o), (g, b, b)),
        interpret=interpret, semiring=sr,
    )
    pivot = pivot.astype(storage)
    colpan = jax.lax.dynamic_slice(dd, (0, 0, o), (g, n, b))

    def kern(t_ref, piv_ref, ppiv_ref, col_ref, row_ref, prow_ref, acc_ref,
             pacc_ref, o_ref, po_ref, colp_ref, pcolp_ref):
        j = pl.program_id(2)
        off = t_ref[0] * b                      # global id of pivot column 0

        @pl.when(j == 0)
        def _col_panel():
            # col' = col ⊗ A*.  Its k == c candidate (A*'s diagonal one)
            # takes A*'s witness, not col's: no round reads it, since the
            # stripe fold below improves on tile = col only where col'
            # beats col, i.e. where some k != c won.
            colp_ref[...] = jnp.full(colp_ref.shape, sr.zero, cd)
            pcolp_ref[...] = jnp.full(pcolp_ref.shape, -1, jnp.int32)
            _fold_pred(col_ref, piv_ref, None, ppiv_ref, colp_ref, pcolp_ref,
                       kc=lc, sr=sr, cd=cd)
            if storage != cd:                  # round col' like fw_round
                colp_ref[...] = colp_ref[...].astype(storage).astype(cd)

        lo = j * bn
        pivot_cols = jnp.logical_and(lo < off + b, off < lo + bn)

        @pl.when(pivot_cols)
        def _with_pivot_cols():
            _fold_pred(colp_ref, row_ref, pcolp_ref, prow_ref, o_ref, po_ref,
                       a_ref=acc_ref, pa_ref=pacc_ref, same=(lo, off), kc=lc,
                       sr=sr, cd=cd)

        @pl.when(jnp.logical_not(pivot_cols))
        def _without():
            _fold_pred(colp_ref, row_ref, pcolp_ref, prow_ref, o_ref, po_ref,
                       a_ref=acc_ref, pa_ref=pacc_ref, kc=lc, sr=sr, cd=cd)

    tile = lambda gi, i, j, t: (gi, i, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, n // b, n // bn),
        in_specs=[
            pl.BlockSpec((None, b, b), lambda gi, i, j, t: (gi, 0, 0)),
            pl.BlockSpec((None, b, b), lambda gi, i, j, t: (gi, 0, 0)),
            pl.BlockSpec((None, b, b), lambda gi, i, j, t: (gi, i, 0)),
            pl.BlockSpec((None, b, bn), lambda gi, i, j, t: (gi, t[0], j)),
            pl.BlockSpec((None, b, bn), lambda gi, i, j, t: (gi, t[0], j)),
            pl.BlockSpec((None, b, bn), tile),
            pl.BlockSpec((None, b, bn), tile),
        ],
        out_specs=[pl.BlockSpec((None, b, bn), tile),
                   pl.BlockSpec((None, b, bn), tile)],
        scratch_shapes=[pltpu.VMEM((b, b), cd), pltpu.VMEM((b, b), jnp.int32)],
    )
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    t = jnp.reshape(o // b, (1,)).astype(jnp.int32)
    out, pout = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((g, n, n), storage),
                   jax.ShapeDtypeStruct((g, n, n), jnp.int32)],
        interpret=interpret,
        name="fw_round_pred",
        **params,
    )(t, pivot, ppivot, colpan, dd, pp, dd, pp)
    return (out, pout) if batched else (out[0], pout[0])


# Raw (unjitted) builders for the kernel grid verifier — see
# ``repro.analysis.kernelcheck`` and the authoring checklist in
# COMPAT.md §Static analysis.
PALLAS_BUILDERS = {
    "fw_round_pallas": fw_round_pallas.__wrapped__,
    "fw_round_pred_pallas": fw_round_pred_pallas.__wrapped__,
}
