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

Bit-exactness: the candidate sums are identical to the chunked-XLA
fallback (``minplus_xla.fw_round_xla``) — same closure fold, same
``col ⊗ A*`` association — and a selective ⊕ over the same candidate set
is order-insensitive, so the two backends agree bit-for-bit (including
bf16 mixed mode, which rounds at the same three points: closed pivot,
col', output).

The predecessor-tracking round is composed from the existing fused-argmin
kernels in ``kernels.ops`` (it needs int32 witness state this kernel does
not carry).  Scalar prefetch carries the pivot *tile index* so the solver
can drive the round from inside a ``fori_loop`` with a traced offset.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import TROPICAL, Semiring

from .fw_block import _window, close_tiles
from .minplus import _fold

__all__ = ["fw_round_pallas", "PALLAS_BUILDERS"]


def _col_tile(n: int) -> int:
    """Output tile width: the widest 128-multiple dividing n, else all of n."""
    for bn in (512, 256, 128):
        if n % bn == 0:
            return bn
    return n


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
    bn = _col_tile(n)
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


# Raw (unjitted) builder for the kernel grid verifier — see
# ``repro.analysis.kernelcheck`` and the authoring checklist in
# COMPAT.md §Static analysis.
PALLAS_BUILDERS = {"fw_round_pallas": fw_round_pallas.__wrapped__}
