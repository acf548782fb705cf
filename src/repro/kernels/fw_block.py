"""In-VMEM Floyd-Warshall pivot-block closure kernel (blocked-FW phase 1).

Phase 1 of the 3-phase blocked FW closes the (B, B) pivot tile: B dependent
pivot steps, each a rank-1 tropical update ``D = min(D, D[:,k] + D[k,:])``.
The dependence chain makes this the one phase that cannot be a min-plus GEMM,
so it gets its own kernel: the whole tile lives in VMEM (B=256 fp32 tile =
256 KiB; B=512 = 1 MiB) and the pivot loop runs entirely on-core, no HBM
traffic between pivots.

The predecessor variant carries the (B, B) int32 predecessor tile and applies
the textbook rule ``pred[i,j] <- pred[k,j]`` on strict improvement.

Grid: 1D over independent diagonal tiles (R-Kleene leaves batch several).
Oracles: ``ref.fw_block_ref`` / ``ref.fw_block_pred_ref``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import TROPICAL, Semiring

INF = jnp.inf

__all__ = ["fw_block_pallas", "fw_block_pred_pallas", "PALLAS_BUILDERS"]


def _window(b: int) -> int:
    """Lane window the pivot loop walks: 128 when it tiles the edge, else
    the whole (single-window) edge."""
    return 128 if b % 128 == 0 else b


def _close(d_ref, p_ref, *, sr: Semiring) -> None:
    """Close the (B, B) tile held in ``d_ref`` in place (and its
    predecessors in ``p_ref``): B dependent rank-1 pivot steps.

    Row k is read at a dynamic sublane offset.  Column k is picked out of
    the 128-lane window that holds it (Mosaic loads lanes only at provably
    128-aligned offsets) by a ⊕-reduction over a one-hot lane mask — exact,
    since every other lane contributes the semiring zero.  Both are re-read
    every step: step k' < k may have improved them."""
    b = d_ref.shape[-1]
    lc = _window(b)
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, lc), 1)

    def step(k, carry):
        k0 = pl.multiple_of((k // lc) * lc, lc) if lc < b else 0
        win = d_ref[:, pl.ds(k0, lc)]                           # (B, lc)
        col = sr.reduce(jnp.where(lane == k - k0, win, sr.zero),
                        axis=1, keepdims=True)                  # (B, 1)
        row = d_ref[pl.ds(k, 1), :]                             # (1, B)
        cur = d_ref[...]
        via = sr.mul(col, row)
        if p_ref is None:
            d_ref[...] = sr.add(cur, via)
            return carry
        better = sr.better(via, cur)
        d_ref[...] = jnp.where(better, via, cur)
        p_ref[...] = jnp.where(better, p_ref[pl.ds(k, 1), :], p_ref[...])
        return carry

    jax.lax.fori_loop(0, b, step, 0)


def _closure_call(d, p, *, interpret: bool, semiring: Semiring):
    """(T, B, B) tiles (and predecessors) -> closed tiles: one grid program
    per independent tile."""
    t, b, _ = d.shape
    spec = pl.BlockSpec((None, b, b), lambda i: (i, 0, 0))
    pred = p is not None

    def kern(*refs):
        d_ref, o_ref = refs[0], refs[1 + pred]
        o_ref[...] = d_ref[...]
        po_ref = None
        if pred:
            po_ref = refs[3]
            po_ref[...] = refs[1][...]
        _close(o_ref, po_ref, sr=semiring)

    out_shape = jax.ShapeDtypeStruct((t, b, b), d.dtype)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        )
    return pl.pallas_call(
        kern,
        grid=(t,),
        in_specs=[spec, spec] if pred else [spec],
        out_specs=(spec, spec) if pred else spec,
        out_shape=(
            (out_shape, jax.ShapeDtypeStruct((t, b, b), jnp.int32))
            if pred else out_shape
        ),
        interpret=interpret,
        name="fw_block_pred" if pred else "fw_block",
        **params,
    )(*((d, p) if pred else (d,)))


def close_tiles(
    d: jax.Array, *, interpret: bool = False, semiring: Semiring = TROPICAL
) -> jax.Array:
    """Unjitted body of :func:`fw_block_pallas` (the fused round calls it
    inside its own trace)."""
    batched = d.ndim == 3
    dd = d if batched else d[None]
    assert dd.shape[1] == dd.shape[2], d.shape
    out = _closure_call(dd, None, interpret=interpret, semiring=semiring)
    return out if batched else out[0]


@functools.partial(jax.jit, static_argnames=("interpret", "semiring"))
def fw_block_pallas(
    d: jax.Array, *, interpret: bool = False, semiring: Semiring = TROPICAL
) -> jax.Array:
    """Close one (B, B) tile, or a batch (T, B, B) of independent tiles."""
    return close_tiles(d, interpret=interpret, semiring=semiring)


@functools.partial(jax.jit, static_argnames=("interpret", "semiring"))
def fw_block_pred_pallas(
    d: jax.Array, p: jax.Array, *, interpret: bool = False,
    semiring: Semiring = TROPICAL,
) -> Tuple[jax.Array, jax.Array]:
    """Closure with predecessor tracking (global node ids in ``p``)."""
    batched = d.ndim == 3
    dd = d if batched else d[None]
    pp = p if batched else p[None]
    assert dd.shape[1] == dd.shape[2] and pp.shape == dd.shape
    do, po = _closure_call(dd, pp, interpret=interpret, semiring=semiring)
    return (do, po) if batched else (do[0], po[0])


# Raw (unjitted) builders for the kernel grid verifier — see
# ``repro.analysis.kernelcheck`` and the authoring checklist in
# COMPAT.md §Static analysis.
PALLAS_BUILDERS = {
    "fw_block_pallas": fw_block_pallas.__wrapped__,
    "fw_block_pred_pallas": fw_block_pred_pallas.__wrapped__,
}
