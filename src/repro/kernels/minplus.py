"""Tiled min-plus (tropical) matmul Pallas kernel — the paper's hot spot.

The paper materializes ``L[i,k,j] = X[i,k] + Y[k,j]`` (N^3 bytes) and reduces
with ``min``/``argmin``.  On TPU we never build L: the grid walks (M/bm,
N/bn, K/bk) tiles with k innermost, each step streams an (bm, bk) X panel and
a (bk, bn) Y panel through VMEM and folds a running elementwise ``min`` into
the (bm, bn) output block.  Inside a tile every k is a rank-1 update
``acc = min(acc, x[:, k] + y[k, :])`` on a register-sized row band of the
block, read straight from the VMEM refs — nothing larger than the
accumulator is ever live, instead of the paper's n^3 wall.

(min, +) has no multiply-accumulate, so this runs on the VPU (8x128 vector
unit), not the 128x128 MXU; block shapes are multiples of the fp32 (8, 128)
vreg tile, and the x panel is read in 128-lane windows (Mosaic accepts a
dynamic lane offset only when it is provably a multiple of 128).  The k grid
dim is "arbitrary" (sequential) — the output block is revisited and
accumulated across k steps, which TPU guarantees for the innermost grid dim.

Batched dispatch: (G, m, k) x (G, k, n) operands add a *leading* batch grid
dimension — the whole multi-graph panel product is one ``pallas_call``
(grid (G, M/bm, N/bn, K/bk)), not a ``vmap`` of G kernel launches.  That is
what lets ``blocked_fw_batch`` drive all G graphs per pivot step with a
single dispatch.

Variants (one kernel body, two flags):
  * fused accumulate  — Z = A ⊕ (X ⊗ Y): phase-3 blocked-FW / R-Kleene
    update without a second HBM round-trip.
  * fused argmin      — running witness (global k index) carried with the
    running ⊕; K* = -1 where no path (or where A kept, in the accumulate
    variant).  Feeds predecessor propagation.

The ``semiring`` argument (static, a ``repro.core.semiring.Semiring``)
selects the (⊕, ⊗) pair, the padding fill, and the improvement direction —
one kernel body serves tropical shortest path, bottleneck widest path,
reliability, and boolean closure; the ⊕/⊗ swap stays on the VPU either way
(none of the instances have a multiply-accumulate the MXU could take).

Oracles: ``repro.kernels.ref``.  Public wrappers: ``repro.kernels.ops``.
Default block sizes below are the compiled-in fallback; the measured
winners live in the autotune cache (``repro.kernels.autotune``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import TROPICAL, Semiring

INF = jnp.inf

__all__ = [
    "minplus_pallas",
    "minplus_argmin_pallas",
    "PALLAS_BUILDERS",
    "DEFAULT_BM",
    "DEFAULT_BN",
    "DEFAULT_BK",
    "DEFAULT_KC",
]

# fp32 vregs are (8, 128); MXU alignment is irrelevant here (VPU op), but
# 128-lane alignment matters: Mosaic loads the x panel only at lane offsets
# it can prove are multiples of 128, so the in-tile k window ``kc`` is 128
# (or the whole block when the contraction is shorter).  bk=512 amortizes
# grid overhead.
DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512
DEFAULT_KC = 128

# live accumulator budget per row band: 32 f32 vregs (half the register
# file), halved again when the int32 witness plane rides along
_BAND_ELEMS = 32 * 1024


def _minplus_body(x_ref, y_ref, acc, idx, *, r0, k_base, kc, sr, cd):
    """Fold ⊕ over the k dim of ``x_ref[r0:r0+rows, :] ⊗ y_ref`` into acc.

    ``x_ref`` is a (bm, bk) ref, ``y_ref`` a (bk, bn) ref, ``acc``/``idx``
    (rows, bn) values.  The contraction is walked in windows of ``kc``
    lanes (a multiple of 128 or the whole of bk); inside a window each k is
    a static rank-1 update ``acc ⊕= x[:, k] ⊗ y[k, :]`` — a lane broadcast
    of one x column against a sublane broadcast of one y row, so nothing
    larger than the accumulator is ever live.  y rows are loaded in aligned
    groups of one packed sublane tile.  With ``idx`` the update keeps a
    strict-improvement witness (global k id): ties keep the earlier,
    smaller k, exactly like the oracle's argmin.
    """
    rows = acc.shape[0]
    track = idx is not None
    grp = _row_group(y_ref.dtype, kc)

    def window(k0, carry):
        xs = x_ref[pl.ds(r0, rows), pl.ds(k0, kc)].astype(cd)     # (rows, kc)
        for q in range(0, kc, grp):
            ys = y_ref[pl.ds(k0 + q, grp), :].astype(cd)          # (grp, bn)
            for j in range(q, q + grp):
                cand = sr.mul(xs[:, j:j + 1], ys[j - q:j - q + 1, :])
                if not track:
                    carry = sr.add(carry, cand)
                    continue
                a, i = carry
                better = sr.better(cand, a)
                carry = (jnp.where(better, cand, a),
                         jnp.where(better, k_base + k0 + j, i))
        return carry

    init = (acc, idx) if track else acc
    out = _windows(x_ref.shape[-1], kc, window, init)
    return out if track else (out, None)


def _windows(k: int, kc: int, body, init):
    """Run ``body(k0, carry)`` over the kc-wide windows of a length-k axis:
    a static offset when there is one window (Mosaic cannot prove a traced
    offset lane-aligned), else a loop over 128-multiple offsets."""
    if k == kc:
        return body(0, init)
    return jax.lax.fori_loop(
        0, k // kc, lambda w, c: body(pl.multiple_of(w * kc, kc), c), init
    )


def _row_group(dtype, kc: int) -> int:
    """Rows of y loaded at once: one sublane tile of the dtype (8 for 32-bit,
    16 for bf16), so each dynamic load starts on a tile boundary."""
    grp = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return grp if kc % grp == 0 else 1  # repro: allow-trace-impurity  kc is a static block size


def _band_rows(bm: int, bn: int, track: bool) -> int:
    """Rows per accumulator band: the whole block when it fits the budget,
    else halved while it stays a multiple of 16 (one bf16 sublane tile)."""
    budget = _BAND_ELEMS // (2 if track else 1)
    rows = bm
    while rows * bn > budget and rows % 32 == 0:
        rows //= 2
    return rows


def _fold(x_ref, y_ref, z_ref, i_ref=None, *, a_ref=None, k_base=0, kc: int,
          sr: Semiring, cd=None):
    """``z ⊕= x ⊗ y`` over one grid step, one register-sized row band at a
    time (``a_ref`` supplies the ⊕-operand when it is not ``z`` itself).
    Values are computed in ``cd`` (default: z's dtype) and stored in z's."""
    bm, bn = z_ref.shape
    cd = z_ref.dtype if cd is None else cd
    src = z_ref if a_ref is None else a_ref
    track = i_ref is not None
    rows = _band_rows(bm, bn, track)

    def band(b, carry):
        r0 = pl.multiple_of(b * rows, rows)
        acc = src[pl.ds(r0, rows), :].astype(cd)
        idx = i_ref[pl.ds(r0, rows), :] if track else None
        acc, idx = _minplus_body(
            x_ref, y_ref, acc, idx, r0=r0, k_base=k_base, kc=kc, sr=sr, cd=cd
        )
        z_ref[pl.ds(r0, rows), :] = acc.astype(z_ref.dtype)
        if track:
            i_ref[pl.ds(r0, rows), :] = idx
        return carry

    jax.lax.fori_loop(0, bm // rows, band, 0)


def _kernel(*refs, kc: int, bk: int, k_axis: int, sr: Semiring,
            accumulate: bool, track: bool):
    """One grid step of Z (⊕)= X ⊗ Y; refs are ([a], x, y, z, [i])."""
    a_ref = refs[0] if accumulate else None
    x_ref, y_ref, z_ref = refs[accumulate:accumulate + 3]
    i_ref = refs[accumulate + 3] if track else None
    kk = pl.program_id(k_axis)

    @pl.when(kk == 0)
    def _init():
        if accumulate:
            z_ref[...] = a_ref[...]
        else:
            z_ref[...] = jnp.full(z_ref.shape, sr.zero, z_ref.dtype)
        if track:
            i_ref[...] = jnp.full(i_ref.shape, -1, jnp.int32)

    _fold(x_ref, y_ref, z_ref, i_ref, k_base=kk * bk, kc=kc, sr=sr)


def _pad(arr, m0, m1, value):
    """Pad the last two dims up to multiples of (m0, m1)."""
    p0 = (-arr.shape[-2]) % m0
    p1 = (-arr.shape[-1]) % m1
    if p0 == 0 and p1 == 0:
        return arr
    cfg = [(0, 0)] * (arr.ndim - 2) + [(0, p0), (0, p1)]
    return jnp.pad(arr, cfg, constant_values=value)


def _specs(batched: bool, bm: int, bn: int, bk: int):
    if batched:
        return (
            pl.BlockSpec((None, bm, bk), lambda g, i, j, kk: (g, i, kk)),
            pl.BlockSpec((None, bk, bn), lambda g, i, j, kk: (g, kk, j)),
            pl.BlockSpec((None, bm, bn), lambda g, i, j, kk: (g, i, j)),
        )
    return (
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
    )


def _grid_call(kernel, grid, in_specs, out_specs, out_shape, interpret):
    params = {}
    if not interpret:
        # batch/m/n blocks are independent; k must stay sequential
        # (accumulation) and is always the innermost grid dim.
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1) + ("arbitrary",)
        )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **params,
    )


def _layout(x, y, bm, bn, bk, kc, fill=INF):
    """Shared shape/grid/spec derivation for both kernel wrappers."""
    assert x.ndim in (2, 3) and y.ndim == x.ndim, (x.shape, y.shape)
    batched = x.ndim == 3
    if batched:
        assert x.shape[0] == y.shape[0], (x.shape, y.shape)
    m, k = x.shape[-2], x.shape[-1]
    k2, n = y.shape[-2], y.shape[-1]
    assert k == k2, (x.shape, y.shape)
    bm, bn = min(bm, _rup(m, 8)), min(bn, _rup(n, 128))
    kc = min(kc, _rup(k, 8))
    bk = min(_rup(bk, kc), _rup(k, kc))
    xp = _pad(x, bm, bk, fill)
    yp = _pad(y, bk, bn, fill)
    mp, kp = xp.shape[-2], xp.shape[-1]
    np_ = yp.shape[-1]
    grid = (mp // bm, np_ // bn, kp // bk)
    out_dims = (mp, np_)
    if batched:
        grid = (x.shape[0],) + grid
        out_dims = (x.shape[0],) + out_dims
    x_spec, y_spec, z_spec = _specs(batched, bm, bn, bk)
    return batched, m, n, xp, yp, grid, x_spec, y_spec, z_spec, out_dims, kc


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "kc", "accumulate", "interpret", "semiring"),
)
def minplus_pallas(
    x: jax.Array,
    y: jax.Array,
    a: Optional[jax.Array] = None,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    kc: int = DEFAULT_KC,
    accumulate: bool = False,
    interpret: bool = False,
    semiring: Semiring = TROPICAL,
) -> jax.Array:
    """Z = ⊕_k x[:,k] ⊗ y[k,:]  (optionally fused Z = a ⊕ (...)).

    Shapes need not be tile-aligned: panels are padded with the semiring
    zero (inert under ⊕, annihilating under ⊗) and the result is sliced
    back.  (G, ., .) operands run the whole batch on one kernel grid
    (leading batch dimension).
    """
    z, _ = _call(x, y, a, bm=bm, bn=bn, bk=bk, kc=kc, accumulate=accumulate,
                 track=False, interpret=interpret, sr=semiring)
    return z


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "kc", "accumulate", "interpret", "semiring"),
)
def minplus_argmin_pallas(
    x: jax.Array,
    y: jax.Array,
    a: Optional[jax.Array] = None,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    kc: int = DEFAULT_KC,
    accumulate: bool = False,
    interpret: bool = False,
    semiring: Semiring = TROPICAL,
) -> Tuple[jax.Array, jax.Array]:
    """(Z, K*) with fused running witness (global k ids; -1 = no winner).

    Semantics match ``ref.minplus_argmin_ref`` / ``ref.minplus_acc_argmin_ref``:
    without ``accumulate`` ties resolve to the smallest k (the running
    ``better(cand, acc)`` comparison is strict, so the first — smallest-k —
    winner is kept, and a fully-unreachable entry never improves on the
    semiring-zero init and keeps K* = -1, matching the oracle's is_zero
    mask); with it, strict improvement over ``a`` is required (K* = -1
    where ``a`` was kept).  Batched (G, ., .) operands run on one kernel
    grid.
    """
    return _call(x, y, a, bm=bm, bn=bn, bk=bk, kc=kc, accumulate=accumulate,
                 track=True, interpret=interpret, sr=semiring)


def _call(x, y, a, *, bm, bn, bk, kc, accumulate, track, interpret, sr):
    """Shared body of both wrappers: pad, grid, one ``pallas_call``, slice."""
    batched, m, n, xp, yp, grid, x_spec, y_spec, z_spec, out_dims, kc = _layout(
        x, y, bm, bn, bk, kc, sr.zero
    )
    kern = functools.partial(
        _kernel, kc=kc, bk=xp.shape[-1] // grid[-1], k_axis=len(grid) - 1,
        sr=sr, accumulate=accumulate, track=track,
    )
    in_specs, operands = [x_spec, y_spec], [xp, yp]
    if accumulate:
        assert a is not None and a.shape[-2:] == (m, n)
        ap = _pad(a, z_spec.block_shape[-2], z_spec.block_shape[-1], sr.zero)
        in_specs, operands = [z_spec] + in_specs, [ap] + operands
    z_shape = jax.ShapeDtypeStruct(out_dims, x.dtype)
    if track:
        i_shape = jax.ShapeDtypeStruct(out_dims, jnp.int32)
        fn = _grid_call(kern, grid, in_specs, (z_spec, z_spec),
                        (z_shape, i_shape), interpret)
        zp, ip = fn(*operands)
        return zp[..., :m, :n], ip[..., :m, :n]
    zp = _grid_call(kern, grid, in_specs, z_spec, z_shape, interpret)(*operands)
    return zp[..., :m, :n], None


def _rup(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


# Raw (unjitted) builders for the kernel grid verifier
# (``repro.analysis.kernelcheck``): interception replaces ``pl.pallas_call``
# at trace time, and the jit cache would silently skip retraces of
# already-seen shapes, so the verifier drives these directly.
PALLAS_BUILDERS = {
    "minplus_pallas": minplus_pallas.__wrapped__,
    "minplus_argmin_pallas": minplus_argmin_pallas.__wrapped__,
}
