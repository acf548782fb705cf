"""Concolic Pallas grid interpreter: run the real kernel body per grid point.

Faithful to the TPU execution model the kernels rely on: the grid is walked
in lexicographic order with the *last* axis fastest (Pallas's sequential
order; parallel axes may be reordered by the hardware, but the race theorem
in ``verify`` separately proves reordering cannot matter), block refs are
views into the padded operands (so an output tile revisited along the
sequential axis carries its accumulated value, exactly the TPU revisit
guarantee), and ``pl.program_id`` / ``pl.num_programs`` / ``pl.when`` are
patched to the concrete coordinates of the current point.

Output buffers are seeded with a **canary** (NaN for floats, INT32_MIN for
the int32 witness planes) instead of zeros: a kernel that accumulates into
a tile before its ``pl.when(program_id == 0)`` init ran reads the canary,
and every semiring's selective ⊕ propagates it to the final output, where
the differential theorem reports it as an uninitialized accumulate rather
than a generic mismatch.

Every tile — input, output, and the scalar-prefetch ``rows[i]`` gather —
is bounds-checked against its operand's (padded) extent *before* the body
runs; a violating grid point records the violation and is skipped (numpy
would silently clip the view, masking the bug with a shape error or, worse,
wrong data).  A ``None`` (squeezed) block dim is a unit tile whose axis the
kernel does not see.

Kernel bodies index their refs with ``pl.ds`` windows and walk them with
``jax.lax.fori_loop``; while a body runs, the loop is a plain Python loop
and ``pl.multiple_of`` the identity, so every window offset is a concrete
int and every ``pl.ds`` a numpy slice.  A ref slice that leaves its block
raises, like Mosaic's bounds check.  VMEM scratch buffers are allocated once
per call (seeded with the canary) and persist across grid points, as the
TPU keeps them across the sequential walk.  Each scratch element remembers
the grid step that last wrote it, and every read of a value written at an
earlier step is recorded in ``call.carries``, for the scratch-carry theorem
in ``verify``.
"""

from __future__ import annotations

import contextlib
from typing import List, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import pallas as _pallas

from .intercept import KernelCall

__all__ = ["simulate", "block_index", "tile_slices", "INT_CANARY"]

INT_CANARY = np.iinfo(np.int32).min


class _Ref:
    """Mutable view standing in for a Pallas Ref (read/write/shape)."""

    __slots__ = ("a",)

    def __init__(self, a: np.ndarray):
        self.a = a

    @property
    def shape(self):
        return self.a.shape

    @property
    def dtype(self):
        return self.a.dtype

    def __getitem__(self, idx):
        return self.a[self._np_index(idx)]

    def __setitem__(self, idx, val):
        self.a[self._np_index(idx)] = np.asarray(val)

    def _np_index(self, idx):
        """Translate ``pl.ds`` windows to numpy slices, refusing any window
        that leaves the block (numpy would clip it silently)."""
        idx = idx if isinstance(idx, tuple) else (idx,)
        out = []
        for d, i in enumerate(idx):
            if isinstance(i, _pallas.Slice):
                lo, size = int(i.start), int(i.size)
                if lo < 0 or lo + size * int(i.stride) > self.a.shape[d]:
                    raise IndexError(
                        f"ref window [{lo}, {lo + size}) outside block dim "
                        f"{d} of extent {self.a.shape[d]}"
                    )
                i = slice(lo, lo + size * int(i.stride), int(i.stride))
            elif not isinstance(i, (slice, type(Ellipsis))):
                i = int(i)
            out.append(i)
        return tuple(out)


class _Scratch(_Ref):
    """A VMEM scratch ref that records values carried between grid steps."""

    __slots__ = ("writer", "step", "index", "carries")

    def __init__(self, a: np.ndarray, index: int, carries: set):
        super().__init__(a)
        self.writer = np.full(a.shape, -1, np.int64)   # step that last wrote
        self.step = -1                                 # step now running
        self.index = index
        self.carries = carries

    def __getitem__(self, idx):
        sl = self._np_index(idx)
        for w in np.unique(self.writer[sl]):
            if 0 <= w < self.step:
                self.carries.add((self.index, int(w), self.step))
        return self.a[sl]

    def __setitem__(self, idx, val):
        sl = self._np_index(idx)
        self.a[sl] = np.asarray(val)
        self.writer[sl] = self.step


def _fori_loop(lower, upper, body, init, **_kw):
    """``jax.lax.fori_loop`` as a Python loop over concrete indices."""
    carry = init
    for i in range(int(lower), int(upper)):
        carry = body(i, carry)
    return carry


@contextlib.contextmanager
def _patched_pl(point: Tuple[int, ...], grid: Tuple[int, ...]):
    """Bind ``pl.program_id``/``num_programs``/``when`` to one grid point,
    and run in-body loops eagerly (see the module docstring)."""
    saved = (_pallas.program_id, _pallas.num_programs, _pallas.when,
             _pallas.multiple_of, jax.lax.fori_loop)

    def when(cond):
        def deco(fn):
            if bool(cond):
                fn()
            return fn

        return deco

    _pallas.program_id = lambda axis: point[axis]
    _pallas.num_programs = lambda axis: grid[axis]
    _pallas.when = when
    _pallas.multiple_of = lambda x, _m: x
    jax.lax.fori_loop = _fori_loop
    try:
        yield
    finally:
        (_pallas.program_id, _pallas.num_programs, _pallas.when,
         _pallas.multiple_of, jax.lax.fori_loop) = saved


def block_index(spec, point: Sequence[int], prefetch) -> Tuple[int, ...]:
    """Evaluate a BlockSpec index map at one concrete grid point."""
    idx = spec.index_map(*point, *prefetch)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(i) for i in idx)


def tile_slices(
    idx: Tuple[int, ...],
    block_shape: Tuple[int, ...],
    extent: Tuple[int, ...],
    *,
    where: str,
    errors: List[str],
) -> Tuple[slice, ...]:
    """Element slices of one tile, recording any out-of-bounds dimension.

    Blocked-mode semantics: the index map returns *block* indices, the tile
    spans ``[idx*bs, (idx+1)*bs)`` per dimension; a ``None`` (squeezed) dim
    is a unit tile indexed by an int, so the view drops that axis.
    """
    sl = []
    for d, (i, bs, n) in enumerate(zip(idx, block_shape, extent)):
        size = 1 if bs is None else bs
        lo, hi = i * size, (i + 1) * size
        if lo < 0 or hi > n:
            errors.append(
                f"bounds: {where}: dim {d} tile [{lo}, {hi}) outside the "
                f"operand extent {n} (block index {i} x block {bs})"
            )
        sl.append(lo if bs is None else slice(lo, hi))
    return tuple(sl)


def _canary(shape, dtype) -> np.ndarray:
    dt = np.dtype(dtype)
    if dt.kind in "iu":
        return np.full(shape, INT_CANARY, dt)
    return np.full(shape, np.nan, dt)


def simulate(call: KernelCall) -> List[np.ndarray]:
    """Execute every grid point of one recorded call; returns output leaves.

    Bounds violations land in ``call.errors`` (grid points carrying one are
    recorded and skipped); outputs start as canaries so uninitialized
    accumulates survive into the differential comparison.
    """
    prefetch = [np.asarray(p) for p in call.prefetch]
    ins = [np.asarray(a) for a in call.inputs]
    outs = [_canary(s.shape, s.dtype) for s in call.out_shapes]
    if len(ins) != len(call.in_specs):
        call.errors.append(
            f"bounds: operand/spec arity mismatch: {len(ins)} non-prefetch "
            f"operands vs {len(call.in_specs)} in_specs"
        )
        return outs

    scratch = [_Scratch(_canary(s.shape, s.dtype), i, call.carries)
               for i, s in enumerate(call.scratch_shapes)]
    for step, point in enumerate(np.ndindex(*call.grid)):
        for ref in scratch:
            ref.step = step
        point_errors: List[str] = []
        views = []
        operands = [(a, s, f"input {i}") for i, (a, s) in
                    enumerate(zip(ins, call.in_specs))]
        operands += [(o, s, f"output {i}") for i, (o, s) in
                     enumerate(zip(outs, call.out_specs))]
        for arr, spec, what in operands:
            idx = block_index(spec, point, prefetch)
            views.append((arr, tile_slices(
                idx, tuple(spec.block_shape), arr.shape,
                where=f"grid point {point}: {what}", errors=point_errors,
            )))
        if point_errors:
            call.errors.extend(point_errors)
            continue
        refs = [_Ref(p) for p in prefetch] + [_Ref(a[sl]) for a, sl in views]
        with _patched_pl(tuple(point), call.grid):
            try:
                call.kernel(*refs, *scratch)
            except IndexError as e:
                call.errors.append(f"bounds: grid point {point}: {e}")
    return outs
