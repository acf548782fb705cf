"""Unified APSP front-end — the paper's technique as a framework feature.

``solve(h, method=...)`` dispatches one dense cost matrix to the registered
solvers:

* ``"squaring"``    — paper-faithful FW-GPU (tropical matrix squaring)
* ``"squaring_3d"`` — paper-faithful *and* memory-faithful (N×N×N broadcast)
* ``"classic"``     — textbook O(n^3) Floyd-Warshall
* ``"blocked_fw"``  — 3-phase tiled FW (TPU-shaped, O(n^3))
* ``"rkleene"``     — R-Kleene divide & conquer (paper §3.3)

``solve_batch(hs, method=...)`` is the multi-graph engine: it takes a
(G, N, N) stack *or* a ragged list of per-graph matrices, inf-pads to a
common edge (padding is inert under (min, +): phantom nodes have no edges,
so no real distance ever routes through them), and runs a batched solver —
one compiled XLA program and one kernel launch per phase for the whole
batch instead of a dispatch round-trip per graph.  ``squaring``,
``classic``, and ``blocked_fw`` have natively batched implementations
(``blocked_fw`` closes all G pivot blocks with a single (G, B, B)
``fw_block`` dispatch); every other registered method is lifted with
``jax.vmap``.  Results match per-graph ``solve()`` exactly.

Every registered solver's panel/quadrant products run on the fused
``repro.kernels.ops`` dispatch (fused accumulate + fused argmin for
predecessors), with block sizes served from the persistent autotune cache
(``repro.kernels.autotune``; ``REPRO_AUTOTUNE*`` env vars) — tune before
first solve of a shape to get measured winners instead of defaults.

Both entry points take ``semiring=`` (a registry name or
``repro.core.semiring.Semiring`` instance): the same solvers then compute
widest paths (``"bottleneck"``), most-reliable paths (``"reliability"``),
or transitive closure (``"boolean"``) instead of shortest paths.  Input
conventions per semiring: off-diagonal "no edge" entries are the semiring
zero, the diagonal is the semiring one (tropical: inf / 0).  The default
``"tropical"`` is bit-exact with the pre-registry solvers.

Distributed execution lives in ``core/distributed.py`` and is selected via
``launch/apsp_run.py`` on a real mesh; the serving loop over batches lives
in ``launch/serve.py``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .blocked_fw import blocked_fw, blocked_fw_batch
from .errors import InputValidationError, NegativeCycleError
from .floyd_warshall import (
    fw_classic,
    fw_classic_batch,
    fw_squaring,
    fw_squaring_batch,
)
from .rkleene import rkleene
from .semiring import TROPICAL, Semiring, SemiringLike, get_semiring
from .spans import (
    DISPATCH, FETCH, NEGCYCLE, PACK, SCATTER, SOLVE, SOLVE_BATCH, VALIDATE,
    span,
)

__all__ = [
    "APSPResult",
    "BatchAPSPResult",
    "solve",
    "solve_batch",
    "pad_batch",
    "METHODS",
    "BATCH_METHODS",
    "register_method",
    "validate_cost_matrix",
    "check_negative_cycles",
]


@jax.jit
def _any_nan(h):
    return jnp.isnan(h).any()


@jax.jit
def _diagonal(d):
    return jnp.diagonal(d, axis1=-2, axis2=-1)


def validate_cost_matrix(h, semiring: SemiringLike = "tropical") -> None:
    """Input-boundary contract check shared by ``solve`` / ``solve_batch`` /
    ``DynamicAPSP``: NaN entries are rejected with a typed
    :class:`~repro.core.errors.InputValidationError` *before* any dispatch —
    a NaN is absorbing under every registered ⊕/⊗ pair, so one poisoned
    entry silently corrupts the whole closure.  Works on a single (n, n)
    matrix or a (G, n, n) stack.  Every entry is scanned: a device input by
    one reduction on the device, whose one-byte verdict is all that comes
    to the host (the whole input only when it holds a NaN, to name the
    first); a host input on the host.  Pass ``validate=False`` at the entry
    points on hot paths that already guarantee clean inputs."""
    if _on_device(h) and not bool(_any_nan(h)):
        return
    a = np.asarray(h)
    bad = np.isnan(a)
    if bad.any():
        idx = tuple(int(x) for x in np.argwhere(bad)[0])
        sr = get_semiring(semiring)
        raise InputValidationError(
            f"cost matrix contains {int(bad.sum())} NaN entr"
            f"{'y' if bad.sum() == 1 else 'ies'} (first at {idx}): NaN is "
            f"absorbing under the {sr.name!r} semiring and would poison the "
            "whole closure.  Clean the input (no-edge is the semiring zero, "
            f"{sr.zero!r}) or pass validate=False to skip this check."
        )


def check_negative_cycles(
    dist, semiring: Semiring, sizes: Optional[np.ndarray] = None,
    diagonal: Optional[jax.Array] = None,
) -> None:
    """Tropical-only post-solve contract check: a strictly negative entry on
    the *solved* diagonal means the graph contains a negative cycle, so
    shortest-path distances are unbounded below and the returned matrix is
    not meaningful — raise :class:`~repro.core.errors.NegativeCycleError`
    instead of handing it back.  Detecting on the closure (not the input)
    is exact: negative *edges* are fine, only a closed negative *walk*
    drives ``dist[i, i]`` below the diagonal's one (0).  Accepts (n, n) or
    (G, n, n); ``sizes`` restricts each graph's check to its true block
    (padding diagonals are the semiring one by construction).  Only the
    diagonal is read: a device ``dist`` has it gathered on the device and
    copies those n (or G·n) entries to the host, nothing else; a caller
    whose program already gathered it passes it as ``diagonal``."""
    if semiring.name != "tropical":
        return
    if diagonal is None:
        diagonal = (_diagonal(dist) if _on_device(dist)
                    else np.diagonal(dist, axis1=-2, axis2=-1))
    diag = np.asarray(diagonal)
    neg = diag < 0
    if sizes is not None:
        neg = neg & (np.arange(diag.shape[-1]) < np.asarray(sizes)[:, None])
    if neg.any():
        idx = tuple(int(x) for x in np.argwhere(neg)[0])
        raise NegativeCycleError(
            f"negative cycle detected: solved diagonal entry {idx} is "
            f"{float(diag[neg].min()):g} < 0, so tropical distances are "
            "unbounded below.  Remove the cycle or pass validate=False to "
            "skip this check (the returned matrix would be meaningless)."
        )


def _on_device(a) -> bool:
    return isinstance(a, jax.Array)


def _device_bytes(a) -> int:
    """Bytes a host read of ``a`` copies off the device (0 for host data)."""
    return a.nbytes if _on_device(a) else 0


def _pred_bytes(pred) -> int:
    """Bytes of int32 predecessor state a solver made on the device."""
    return 0 if pred is None else pred.nbytes


def _validate_counts(hs) -> Dict[str, int]:
    """Span counts of :func:`validate_cost_matrix` over ``hs`` (one matrix
    or stack, or a list of them) that holds no NaN: each device input sends
    its one-byte verdict to the host; a host input moves nothing."""
    on = sum(map(_on_device, [hs] if hasattr(hs, "ndim") else hs))
    return {"bytes_to_host": on, "on_device": on}


def _negcycle_counts(dist, semiring: Semiring) -> Dict[str, int]:
    """Span counts of :func:`check_negative_cycles`: a device ``dist``'s
    diagonal is what crosses to the host (tropical only)."""
    if semiring.name != "tropical" or not _on_device(dist):
        return {"bytes_to_host": 0, "on_device": 0}
    diag = dist.nbytes // dist.shape[-1]
    return {"bytes_to_host": diag, "on_device": 1}


@dataclass
class APSPResult:
    dist: jax.Array
    pred: Optional[jax.Array]
    method: str


@dataclass
class BatchAPSPResult:
    """Batched APSP result over G graphs padded to a common edge N.

    ``dist``/``pred`` are (G, N, N); ``sizes[i]`` is graph i's true node
    count — entries at index >= sizes[i] are padding (inf off-diagonal / 0
    diagonal distances, -1 / identity predecessors).
    """

    dist: jax.Array                # (G, N, N)
    pred: Optional[jax.Array]      # (G, N, N) or None
    sizes: np.ndarray              # (G,) true node counts
    method: str

    def __len__(self) -> int:
        return int(self.dist.shape[0])

    def unpadded(self, i: int) -> APSPResult:
        """Graph i's result with the padding sliced off."""
        n = int(self.sizes[i])
        return APSPResult(
            dist=self.dist[i, :n, :n],
            pred=None if self.pred is None else self.pred[i, :n, :n],
            method=self.method,
        )


def _squaring(h, with_pred, semiring=TROPICAL, **kw):
    return fw_squaring(h, with_pred=with_pred, semiring=semiring)


def _squaring_3d(h, with_pred, semiring=TROPICAL, **kw):
    return fw_squaring(h, with_pred=with_pred, use_3d=True, semiring=semiring)


def _classic(h, with_pred, semiring=TROPICAL, **kw):
    return fw_classic(h, with_pred=with_pred, semiring=semiring)


def _blocked(h, with_pred, block_size=None, semiring=TROPICAL, donate=False,
             round_mode=None, **kw):
    return blocked_fw(
        h, block_size=block_size, with_pred=with_pred, semiring=semiring,
        round_mode=round_mode, donate=donate,
    )


def _rkleene(h, with_pred, base=64, semiring=TROPICAL, donate=False, **kw):
    return rkleene(
        h, base=base, with_pred=with_pred, semiring=semiring, donate=donate
    )


METHODS: Dict[str, Callable] = {
    "squaring": _squaring,
    "squaring_3d": _squaring_3d,
    "classic": _classic,
    "blocked_fw": _blocked,
    "rkleene": _rkleene,
}


def _squaring_batch(hs, with_pred, semiring=TROPICAL, **kw):
    return fw_squaring_batch(hs, with_pred=with_pred, semiring=semiring)


def _squaring_3d_batch(hs, with_pred, semiring=TROPICAL, **kw):
    return fw_squaring_batch(
        hs, with_pred=with_pred, use_3d=True, semiring=semiring
    )


def _classic_batch(hs, with_pred, semiring=TROPICAL, **kw):
    return fw_classic_batch(hs, with_pred=with_pred, semiring=semiring)


def _blocked_batch(hs, with_pred, block_size=None, semiring=TROPICAL,
                   donate=False, round_mode=None, **kw):
    return blocked_fw_batch(
        hs, block_size=block_size, with_pred=with_pred, semiring=semiring,
        round_mode=round_mode, donate=donate,
    )


BATCH_METHODS: Dict[str, Callable] = {
    "squaring": _squaring_batch,
    "squaring_3d": _squaring_3d_batch,
    "classic": _classic_batch,
    "blocked_fw": _blocked_batch,
}


def register_method(
    name: str, fn: Callable, batch_fn: Optional[Callable] = None
) -> None:
    """Register a solver.  ``fn(h, with_pred, **kw)`` handles one graph;
    ``batch_fn(hs, with_pred, **kw)``, if given, handles a (G, N, N) stack
    (otherwise ``solve_batch`` lifts ``fn`` with ``jax.vmap``)."""
    METHODS[name] = fn
    if batch_fn is not None:
        BATCH_METHODS[name] = batch_fn
    else:
        # don't leave a stale batched solver behind a re-registered name
        BATCH_METHODS.pop(name, None)


def solve(
    h: jax.Array,
    *,
    method: str = "blocked_fw",
    with_pred: bool = False,
    semiring: SemiringLike = "tropical",
    donate: Optional[bool] = None,
    dtype=None,
    validate: bool = True,
    **kwargs,
) -> APSPResult:
    """Solve the all-pairs path problem on a dense cost matrix.

    Input conventions: off-diagonal "no edge" = semiring zero (tropical:
    inf), diagonal = semiring one (tropical: 0).  ``semiring`` is a
    registry name or instance; see ``repro.core.semiring.SEMIRINGS``.

    ``donate``: None (default) auto-donates the solver input whenever this
    call made a fresh conversion copy of ``h`` (host array or dtype cast) —
    in-place solve with zero aliasing hazard.  ``True`` forces donation (a
    jax-array ``h`` is consumed: reads after the call raise); ``False``
    never donates.  Donation is honored by ``blocked_fw`` and ``rkleene``
    (the in-place solver cores); other methods accept and ignore it.

    ``dtype``: storage dtype for the solve (default float32).
    ``jnp.bfloat16`` selects the mixed-precision mode — bf16 distance
    state with f32 pivot/panel arithmetic, tropical-only, error contract
    in COMPAT.md §Precision & memory.

    ``validate`` (default True): reject NaN input entries with a typed
    ``InputValidationError`` before dispatch, and (tropical only) raise
    ``NegativeCycleError`` when the solved diagonal goes negative instead
    of returning meaningless distances.  A device input is scanned on the
    device and only the verdict comes back; the negative-cycle check copies
    the solved diagonal alone (n entries) to the host.  Both checks wait
    for their device result; pass ``validate=False`` on hot paths with
    guaranteed-clean inputs.
    """
    if method not in METHODS:
        raise ValueError(f"unknown APSP method {method!r}; have {sorted(METHODS)}")
    sr = get_semiring(semiring)
    with span(SOLVE, n=np.shape(h)[-1], with_pred=int(with_pred)):
        if validate:
            with span(VALIDATE, **_validate_counts(h)):
                validate_cost_matrix(h, sr)
        target = jnp.float32 if dtype is None else jnp.dtype(dtype)
        with span(DISPATCH) as sp:
            x = jnp.asarray(h, target)
            if donate is None:
                donate = x is not h               # fresh copy -> safe to consume
            dist, pred = METHODS[method](x, with_pred, semiring=sr,
                                         donate=donate, **kwargs)
            sp.set_metadata(bytes_to_device=0 if _on_device(h) else x.nbytes,
                            pred_bytes=_pred_bytes(pred))
        if validate:
            with span(NEGCYCLE, **_negcycle_counts(dist, sr)):
                check_negative_cycles(dist, sr)
    return APSPResult(dist=dist, pred=pred, method=method)


def pad_batch(
    hs: Union[jax.Array, np.ndarray, Sequence],
    sizes: Optional[Sequence[int]] = None,
    *,
    n_max: Optional[int] = None,
    semiring: SemiringLike = "tropical",
) -> Tuple[jax.Array, np.ndarray]:
    """Pack graphs into a zero-padded (G, N, N) stack + true-size vector.

    Accepts a ragged list of (n_i, n_i) cost matrices or an already-stacked
    (G, N, N) array (with optional ``sizes``; defaults to N for every
    graph).  ``n_max`` forces the padded edge (>= max graph size) so a
    serving loop can keep one compiled shape across batches.  Padding is a
    phantom node: semiring zero off-diagonal, semiring one self-loop —
    inert under every registered semiring (tropical: inf / 0).

    A pre-stacked input with ``sizes[i] < N`` is *not* trusted: only the
    true (sizes[i], sizes[i]) block is kept and the padding region is
    re-inertized.  (An earlier revision returned the stack as-is — garbage
    in the caller's padding, e.g. 0.0 off-diagonal under tropical, became
    free phantom-node shortcuts that corrupted real distances.)
    """
    sr = get_semiring(semiring)
    if hasattr(hs, "ndim") and hs.ndim == 3:
        g, n, _ = hs.shape
        sizes = np.full(g, n) if sizes is None else np.asarray(sizes, np.int64)
        if int(sizes.max(initial=0)) > n:
            raise ValueError(f"sizes {sizes.max()} larger than stack edge {n}")
        full = bool((sizes == n).all())
        if full and (n_max is None or n_max == n):
            return jnp.asarray(hs, jnp.float32), sizes
        # keep only each graph's true block; repack with inert padding below
        mats = [np.asarray(hs[i])[: int(k), : int(k)] for i, k in enumerate(sizes)]
        if n_max is None:
            n_max = n                        # preserve the stack's edge
    else:
        mats = [np.asarray(h) for h in hs]
        if sizes is None:
            sizes = np.array([m.shape[0] for m in mats], np.int64)
        else:
            sizes = np.asarray(sizes, np.int64)
    if not mats:
        raise ValueError("empty graph batch")
    n = int(max(m.shape[0] for m in mats)) if n_max is None else int(n_max)
    if any(m.shape[0] > n for m in mats):
        raise ValueError(f"n_max={n} smaller than largest graph")
    out = np.full((len(mats), n, n), sr.zero, np.float32)
    idx = np.arange(n)
    out[:, idx, idx] = sr.one
    for i, m in enumerate(mats):
        k = m.shape[0]
        out[i, :k, :k] = m
    return jnp.asarray(out), sizes


def _solve_stack(stack, with_pred, method, semiring=TROPICAL, donate=False,
                 **kwargs):
    """Run one (G, N, N) zero-padded stack through the batched solver."""
    batch_fn = BATCH_METHODS.get(method)
    if batch_fn is not None:
        return batch_fn(stack, with_pred, semiring=semiring, donate=donate,
                        **kwargs)
    # vmap fallback: per-slice solvers can't take ownership of the stack,
    # so donation stops here for non-natively-batched methods
    return jax.vmap(
        lambda h: METHODS[method](h, with_pred, semiring=semiring, **kwargs)
    )(stack)


def next_pow2(x: int, floor: int = 1) -> int:
    """Smallest power-of-two >= x, with a floor — the shared bucketing rule
    (batch edges/slots here, update-batch widths in ``core.dynamic``)."""
    e = floor
    while e < x:
        e *= 2
    return e


def _bucket_edge(n: int) -> int:
    """Padded edge for a size-n graph: next power of two, floor 8."""
    return next_pow2(n, 8)


def _bucket_count(c: int) -> int:
    """Padded slot count for a c-graph bucket: next power of two up to 8,
    then next multiple of 8 — keeps the set of compiled (count, edge)
    shapes small and reused across serving cycles."""
    if c <= 8:
        return next_pow2(c)
    return -(-c // 8) * 8


def _input_device_bytes(hs) -> int:
    """Bytes a host read of every graph of a batch copies off the device."""
    if hasattr(hs, "ndim"):
        return _device_bytes(hs)
    return sum(_device_bytes(m) for m in hs)


def _group_by_edge(sizes: np.ndarray) -> Dict[int, List[int]]:
    """Graph indices grouped by padded bucket edge."""
    buckets: Dict[int, List[int]] = {}
    for i, k in enumerate(sizes):
        buckets.setdefault(_bucket_edge(int(k)), []).append(i)
    return buckets


@functools.partial(jax.jit, static_argnames=(
    "slots", "edge", "dtype", "semiring", "with_pred"))
def _staging(slots: int, edge: int, dtype, semiring: Semiring, with_pred: bool):
    """(slots, edge, edge) stacks of inert graphs, the semiring's zero off
    the diagonal and its one on it; with ``with_pred`` also the int32
    predecessor stack (-1, identity diagonal)."""
    dist = jnp.broadcast_to(semiring.eye(edge, dtype), (slots, edge, edge))
    if not with_pred:
        return (dist,)
    idx = jnp.arange(edge, dtype=jnp.int32)
    pred = jnp.where(idx[:, None] == idx[None, :], idx[None, :], -1)
    return dist, jnp.broadcast_to(pred, (slots, edge, edge))


@functools.partial(jax.jit, donate_argnums=0, static_argnames=(
    "with_pred", "method", "solvers", "semiring", "dtype", "solver_kw"))
def _solve_into(staging, stack, offset, *, with_pred, method, solvers,
                semiring, dtype, solver_kw):
    """Solve one bucket's (slots, edge, edge) stack in ``dtype`` and write
    its results into rows ``offset:offset + slots`` of the staging stacks,
    in place.  The staging's edge is at least the bucket's and the rest of
    each row is inert, so a row reads as the graph solved at the larger
    edge.  One program per bucket shape and staging shape, as the solver
    alone.  ``solvers``, the registry's entries for ``method``, are part
    of the program's key: a method registered anew is traced anew.  What
    a solver decides on the host (the autotune table's block size) is
    fixed when its shape is first traced."""
    out = _solve_stack(stack.astype(dtype), with_pred, method,
                       semiring=semiring, **dict(solver_kw))
    return tuple(jax.lax.dynamic_update_slice(s, r.astype(s.dtype),
                                              (offset, 0, 0))
                 for s, r in zip(staging, out))


@functools.partial(jax.jit, static_argnames="n")
def _gather_frame(staging, rows, n: int):
    """The (G, n, n) frame, graph i from staging row ``rows[i]``, and the
    distance frame's diagonal (G, n) for the negative-cycle check."""
    frames = tuple(jnp.take(s, rows, axis=0, mode="clip")[:, :n, :n]
                   for s in staging)
    return frames, jnp.diagonal(frames[0], axis1=-2, axis2=-1)


def _solve_bucketed(
    mats: List[np.ndarray], sizes: np.ndarray, buckets: Dict[int, List[int]],
    n: int, method: str, with_pred: bool, semiring=TROPICAL, dtype=None,
    **kwargs
) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Size-bucketed batched solve: graphs grouped by power-of-two padded
    edge (``buckets``, from :func:`_group_by_edge`), one batched program
    per bucket.  Nothing comes back to the host: each bucket's program
    writes its results into a staging stack on the device (bucket after
    bucket, at the edge of the largest bucket), and one gather puts graph
    i at index i of the common (G, n, n) frame.  So bucket k solves while
    the host packs bucket k+1, and the compiled programs are keyed on the
    bucket and staging shapes, not on the graphs' order.  Bit-identical
    to the single-stack path — padding is inert either way — but a ragged
    corpus does ~size^3 work per graph instead of n_max^3.  ``dtype``
    casts each bucket's stack (bf16 mixed mode) and sets the frame's.
    Returns the frames (dist, then pred with ``with_pred``) and the
    distance frame's diagonal, still on the device."""
    g = len(mats)
    dtype = jnp.dtype(jnp.float32 if dtype is None else dtype)
    slots = {edge: _bucket_count(len(m)) for edge, m in buckets.items()}
    staging = _staging(next_pow2(sum(slots.values())), _bucket_edge(n),
                       dtype, semiring, with_pred)
    solvers = (METHODS[method], BATCH_METHODS.get(method))
    solver_kw = tuple(sorted(kwargs.items()))
    rows = np.empty(g, np.int32)
    offset = 0
    for edge, members in sorted(buckets.items()):
        sub = [mats[i] for i in members]
        sub += [np.zeros((0, 0), np.float32)] * (slots[edge] - len(members))
        with span(PACK, edge=edge, graphs=len(members), slots=slots[edge],
                  useful_work=int(np.sum(sizes[members] ** 3)),
                  padded_work=slots[edge] * edge ** 3) as pack:
            stack, _ = pad_batch(sub, n_max=edge, semiring=semiring)
            pack.set_metadata(bytes_to_device=stack.nbytes)
        with span(DISPATCH, pred_bytes=4 * stack.size if with_pred else 0):
            staging = _solve_into(
                staging, stack, offset, with_pred=with_pred, method=method,
                solvers=solvers, semiring=semiring, dtype=dtype,
                solver_kw=solver_kw)
        rows[members] = offset + np.arange(len(members))
        offset += slots[edge]
    # the row index vector (G int32) is all that crosses
    with span(SCATTER, bytes_to_device=rows.nbytes, on_device=1):
        return _gather_frame(staging, rows, n)


def solve_batch(
    hs: Union[jax.Array, np.ndarray, Sequence],
    sizes: Optional[Sequence[int]] = None,
    *,
    method: str = "blocked_fw",
    with_pred: bool = False,
    n_max: Optional[int] = None,
    bucket_by_size: bool = False,
    semiring: SemiringLike = "tropical",
    donate: Optional[bool] = None,
    dtype=None,
    validate: bool = True,
    **kwargs,
) -> BatchAPSPResult:
    """Solve the all-pairs path problem on a batch of independent graphs in
    one compiled program.

    ``hs`` is a (G, N, N) stack or a ragged list of (n_i, n_i) matrices
    (auto-padded; see :func:`pad_batch`).  Every registered method and
    semiring is supported; results agree with per-graph :func:`solve` on
    the unpadded blocks.  Use :meth:`BatchAPSPResult.unpadded` to slice
    graph i back out.

    ``bucket_by_size=True`` turns on the ragged-batch scheduler: graphs are
    grouped into power-of-two edge buckets and each bucket runs as its own
    batched program (a small, bounded family of compiled shapes instead of
    exactly one), so a mixed-size corpus pays ~size^3 per graph rather than
    n_max^3.  Output is bit-identical to the single-stack path.

    ``donate``/``dtype`` follow :func:`solve`: None auto-donates the
    padded stack whenever packing made a fresh buffer (always, except a
    full-size pre-stacked jax input), halving the resident batch state for
    the natively-batched in-place solvers (the size-bucketed path's stacks
    are its own and write into a staging stack, so ``donate`` does not
    reach them); ``dtype=jnp.bfloat16`` selects
    mixed precision (tropical only).  ``validate`` follows :func:`solve`
    (NaN rejection per graph + tropical negative-cycle detection on each
    unpadded diagonal; ``validate=False`` to skip on hot paths).  Both
    paths keep their results on the device: the negative-cycle check
    copies the solved diagonals (G·N entries) to the host, and with
    ``validate=False`` nothing is read back.
    """
    if method not in METHODS:
        raise ValueError(f"unknown APSP method {method!r}; have {sorted(METHODS)}")
    semiring = get_semiring(semiring)
    with span(SOLVE_BATCH, graphs=len(hs), with_pred=int(with_pred)) as call:
        if validate:
            with span(VALIDATE, graphs=len(hs), **_validate_counts(hs)):
                if hasattr(hs, "ndim"):
                    validate_cost_matrix(hs, semiring)
                else:
                    for m in hs:
                        validate_cost_matrix(m, semiring)
        if bucket_by_size:
            with span(FETCH, bytes_to_host=_input_device_bytes(hs)):
                if hasattr(hs, "ndim") and hs.ndim == 3:
                    mats = [np.asarray(h) for h in hs]
                    sizes_ = (np.full(len(mats), hs.shape[1], np.int64)
                              if sizes is None else np.asarray(sizes, np.int64))
                    mats = [m[:k, :k] for m, k in zip(mats, sizes_)]
                else:
                    mats = [np.asarray(h) for h in hs]
                    sizes_ = (np.array([m.shape[0] for m in mats], np.int64)
                              if sizes is None else np.asarray(sizes, np.int64))
            if not mats:
                raise ValueError("empty graph batch")
            n = int(max(sizes_.max(), 1)) if n_max is None else int(n_max)
            if int(sizes_.max()) > n:
                raise ValueError(f"n_max={n} smaller than largest graph")
            buckets = _group_by_edge(sizes_)
            call.set_metadata(buckets=len(buckets))
            frames, diag = _solve_bucketed(
                mats, sizes_, buckets, n, method, with_pred, semiring=semiring,
                dtype=dtype, **kwargs
            )
            dist, pred = frames if with_pred else (frames[0], None)
            if validate:
                with span(NEGCYCLE, **_negcycle_counts(dist, semiring)):
                    check_negative_cycles(dist, semiring, sizes=sizes_,
                                          diagonal=diag)
            return BatchAPSPResult(dist=dist, pred=pred, sizes=sizes_,
                                   method=method)
        call.set_metadata(buckets=1)
        with span(PACK) as pack:
            stack, sizes = pad_batch(hs, sizes, n_max=n_max, semiring=semiring)
            # a full-size device stack goes straight to the solver; any
            # other input was read on the host and uploaded
            direct = (_on_device(hs) and stack.shape == hs.shape
                      and bool((sizes == hs.shape[1]).all()))
            g, edge = stack.shape[0], stack.shape[-1]
            pack.set_metadata(
                edge=edge, graphs=len(sizes), slots=g,
                useful_work=int(np.sum(sizes ** 3)), padded_work=g * edge ** 3,
                bytes_to_host=0 if direct else _input_device_bytes(hs),
                bytes_to_device=0 if direct else stack.nbytes)
            if dtype is not None:
                stack = stack.astype(jnp.dtype(dtype))
        if donate is None:
            donate = stack is not hs          # fresh packed stack -> consume it
        with span(DISPATCH) as sp:
            dist, pred = _solve_stack(stack, with_pred, method,
                                      semiring=semiring, donate=donate, **kwargs)
            sp.set_metadata(pred_bytes=_pred_bytes(pred))
        if validate:
            with span(NEGCYCLE, **_negcycle_counts(dist, semiring)):
                check_negative_cycles(dist, semiring, sizes=sizes)
    return BatchAPSPResult(dist=dist, pred=pred, sizes=sizes, method=method)
