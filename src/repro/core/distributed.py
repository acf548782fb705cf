"""Distributed APSP — the paper's future-work item ("use multiple devices").

The distance matrix D (N, N) lives as a 2D block grid over the device mesh:
rows sharded over ``row_axes`` (single-pod: ``("data",)``; multi-pod:
``("pod", "data")`` so the pod axis carries row-parallelism), columns over
``col_axes`` (``("model",)``).  Everything below is ``jax.shard_map`` with
explicit collectives, so the compiled HLO shows exactly the communication
each solver pays.

Three solvers:

* ``summa_minplus``      — tropical SUMMA: k-panel loop, each panel broadcast
                           along the orthogonal mesh axis, local min-plus
                           accumulation.  O(N^2 (1/nr + 1/nc)) bytes moved per
                           product, O(panel) live memory.
* ``squaring_distributed`` — paper-faithful FW-GPU at scale: ceil(log2 N)
                           SUMMA squarings.
* ``fw_distributed``     — distributed 3-phase blocked FW: per pivot tile,
                           close on every device (replicated B^3 — cheaper
                           than a round-trip), broadcast the row panel along
                           the row axes and the col panel along the col axes,
                           then one local fused min-plus-accumulate.

Broadcasts are masked ``psum``s (contribute the panel iff you own it): a
collective XLA already knows how to schedule on ICI, and one that shows up
unambiguously in the HLO for the collective-bytes term.

``rkleene_distributed`` runs the R-Kleene recursion at the host level over
global sharded arrays, with every quadrant product a ``summa_minplus`` and
leaves closed by ``fw_distributed`` — the "divide the tensor" answer to the
paper's memory wall.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .semiring import INF, TROPICAL, Semiring, ceil_log2


def _kops():
    from repro.kernels import ops  # lazy: avoids import cycle

    return ops
from .blocked_fw import closure_block

__all__ = [
    "summa_minplus",
    "squaring_distributed",
    "fw_distributed",
    "rkleene_distributed",
    "apsp_distributed",
    "dist_spec",
]


def dist_spec(multi_pod: bool = False) -> P:
    """PartitionSpec of the distributed distance matrix on our meshes."""
    return P(("pod", "data"), "model") if multi_pod else P("data", "model")


def _axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _bcast(value: jax.Array, axes, src, my_index) -> jax.Array:
    """Broadcast ``value`` from the shard(s) with ``my_index == src`` along
    ``axes`` — masked psum (everyone else contributes zeros)."""
    contrib = jnp.where(my_index == src, value, jnp.zeros_like(value))
    return lax.psum(contrib, axes)


def _shard_map(body, mesh: Mesh, in_specs, out_spec):
    """``jax.shard_map`` without varying-axis checks: the per-shard bodies
    call Pallas kernels, whose output shapes carry no varying-axis type."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)


def _panel_coords(p, k_shard: int, panels_per_shard: int, panel: int):
    """Which shard owns global k-panel ``p``, and the local offset inside it."""
    shard = p // panels_per_shard
    off = (p % panels_per_shard) * panel
    return shard, off


@partial(jax.jit, static_argnames=("mesh", "row_axes", "col_axes", "semiring"))
def summa_minplus(
    x: jax.Array,
    y: jax.Array,
    acc: jax.Array | None = None,
    *,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axes: Tuple[str, ...] = ("model",),
    semiring: Semiring = TROPICAL,
) -> jax.Array:
    """Semiring SUMMA (tropical by default): Z = X (x) Y on the 2D block grid.

    Panel count = lcm(nr, nc) so it works on non-square grids (the multi-pod
    (32-row, 16-col) layout).  Per panel: X's (m_l, k/P) column slice is
    broadcast along ``col_axes`` from its owner, Y's (k/P, n_l) row slice
    along ``row_axes``, then a local fused min-plus accumulate.

    ``acc`` (same sharding as Z) fuses Z = acc (+) X (x) Y: it seeds the
    panel loop's running ⊕, so the accumulate costs no second pass over
    the output shards.  The masked-psum broadcasts are untouched by the
    semiring choice — non-owners contribute arithmetic zeros and exactly
    one shard contributes the panel, so any payload value survives.
    """
    sr = semiring
    nr = _axes_size(mesh, row_axes)
    nc = _axes_size(mesh, col_axes)
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    npanels = math.lcm(nr, nc)
    assert k % npanels == 0, (k, npanels)
    panel = k // npanels
    x_pps = npanels // nc   # x k-panels per column shard
    y_pps = npanels // nr   # y k-panels per row shard

    spec = P(tuple(row_axes), tuple(col_axes))

    def body(xl: jax.Array, yl: jax.Array, *rest) -> jax.Array:
        r = lax.axis_index(tuple(row_axes)) if len(row_axes) > 1 else lax.axis_index(row_axes[0])
        c = lax.axis_index(tuple(col_axes)) if len(col_axes) > 1 else lax.axis_index(col_axes[0])
        m_l = xl.shape[0]
        n_l = yl.shape[1]

        def step(p, a):
            xc, xoff = _panel_coords(p, k // nc, x_pps, panel)
            yc, yoff = _panel_coords(p, k // nr, y_pps, panel)
            xp = lax.dynamic_slice(xl, (0, xoff), (m_l, panel))
            yp = lax.dynamic_slice(yl, (yoff, 0), (panel, n_l))
            xp = _bcast(xp, tuple(col_axes), xc, c)
            yp = _bcast(yp, tuple(row_axes), yc, r)
            return _kops().minplus(xp, yp, a, semiring=sr)  # fused local accumulate

        if rest:
            acc0 = rest[0]                          # fused Z = min(acc, X(x)Y)
        else:
            acc0 = jnp.full((m_l, n_l), sr.zero, x.dtype)
        return lax.fori_loop(0, npanels, step, acc0)

    specs = (spec, spec) if acc is None else (spec, spec, spec)
    fn = _shard_map(body, mesh, specs, spec)
    return fn(x, y) if acc is None else fn(x, y, acc)


@partial(jax.jit, static_argnames=("mesh", "row_axes", "col_axes", "iters", "semiring"))
def squaring_distributed(
    h: jax.Array,
    *,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axes: Tuple[str, ...] = ("model",),
    iters: int | None = None,
    semiring: Semiring = TROPICAL,
) -> jax.Array:
    """Paper-faithful FW-GPU at scale: D <- D (+) D (x) D, ceil(log2 N) times."""
    n = h.shape[0]
    it = ceil_log2(n) if iters is None else iters

    def body(_, d):
        return summa_minplus(
            d, d, d, mesh=mesh, row_axes=row_axes, col_axes=col_axes,
            semiring=semiring,
        )

    return lax.fori_loop(0, it, body, h)


@partial(jax.jit, static_argnames=("mesh", "row_axes", "col_axes", "block_size", "semiring"))
def fw_distributed(
    h: jax.Array,
    *,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axes: Tuple[str, ...] = ("model",),
    block_size: int = 512,
    semiring: Semiring = TROPICAL,
) -> jax.Array:
    """Distributed 3-phase blocked Floyd-Warshall (O(N^3) work total).

    Requires ``block_size`` to divide the local shard in both dims.  Per
    pivot t: replicated pivot closure; row panel (B, n_l) broadcast along
    the row axes; col panel (m_l, B) broadcast along the col axes; one local
    min-plus accumulate touches every local element once.
    """
    sr = semiring
    nr = _axes_size(mesh, row_axes)
    nc = _axes_size(mesh, col_axes)
    n = h.shape[0]
    b = block_size
    assert n % (nr * b) == 0 and n % (nc * b) == 0, (n, nr, nc, b)
    nblk = n // b
    spec = P(tuple(row_axes), tuple(col_axes))

    def body(dl: jax.Array) -> jax.Array:
        r = lax.axis_index(tuple(row_axes)) if len(row_axes) > 1 else lax.axis_index(row_axes[0])
        c = lax.axis_index(tuple(col_axes)) if len(col_axes) > 1 else lax.axis_index(col_axes[0])
        m_l, n_l = dl.shape          # n/nr, n/nc
        bpr = m_l // b               # pivot blocks per row shard
        bpc = n_l // b

        def pivot_step(t, d):
            orow, roff = t // bpr, (t % bpr) * b   # owner row shard, local row offset
            ocol, coff = t // bpc, (t % bpc) * b

            # -- phase 1: extract pivot block, broadcast, close everywhere --
            mine = jnp.logical_and(r == orow, c == ocol)
            pv = lax.dynamic_slice(d, (roff, coff), (b, b))
            pv = jnp.where(mine, pv, jnp.zeros_like(pv))
            pv = lax.psum(pv, tuple(row_axes) + tuple(col_axes))
            pv = closure_block(pv, sr)

            # -- phase 2a: row panel (pivot rows x my cols), owner row computes
            rp = lax.dynamic_slice(d, (roff, 0), (b, n_l))
            rp = _kops().minplus(pv, rp, semiring=sr)  # pivot diag one => subsumes old
            rp = _bcast(rp, tuple(row_axes), orow, r)

            # -- phase 2b: col panel (my rows x pivot cols), owner col computes
            cp = lax.dynamic_slice(d, (0, coff), (m_l, b))
            cp = _kops().minplus(cp, pv, semiring=sr)
            # owner-row devices overwrite their pivot rows with the closed
            # pivot so phase 3 re-derives the row/col panels exactly.
            cp_fixed = lax.dynamic_update_slice(cp, pv, (roff, 0))
            cp = jnp.where(r == orow, cp_fixed, cp)
            cp = _bcast(cp, tuple(col_axes), ocol, c)

            # -- phase 3: one fused local update touches all of d once --
            return _kops().minplus(cp, rp, d, semiring=sr)

        return lax.fori_loop(0, nblk, pivot_step, dl)

    return _shard_map(body, mesh, (spec,), spec)(h)


def rkleene_distributed(
    h: jax.Array,
    *,
    mesh: Mesh,
    row_axes: Tuple[str, ...] = ("data",),
    col_axes: Tuple[str, ...] = ("model",),
    leaf: int = 4096,
    block_size: int = 512,
    semiring: Semiring = TROPICAL,
) -> jax.Array:
    """R-Kleene over the 2D block grid: host-level recursion, SUMMA products,
    leaves closed with the distributed blocked FW.

    The paper's §5 asks to "divide the 3D-Tensor L" — this divides the
    *problem* instead (quadrant recursion), with every product streamed
    through SUMMA panels, so nothing N^3-sized ever exists.
    """
    n = h.shape[0]

    def mp(x, y, acc=None):
        return summa_minplus(
            x, y, acc, mesh=mesh, row_axes=row_axes, col_axes=col_axes,
            semiring=semiring,
        )

    nr = _axes_size(mesh, row_axes)
    nc = _axes_size(mesh, col_axes)

    def rk(d):
        m = d.shape[0]
        if m <= leaf:
            # pivot tile must divide the leaf's local shard in both dims
            b = min(block_size, m // nr, m // nc)
            return fw_distributed(
                d, mesh=mesh, row_axes=row_axes, col_axes=col_axes,
                block_size=max(b, 1), semiring=semiring,
            )
        half = m // 2
        a, bq = d[:half, :half], d[:half, half:]
        cq, dd = d[half:, :half], d[half:, half:]
        a = rk(a)
        bq = mp(a, bq)
        cq = mp(cq, a)
        dd = mp(cq, bq, acc=dd)         # fused quadrant accumulate
        dd = rk(dd)
        bq = mp(bq, dd)
        cq = mp(dd, cq)
        a = mp(bq, cq, acc=a)
        top = jnp.concatenate([a, bq], axis=1)
        bot = jnp.concatenate([cq, dd], axis=1)
        return jnp.concatenate([top, bot], axis=0)

    return rk(h)


def apsp_distributed(
    h: jax.Array,
    *,
    mesh: Mesh,
    method: str = "fw",
    multi_pod: bool = False,
    block_size: int = 512,
    semiring: Semiring = TROPICAL,
) -> jax.Array:
    """Place a (padded) cost matrix on the mesh and solve.

    Pads N up so every shard divides evenly (phantom unreachable nodes), runs
    the requested distributed solver, slices back.
    """
    row_axes = ("pod", "data") if multi_pod else ("data",)
    col_axes = ("model",)
    nr = _axes_size(mesh, row_axes)
    nc = _axes_size(mesh, col_axes)
    n = h.shape[0]
    if method in ("fw", "rkleene"):
        # blocked solvers: the pivot tile must divide every shard evenly
        mult = block_size * math.lcm(nr, nc)
    else:
        # squaring: shards + SUMMA panels must divide evenly
        mult = math.lcm(nr, nc)
    from .semiring import get_semiring, pad_to_multiple

    semiring = get_semiring(semiring)
    d = pad_to_multiple(h, mult, semiring)
    spec = dist_spec(multi_pod)
    d = jax.device_put(d, NamedSharding(mesh, spec))
    if method == "squaring":
        out = squaring_distributed(
            d, mesh=mesh, row_axes=row_axes, col_axes=col_axes, semiring=semiring
        )
    elif method == "fw":
        out = fw_distributed(
            d, mesh=mesh, row_axes=row_axes, col_axes=col_axes,
            block_size=block_size, semiring=semiring,
        )
    elif method == "rkleene":
        out = rkleene_distributed(
            d, mesh=mesh, row_axes=row_axes, col_axes=col_axes,
            block_size=block_size, semiring=semiring,
        )
    else:
        raise ValueError(f"unknown distributed method {method!r}")
    return out[:n, :n]
