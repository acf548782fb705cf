"""Tuned public dispatch for the fused min-plus / FW-block kernel surface.

Every solver in ``repro.core`` routes its panel products through this module
— it is the single seam behind which backends (TPU Pallas, interpret-mode
Pallas, chunked XLA fallback, and later GPU/sharded paths) drop in.

The tuned-dispatch contract:

  * **Fused accumulate.**  ``minplus(x, y, a)`` computes
    ``Z = min(A, X (x) Y)`` in one pass; solvers never call an unfused
    product followed by a separate elementwise ``jnp.minimum``.
  * **Fused provenance.**  ``minplus_argmin`` carries the winning global k
    (K* = -1 where nothing improved / nothing is reachable);
    ``minplus_pred`` derives predecessor matrices from K* via
    :func:`pred_from_kstar` — one derivation rule shared by the Pallas and
    XLA backends (lifted from the old ``semiring.minplus_pred``).
  * **Batched lowering.**  (G, ., .) operands are one batched kernel
    dispatch (leading grid dimension on the Pallas path, a single vmapped
    XLA program on the fallback) — never a Python/vmap loop of
    ``pallas_call``.
  * **Self-tuning block sizes.**  Explicit ``**block_kw`` wins; otherwise
    the persistent autotune cache (``repro.kernels.autotune``,
    ``REPRO_AUTOTUNE*`` env vars) is consulted per (shape-bucket, dtype,
    backend, semiring); otherwise compiled-in defaults apply.  The consult
    is a trace-time dict read — no measurement ever runs on the dispatch
    path.
  * **Pluggable semiring.**  Every entry point takes ``semiring=`` (a
    registry name or ``repro.core.semiring.Semiring`` instance; default
    ``"tropical"`` reproduces classic min-plus bit-exactly).  The same
    kernels then compute widest path (``"bottleneck"``), most-reliable
    path (``"reliability"``), and transitive closure (``"boolean"``).

On TPU the Pallas kernels are the hot path.  On this CPU container the
kernels are validated in ``interpret=True`` mode (Python-level execution) by
the test suite, while runtime callers get the chunked pure-XLA fallback from
``repro.kernels.minplus_xla`` — same semantics (bit-exact, see the parity
suite), fast on CPU, and the thing the dry-run lowers.

Backend selection (read at trace time — jit'd callers retrace only on shape
change, so set the env before first use):
  * default                  — pallas on TPU, XLA fallback elsewhere
  * REPRO_KERNELS=interpret  — force pallas interpret mode (kernel tests)
  * REPRO_KERNELS=xla        — force the fallback everywhere
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.semiring import Semiring, SemiringLike, get_semiring

from . import ref
from .fw_block import fw_block_pallas, fw_block_pred_pallas
from .minplus import minplus_argmin_pallas, minplus_pallas
from .minplus_xla import fw_round_xla, minplus_argmin_xla, minplus_xla

__all__ = [
    "minplus",
    "minplus_argmin",
    "minplus_pred",
    "pred_from_kstar",
    "rank_k_update",
    "row_restricted_close",
    "fw_block",
    "fw_block_pred",
    "fw_round",
    "fw_round_pred",
    "backend",
    "MIXED_PRECISION_SEMIRINGS",
]

# Semirings validated for bf16 storage with f32 accumulation (the
# mixed-precision mode).  Tropical-only until the differential oracle has
# pinned an error contract for the others — see COMPAT.md §Precision &
# memory for the tropical bound.
MIXED_PRECISION_SEMIRINGS = ("tropical",)


def backend() -> str:
    env = os.environ.get("REPRO_KERNELS", "")
    if env in ("interpret", "xla", "pallas"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _check_mixed(sr: Semiring, *arrays) -> bool:
    """True when any operand is bf16 (mixed mode); rejects unvalidated
    semirings — the one guard every entry point shares."""
    mixed = any(
        a is not None and a.dtype == jnp.bfloat16 for a in arrays
    )
    if mixed and sr.name not in MIXED_PRECISION_SEMIRINGS:
        raise ValueError(
            f"bf16 mixed-precision min-plus is only validated for semirings "
            f"{list(MIXED_PRECISION_SEMIRINGS)}; semiring {sr.name!r} must "
            f"stay in float32 until its error contract is established "
            f"(COMPAT.md §Precision & memory)"
        )
    return mixed


def _dims(x, y):
    batched = x.ndim == 3
    g = x.shape[0] if batched else 0
    return batched, g, x.shape[-2], x.shape[-1], y.shape[-1]


def _tuned(b: str, x, y, block_kw: dict, sr: Semiring) -> dict:
    """Block params for this dispatch: explicit kwargs win, else the
    autotune cache (keyed per-semiring; tropical keeps the legacy keys);
    either way filtered to the active backend's knobs."""
    if not block_kw:
        from . import autotune  # lazy: cheap, and keeps import order trivial

        batched, g, m, k, n = _dims(x, y)
        block_kw = autotune.lookup(b, x.dtype, m, k, n, g=g, semiring=sr.name)
    keys = ("row_chunk", "k_chunk") if b == "xla" else ("bm", "bn", "bk", "kc")
    return {k_: v for k_, v in block_kw.items() if k_ in keys}


def minplus(
    x: jax.Array,
    y: jax.Array,
    a: Optional[jax.Array] = None,
    *,
    semiring: SemiringLike = "tropical",
    **block_kw,
) -> jax.Array:
    """Z = ⊕_k x[:,k] ⊗ y[k,:]; fused Z = a ⊕ (.) when ``a`` is given.

    2D or batched (G, ., .) operands; ``semiring`` is a registry name or
    instance (default tropical min-plus, bit-exact with the pre-registry
    dispatch); block sizes from ``block_kw`` or the autotune cache (see
    module docstring).
    """
    sr = get_semiring(semiring)
    b = backend()
    mixed = _check_mixed(sr, x, y, a)
    kw = _tuned(b, x, y, block_kw, sr)
    if b == "xla":
        rc, kc = kw.get("row_chunk"), kw.get("k_chunk")
        if x.ndim == 3:
            return jax.vmap(
                lambda xx, yy, aa: minplus_xla(
                    xx, yy, aa, row_chunk=rc, k_chunk=kc, semiring=sr
                )
            )(x, y, a)
        return minplus_xla(x, y, a, row_chunk=rc, k_chunk=kc, semiring=sr)
    if mixed:
        # pallas kernel is dtype-generic; run it in f32 and round once —
        # elementwise identical to the XLA fallback's per-row rounding
        out = x.dtype
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        a = None if a is None else a.astype(jnp.float32)
        z = minplus_pallas(
            x, y, a, accumulate=a is not None, interpret=(b == "interpret"),
            semiring=sr, **kw,
        )
        return z.astype(out)
    return minplus_pallas(
        x, y, a, accumulate=a is not None, interpret=(b == "interpret"),
        semiring=sr, **kw,
    )


def minplus_argmin(
    x: jax.Array,
    y: jax.Array,
    a: Optional[jax.Array] = None,
    *,
    semiring: SemiringLike = "tropical",
    **block_kw,
) -> Tuple[jax.Array, jax.Array]:
    """(Z, K*) with fused global-k witness (see ref for tie/-1 semantics)."""
    sr = get_semiring(semiring)
    b = backend()
    mixed = _check_mixed(sr, x, y, a)
    kw = _tuned(b, x, y, block_kw, sr)
    if b == "xla":
        rc, kc = kw.get("row_chunk"), kw.get("k_chunk")
        if x.ndim == 3:
            return jax.vmap(
                lambda xx, yy, aa: minplus_argmin_xla(
                    xx, yy, aa, row_chunk=rc, k_chunk=kc, semiring=sr
                )
            )(x, y, a)
        return minplus_argmin_xla(x, y, a, row_chunk=rc, k_chunk=kc, semiring=sr)
    if mixed:
        out = x.dtype
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        a = None if a is None else a.astype(jnp.float32)
        z, ks = minplus_argmin_pallas(
            x, y, a, accumulate=a is not None, interpret=(b == "interpret"),
            semiring=sr, **kw,
        )
        return z.astype(out), ks
    return minplus_argmin_pallas(
        x, y, a, accumulate=a is not None, interpret=(b == "interpret"),
        semiring=sr, **kw,
    )


def pred_from_kstar(
    kstar: jax.Array,
    px: jax.Array,
    py: jax.Array,
    *,
    k_offset=0,
    j_offset=0,
    fallback: Optional[jax.Array] = None,
) -> jax.Array:
    """Derive predecessors from argmin winners — the one shared rule.

    ``k* = argmin_k x[i,k] + y[k,j]`` means the combined path is
    i --(x-path)--> k* --(y-path)--> j, so the predecessor of j is
    ``py[k*, j]`` — *unless* the y-path is empty (global index of k* equals
    global index of j, i.e. y contributed its tropical-diagonal zero), in
    which case it is x's own last hop ``px[i, k*]``.

    ``k_offset`` / ``j_offset`` are the global node ids of x's column 0 and
    the output's column 0 (blocked-FW panels / R-Kleene quadrants are tiles
    of a larger matrix).  Where ``kstar < 0`` (nothing improved / nothing
    reachable) the entry comes from ``fallback`` (the pre-update
    predecessors), or -1 when no fallback is given.  Accepts batched
    (G, ., .) operands.
    """
    if kstar.ndim == 3:
        fn = lambda kk, pxx, pyy, fb: pred_from_kstar(
            kk, pxx, pyy, k_offset=k_offset, j_offset=j_offset, fallback=fb
        )
        return jax.vmap(fn)(kstar, px, py, fallback)
    n = kstar.shape[-1]
    cols = jnp.arange(n)
    ks = jnp.maximum(kstar, 0)  # repro: allow-semiring-hardcode index clamp, not an ⊕⊗ op
    p_via = py[ks, cols[None, :]]
    p_own = jnp.take_along_axis(px, ks, axis=1)
    same_node = (ks + k_offset) == (cols[None, :] + j_offset)
    pz = jnp.where(same_node, p_own, p_via)
    kept = fallback if fallback is not None else jnp.full_like(pz, -1)
    return jnp.where(kstar < 0, kept, pz)


def minplus_pred(
    x: jax.Array,
    y: jax.Array,
    px: jax.Array,
    py: jax.Array,
    *,
    a: Optional[jax.Array] = None,
    pa: Optional[jax.Array] = None,
    k_offset=0,
    j_offset=0,
    semiring: SemiringLike = "tropical",
    **block_kw,
) -> Tuple[jax.Array, jax.Array]:
    """Fused ⊕⊗ with predecessor propagation, on the argmin kernel.

    Without ``a``: plain product; predecessors are -1 where Z is the
    semiring zero.  With ``a``/``pa``: the strict-improvement accumulate
    update ``Z = a ⊕ (x ⊗ y)`` where entries that kept ``a`` keep ``pa`` —
    i.e. exactly the old ``z, pz = minplus_pred(...); better = z < a``
    pattern, in one fused dispatch.
    """
    z, kstar = minplus_argmin(x, y, a, semiring=semiring, **block_kw)
    pz = pred_from_kstar(
        kstar, px, py, k_offset=k_offset, j_offset=j_offset, fallback=pa
    )
    return z, pz


def rank_k_update(
    dist: jax.Array,
    u: jax.Array,
    v: jax.Array,
    w: jax.Array,
    *,
    pred: Optional[jax.Array] = None,
    semiring: SemiringLike = "tropical",
    **block_kw,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One fused rank-k edge-relaxation pass over a solved distance state.

    For an update set ``{(u_i, v_i, w_i)}`` (k edges, as index vectors
    ``u``/``v`` and a weight vector ``w``),

        ``dist' = dist ⊕ (dist[:, U] ⊗ W ⊗ dist[V, :])``

    is dispatched as a single fused (n, k) x (k, n) accumulate — the
    contraction axis indexes *update edges*, not nodes, so one pass relaxes
    every pair through every updated edge at once.  This is the primitive
    the incremental engine (``repro.core.dynamic``) iterates to fixpoint.

    With ``pred`` the pass runs on the fused-argmin kernel and derives the
    updated predecessors from the winning edge index k*: the improved path
    is ``a --(dist-path)--> u_{k*} --(edge)--> v_{k*} --(dist-path)--> b``,
    so b's predecessor is ``pred[v_{k*}, b]`` — unless b *is* ``v_{k*}``
    (empty tail), in which case it is ``u_{k*}`` itself.  Entries that kept
    their old value (k* = -1, strict-improvement accumulate semantics) keep
    their old predecessor.  Note ``pred_from_kstar`` does not apply here:
    its empty-tail rule equates contraction index with column id, which
    only holds for node-indexed contractions.

    2D (n, n) state only; semiring and block-size resolution as in
    :func:`minplus`.
    """
    sr = get_semiring(semiring)
    x = sr.mul(dist[:, u], w[None, :])           # (n, k): col i = d[:,u_i]⊗w_i
    y = dist[v, :]                               # (k, n)
    if pred is None:
        return minplus(x, y, dist, semiring=sr, **block_kw), None
    z, kstar = minplus_argmin(x, y, dist, semiring=sr, **block_kw)
    ks = jnp.maximum(kstar, 0)  # repro: allow-semiring-hardcode index clamp, not an ⊕⊗ op
    cols = jnp.arange(dist.shape[-1])[None, :]
    p_via = pred[v, :][ks, cols]                 # pred[v_{k*}, b]
    pz = jnp.where(v[ks] == cols, u[ks], p_via)  # empty tail: pred is u_{k*}
    pz = jnp.where(kstar < 0, pred, pz)
    return z, pz


def row_restricted_close(
    dist: jax.Array,
    rows: jax.Array,
    *,
    pred: Optional[jax.Array] = None,
    semiring: SemiringLike = "tropical",
    **block_kw,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One row-restricted relaxation pass: ``dist[R,:] ⊕= dist[R,:] ⊗ dist``.

    ``rows`` is a traced int32 vector of affected source-row ids R
    (duplicates allowed — padded row lists repeat an id; duplicates compute
    identical panel rows, so the scatter back is deterministic).  Non-R
    rows pass through untouched, which is what makes the iterated pass a
    *bounded* re-solve: the remainder of ``dist`` is already closed, so
    each pass doubles the covered affected-prefix length exactly like the
    squaring solver, at O(|R|·n²) instead of O(n³).  The incremental
    engine (``repro.core.dynamic``) iterates this to early-exit fixpoint
    on the worsening path.

    With ``pred`` the pass runs on the fused-argmin kernels; the
    contraction axis indexes nodes, so :func:`pred_from_kstar` applies
    directly (fallback = the panel's old predecessors where nothing
    improved).  Returns the updated full (n, n) matrix (and predecessor
    matrix) — a panel compute plus one scatter, never a full re-close.

    2D (n, n) state only; semiring and block-size resolution as in
    :func:`minplus` except the autotune consult hits the dedicated
    ``rowclose|…`` key family (the (r, n) x (n, n) shape is asymmetric
    enough that square-bucket winners systematically mis-tune it).
    """
    sr = get_semiring(semiring)
    b = backend()
    mixed = _check_mixed(sr, dist)
    r, n = rows.shape[0], dist.shape[-1]
    if not block_kw:
        from . import autotune

        block_kw = autotune.lookup_row_close(
            b, dist.dtype, r, n, semiring=sr.name
        )
    keys = ("row_chunk", "k_chunk") if b == "xla" else ("bn", "bk", "kc")
    kw = {k_: v for k_, v in block_kw.items() if k_ in keys}

    if b == "xla":
        rc, kc = kw.get("row_chunk"), kw.get("k_chunk")
        panel = dist[rows, :]
        if pred is None:
            z = minplus_xla(
                panel, dist, panel, row_chunk=rc, k_chunk=kc, semiring=sr
            )
            return dist.at[rows].set(z), None
        z, kstar = minplus_argmin_xla(
            panel, dist, panel, row_chunk=rc, k_chunk=kc, semiring=sr
        )
        ppanel = pred[rows, :]
        pz = pred_from_kstar(
            kstar, ppanel, pred, k_offset=0, j_offset=0, fallback=ppanel
        )
        return dist.at[rows].set(z), pred.at[rows].set(pz)

    from .row_close import row_close_pallas

    d = dist.astype(jnp.float32) if mixed else dist
    z, kstar = row_close_pallas(
        d, rows, track=pred is not None, interpret=(b == "interpret"),
        semiring=sr, **kw,
    )
    z = z.astype(dist.dtype)
    if pred is None:
        return dist.at[rows].set(z), None
    ppanel = pred[rows, :]
    pz = pred_from_kstar(
        kstar, ppanel, pred, k_offset=0, j_offset=0, fallback=ppanel
    )
    return dist.at[rows].set(z), pred.at[rows].set(pz)


def fw_block(d: jax.Array, *, semiring: SemiringLike = "tropical") -> jax.Array:
    """In-VMEM FW closure of a (B,B) tile or (T,B,B) batch of tiles.

    bf16 tiles are closed with f32 accumulation (the pivot chain is the
    most rounding-sensitive piece of a round) and rounded once on exit.
    """
    sr = get_semiring(semiring)
    b = backend()
    out = d.dtype
    if _check_mixed(sr, d):
        d = d.astype(jnp.float32)
    if b == "xla":
        if d.ndim == 3:
            return jax.vmap(lambda dd: ref.fw_block_ref(dd, sr))(d).astype(out)
        return ref.fw_block_ref(d, sr).astype(out)
    return fw_block_pallas(
        d, interpret=(b == "interpret"), semiring=sr
    ).astype(out)


def fw_block_pred(
    d: jax.Array, p: jax.Array, *, semiring: SemiringLike = "tropical"
) -> Tuple[jax.Array, jax.Array]:
    sr = get_semiring(semiring)
    b = backend()
    out = d.dtype
    if _check_mixed(sr, d):
        d = d.astype(jnp.float32)
    if b == "xla":
        if d.ndim == 3:
            z, pz = jax.vmap(lambda dd, pp: ref.fw_block_pred_ref(dd, pp, sr))(d, p)
        else:
            z, pz = ref.fw_block_pred_ref(d, p, sr)
    else:
        z, pz = fw_block_pred_pallas(
            d, p, interpret=(b == "interpret"), semiring=sr
        )
    return z.astype(out), pz


def fw_round(
    d: jax.Array,
    o,
    *,
    block_size: int,
    semiring: SemiringLike = "tropical",
    **block_kw,
) -> jax.Array:
    """One fused multi-stage blocked-FW k-round over the full matrix.

    ``o`` is the (traced) element offset of pivot block t = o // B.  The
    three stages (pivot closure, col' = col ⊗ A*, fused full accumulate
    D ⊕ col' ⊗ row) run as a closure dispatch plus one Pallas grid on the
    pallas/interpret backends (``kernels.fw_round``) and as one jitted
    chunked-XLA program on the fallback (``minplus_xla.fw_round_xla``) —
    replacing the legacy 4-product round.  Accepts (N, N) or batched
    (G, N, N) state; bf16 storage selects the mixed-precision mode
    (f32 arithmetic, tropical-only).  ``block_kw`` overrides the stage-3
    chunking; otherwise the autotune cache is consulted for the dominant
    (N, B) x (B, N) accumulate shape.
    """
    sr = get_semiring(semiring)
    _check_mixed(sr, d)
    b = backend()
    if b == "xla":
        n = d.shape[-1]
        g = d.shape[0] if d.ndim == 3 else 0
        if not block_kw:
            from . import autotune

            block_kw = autotune.lookup(
                b, d.dtype, n, block_size, n, g=g, semiring=sr.name
            )
        rc, kc = block_kw.get("row_chunk"), block_kw.get("k_chunk")
        if d.ndim == 3:
            return jax.vmap(
                lambda dd: fw_round_xla(
                    dd, o, block_size=block_size, row_chunk=rc, k_chunk=kc,
                    semiring=sr,
                )
            )(d)
        return fw_round_xla(
            d, o, block_size=block_size, row_chunk=rc, k_chunk=kc, semiring=sr
        )
    from .fw_round import fw_round_pallas

    return fw_round_pallas(
        d, o, block_size=block_size, interpret=(b == "interpret"), semiring=sr
    )


def fw_round_pred(
    d: jax.Array,
    p: jax.Array,
    o,
    *,
    block_size: int,
    semiring: SemiringLike = "tropical",
    **block_kw,
) -> Tuple[jax.Array, jax.Array]:
    """Fused multi-stage round with predecessor propagation.

    Same three stages as :func:`fw_round`, composed from the fused-argmin
    primitives (the witness state k* rides each stage): pivot closure via
    :func:`fw_block_pred`, col' via one accumulate :func:`minplus_pred`,
    and the full update via one accumulate :func:`minplus_pred` — the
    stripe/pivot subsumption argument carries over because the pred rule
    only reads the winning k*.  Values are identical to :func:`fw_round`
    (the col' accumulate's ``col ⊕ .`` candidates are already inside the
    plain product's candidate set: A* carries ``one`` on its diagonal).
    """
    sr = get_semiring(semiring)
    _check_mixed(sr, d)
    bsz = block_size
    n = d.shape[-1]
    if d.ndim == 3:
        g = d.shape[0]

        def sl(arr, starts, sizes):
            return jax.lax.dynamic_slice(arr, (0,) + starts, (g,) + sizes)
    else:
        sl = jax.lax.dynamic_slice
    pivot = sl(d, (o, o), (bsz, bsz))
    ppivot = sl(p, (o, o), (bsz, bsz))
    pivot, ppivot = fw_block_pred(pivot, ppivot, semiring=sr)
    col = sl(d, (0, o), (n, bsz))
    pcol = sl(p, (0, o), (n, bsz))
    colp, pcolp = minplus_pred(
        col, pivot, pcol, ppivot, a=col, pa=pcol, k_offset=o, j_offset=o,
        semiring=sr, **block_kw,
    )
    row = sl(d, (o, 0), (bsz, n))
    prow = sl(p, (o, 0), (bsz, n))
    return minplus_pred(
        colp, row, pcolp, prow, a=d, pa=p, k_offset=o, j_offset=0,
        semiring=sr, **block_kw,
    )
