"""Persistent block-size autotuner for the fused min-plus dispatch surface.

The paper's scaling wall is min-plus bandwidth, and the right tile/chunk
sizes are hardware- and shape-dependent — so instead of guessing them, this
module measures a small candidate lattice per (shape-bucket, dtype, backend)
and persists the winners.  ``kernels.ops`` consults :func:`lookup` on every
dispatch (a trace-time dict read — no measurement on the hot path); winners
come from :func:`tune`, invoked by the benchmark harness, ``make
bench-smoke``, and the serving warmup.

Cache file (JSON, atomic tmp+rename writes, merged on save):

    {"schema": 1,
     "entries": {
       "xla|float32|g0|m1024|k128|n1024": {
          "params": {"row_chunk": 32},
          "us": 41520.3,            # best candidate wall time (microseconds)
          "lattice": 7,             # candidates measured
          "source": "measured",
          "measured_at": "2026-07-29T12:00:00"}}}

Keys bucket every dimension to the next power of two (floor 8) so one
measurement serves all nearby shapes.  Tuned parameters per backend:

  * ``xla``                 — ``row_chunk`` (scan slice of the chunked
                              fallback in ``kernels.minplus_xla``)
  * ``pallas``/``interpret``— ``bm``, ``bn``, ``bk``, ``kc`` (Pallas grid
                              block sizes / in-tile k chunk)

Environment:

  * ``REPRO_AUTOTUNE=0``      disabled: :func:`lookup` returns {} and
                              :func:`tune` is a no-op (compiled-in defaults).
  * unset / ``REPRO_AUTOTUNE=1``  :func:`lookup` consults the cache;
                              :func:`tune` measures only on a cache miss and
                              reuses persisted winners otherwise.
  * ``REPRO_AUTOTUNE=force``  :func:`tune` re-measures and overwrites even
                              when a cached winner exists.
  * ``REPRO_AUTOTUNE_CACHE``  cache file path (default
                              ``<checkout>/.cache/autotune.json``, see
                              ``repro.caches``).

Note: solvers are jit-compiled and read the cache at trace time — tune
before the first solver call of a given shape (the harnesses do), or new
winners only take effect on the next retrace/process.
"""

from __future__ import annotations

import datetime
import json
import os
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "mode",
    "cache_path",
    "bucket",
    "key_for",
    "key_for_fw_round",
    "key_for_row_close",
    "lookup",
    "lookup_fw_round",
    "lookup_row_close",
    "candidates",
    "tune",
    "tune_blocked_fw",
    "tune_fw_round",
    "tune_row_close",
    "load_entries",
    "touched_entries",
    "measure",
]

SCHEMA = 1
_PALLAS_KEYS = ("bm", "bn", "bk", "kc")
_XLA_KEYS = ("row_chunk", "k_chunk")
# the row-restricted close pass gathers one row per grid program, so the
# Pallas row-block size is pinned to 1 and only (bn, bk, kc) are tunable
_ROWCLOSE_PALLAS_KEYS = ("bn", "bk", "kc")
_FW_ROUND_KEYS = ("block_size", "round_mode")
_FW_ROUND_BLOCKS = (32, 64, 128, 256)
_FW_ROUND_MODES = ("fused", "split")

# memoized parse of the cache file, invalidated by mtime
_memo = {"path": None, "mtime": None, "entries": {}}

# cache keys this process actually consulted (hit) or tuned — lets harnesses
# report exactly the tiles a run used instead of the whole machine-wide cache
_touched: set = set()


def mode() -> str:
    """Autotune behaviour: 'off' | 'on' | 'force' (see module docstring)."""
    env = os.environ.get("REPRO_AUTOTUNE", "1").strip().lower()
    if env in ("0", "off", "false", "no"):
        return "off"
    if env == "force":
        return "force"
    return "on"


def cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE", "")
    if env:
        return Path(env)
    from repro.caches import autotune_cache_file

    return autotune_cache_file()


def bucket(v: int) -> int:
    """Shape bucket: next power of two, floor 8."""
    p = 8
    while p < v:
        p *= 2
    return p


def key_for(
    backend: str, dtype, m: int, k: int, n: int, g: int = 0,
    semiring: str = "tropical",
) -> str:
    """Cache key.  Non-tropical semirings get an extra ``|s:<name>`` segment;
    tropical keeps the legacy key format, so caches tuned before the
    semiring registry existed stay valid."""
    name = jnp.dtype(dtype).name
    gb = bucket(g) if g else 0
    key = f"{backend}|{name}|g{gb}|m{bucket(m)}|k{bucket(k)}|n{bucket(n)}"
    if semiring != "tropical":
        key += f"|s:{semiring}"
    return key


def key_for_fw_round(
    backend: str, dtype, n: int, g: int = 0, semiring: str = "tropical"
) -> str:
    """Cache key of the blocked-FW *round shape* family: winner is a
    (block_size, round_mode) pair for one matrix edge bucket, distinct from
    the per-product chunk entries (``key_for``) that the round's inner
    dispatches keep consulting.  dtype is part of the key — bf16 mixed mode
    tunes (and persists) separately from f32."""
    name = jnp.dtype(dtype).name
    gb = bucket(g) if g else 0
    key = f"fwround|{backend}|{name}|g{gb}|n{bucket(n)}"
    if semiring != "tropical":
        key += f"|s:{semiring}"
    return key


def key_for_row_close(
    backend: str, dtype, r: int, n: int, semiring: str = "tropical"
) -> str:
    """Cache key of the row-restricted close pass family (``rowclose|...``):
    one fused (r, n) x (n, n) panel relaxation against the full matrix,
    keyed by the affected-row-count bucket r and the matrix edge n.  The
    shape is asymmetric enough (r << n on the serving path) that reusing
    the square ``key_for`` buckets would systematically mis-tune it."""
    name = jnp.dtype(dtype).name
    key = f"rowclose|{backend}|{name}|r{bucket(r)}|n{bucket(n)}"
    if semiring != "tropical":
        key += f"|s:{semiring}"
    return key


def load_entries(*, reload: bool = False) -> Dict[str, dict]:
    """Parsed cache entries (mtime-memoized; {} on absent/corrupt file)."""
    p = cache_path()
    try:
        st = os.stat(p)
    except OSError:
        _memo.update(path=str(p), mtime=None, entries={})
        return {}
    if (
        not reload
        and _memo["path"] == str(p)
        and _memo["mtime"] == st.st_mtime_ns
    ):
        return _memo["entries"]
    try:
        data = json.loads(Path(p).read_text())
        entries = data.get("entries", {}) if data.get("schema") == SCHEMA else {}
        if not isinstance(entries, dict):
            entries = {}
    except Exception:
        entries = {}
    _memo.update(path=str(p), mtime=st.st_mtime_ns, entries=entries)
    return entries


def _save(new_entries: Dict[str, dict]) -> None:
    """Merge ``new_entries`` into the cache file atomically."""
    p = cache_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    entries = dict(load_entries(reload=True))
    entries.update(new_entries)
    payload = json.dumps({"schema": SCHEMA, "entries": entries}, indent=1,
                         sort_keys=True)
    fd, tmp = tempfile.mkstemp(dir=str(p.parent), prefix=".autotune-")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(payload)
        os.replace(tmp, p)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _memo.update(path=str(p), mtime=None, entries={})  # force re-read


def _filter(backend: str, params: dict) -> dict:
    keys = _XLA_KEYS if backend == "xla" else _PALLAS_KEYS
    return {k: int(v) for k, v in params.items() if k in keys}


def lookup(
    backend: str, dtype, m: int, k: int, n: int, g: int = 0,
    semiring: str = "tropical",
) -> dict:
    """Winner params for a dispatch site, or {} (miss / disabled).

    Falls back to the unbatched (g=0) bucket when no batched entry exists —
    the per-slice working set is what the chunk sizes bound.  Non-tropical
    semirings additionally fall back to the tropical entry of the same
    shape: the memory-traffic shape is identical, only the elementwise ⊕⊗
    pair differs, so a tropical winner is a good prior until a per-semiring
    ``tune`` runs.
    """
    if mode() == "off":
        return {}
    entries = load_entries()
    srs = (semiring, "tropical") if semiring != "tropical" else ("tropical",)
    for sq in srs:
        for gq in ((g, 0) if g else (0,)):
            key = key_for(backend, dtype, m, k, n, g=gq, semiring=sq)
            e = entries.get(key)
            if e and isinstance(e.get("params"), dict):
                _touched.add(key)
                return _filter(backend, e["params"])
    return {}


def lookup_fw_round(
    backend: str, dtype, n: int, g: int = 0, semiring: str = "tropical"
) -> dict:
    """Winner (block_size, round_mode) for a blocked-FW solve of edge n, or
    {} (miss / disabled).  Fallbacks mirror :func:`lookup`: batched -> g=0
    (the per-round product shapes are what the winner bounds), non-tropical
    -> tropical same shape (identical memory traffic)."""
    if mode() == "off":
        return {}
    entries = load_entries()
    srs = (semiring, "tropical") if semiring != "tropical" else ("tropical",)
    for sq in srs:
        for gq in ((g, 0) if g else (0,)):
            key = key_for_fw_round(backend, dtype, n, g=gq, semiring=sq)
            e = entries.get(key)
            if e and isinstance(e.get("params"), dict):
                _touched.add(key)
                p = e["params"]
                out = {}
                if "block_size" in p:
                    out["block_size"] = int(p["block_size"])
                if p.get("round_mode") in _FW_ROUND_MODES:
                    out["round_mode"] = p["round_mode"]
                return out
    return {}


def lookup_row_close(
    backend: str, dtype, r: int, n: int, semiring: str = "tropical"
) -> dict:
    """Winner chunking for one row-restricted close pass, or {} (miss /
    disabled).  Non-tropical falls back to the tropical entry of the same
    shape (identical memory traffic); there is no g axis — the serving
    tier's batched drains go through the rank-k family, not this one."""
    if mode() == "off":
        return {}
    entries = load_entries()
    srs = (semiring, "tropical") if semiring != "tropical" else ("tropical",)
    for sq in srs:
        key = key_for_row_close(backend, dtype, r, n, semiring=sq)
        e = entries.get(key)
        if e and isinstance(e.get("params"), dict):
            _touched.add(key)
            keys = _XLA_KEYS if backend == "xla" else _ROWCLOSE_PALLAS_KEYS
            return {k: int(v) for k, v in e["params"].items() if k in keys}
    return {}


def touched_entries() -> Dict[str, dict]:
    """{key: params} for the cache entries this process consulted or tuned."""
    entries = load_entries()
    return {
        key: entries[key].get("params")
        for key in sorted(_touched)
        if key in entries
    }


def candidates(backend: str, m: int, k: int, n: int) -> List[dict]:
    """The candidate lattice measured per shape bucket (kept deliberately
    small: dispatch tuning should cost seconds, not minutes)."""
    if backend == "xla":
        mb, kb = bucket(m), bucket(k)
        out = [
            {"row_chunk": rc, "k_chunk": 0}          # single-pass row scan
            for rc in (4, 16, 64)
            if rc <= mb
        ] or [{"row_chunk": 4, "k_chunk": 0}]
        out += [
            {"row_chunk": rc, "k_chunk": kc}         # two-level chunking
            for rc in (16, 32, 64, 128)
            for kc in (16, 32)
            if rc <= mb and kc < kb
        ]
        return out
    # Pallas lattice: only tilings Mosaic compiles — (8, 128)-aligned
    # blocks, and the in-tile k window kc = 128 (the kernel reads x at lane
    # offsets it must prove 128-aligned; shorter contractions clamp kc to
    # the whole block in ``minplus._layout``).
    from .minplus import DEFAULT_KC

    out, seen = [], set()
    for bm in (64, 128, 256):
        for bn in (128, 256):
            for bk in (256, 512):
                bk_ = min(bk, bucket(k))
                cand = (min(bm, bucket(m)), min(bn, max(bucket(n), 128)),
                        bk_, min(DEFAULT_KC, bk_))
                if cand not in seen:
                    seen.add(cand)
                    out.append(dict(zip(_PALLAS_KEYS, cand)))
    return out


def measure(fn, reps: int) -> float:
    """Best-of-reps wall time in microseconds (first call warms/compiles).

    The one timing policy shared by the tuner and the benchmark harnesses —
    keep them on the same helper so winners and headlines stay comparable."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _inputs(m: int, k: int, n: int, g: int, dtype, seed: int = 0,
            semiring: str = "tropical"):
    rng = np.random.default_rng(seed)

    def mk(*shape):
        # in-domain values per semiring; ~30% "no edge" (semiring zero)
        no_edge = rng.uniform(size=shape) < 0.3
        if semiring == "reliability":
            a = rng.uniform(0.05, 1.0, size=shape).astype(np.float32)
            a = np.where(no_edge, 0.0, a)
        elif semiring == "boolean":
            a = np.where(no_edge, 0.0, 1.0).astype(np.float32)
        elif semiring == "bottleneck":
            a = rng.uniform(1, 100, size=shape).astype(np.float32)
            a = np.where(no_edge, -np.inf, a)
        else:
            a = rng.uniform(1, 100, size=shape).astype(np.float32)
            a = np.where(no_edge, np.inf, a)
        return jnp.asarray(a, dtype)

    if g:
        return mk(g, m, k), mk(g, k, n), mk(g, m, n)
    return mk(m, k), mk(k, n), mk(m, n)


def tune(
    m: int,
    k: int,
    n: int,
    *,
    g: int = 0,
    dtype=jnp.float32,
    backend: Optional[str] = None,
    reps: int = 2,
    force: Optional[bool] = None,
    semiring: str = "tropical",
) -> dict:
    """Measure the candidate lattice for one shape bucket and persist the
    winner.  Returns the cache entry; ``entry["source"]`` is ``"cache"``
    when a persisted winner was reused without re-measurement,
    ``"measured"`` after a fresh sweep, ``"disabled"`` under
    ``REPRO_AUTOTUNE=0``.  ``semiring`` tunes (and keys) that registry
    instance's dispatch with in-domain inputs.
    """
    from repro.core.semiring import get_semiring

    from . import ops
    from .minplus import minplus_pallas
    from .minplus_xla import minplus_xla

    b = backend or ops.backend()
    sr = get_semiring(semiring)
    md = mode()
    if md == "off":
        return {"params": {}, "source": "disabled"}
    key = key_for(b, dtype, m, k, n, g=g, semiring=sr.name)
    _touched.add(key)
    refresh = (md == "force") if force is None else force
    if not refresh:
        cached = load_entries().get(key)
        if cached and isinstance(cached.get("params"), dict):
            out = dict(cached)
            out["params"] = _filter(b, cached["params"])
            out["source"] = "cache"
            return out

    mb, kb, nb = bucket(m), bucket(k), bucket(n)
    gb = min(bucket(g), 8) if g else 0       # cap batch for measurement cost
    x, y, a = _inputs(mb, kb, nb, gb, dtype, semiring=sr.name)

    def make(params):
        if b == "xla":
            rc, kc = params["row_chunk"], params.get("k_chunk")
            if gb:
                return lambda: jax.vmap(
                    lambda xx, yy, aa: minplus_xla(
                        xx, yy, aa, row_chunk=rc, k_chunk=kc, semiring=sr
                    )
                )(x, y, a)
            return lambda: minplus_xla(
                x, y, a, row_chunk=rc, k_chunk=kc, semiring=sr
            )
        return lambda: minplus_pallas(
            x, y, a, accumulate=True, interpret=(b == "interpret"),
            semiring=sr, **params
        )

    best_params, best_us = None, float("inf")
    cands = candidates(b, mb, kb, nb)
    for params in cands:
        us = measure(make(params), reps)
        if us < best_us:
            best_params, best_us = params, us
    entry = {
        "params": best_params,
        "us": best_us,
        "lattice": len(cands),
        "source": "measured",
        "measured_at": datetime.datetime.now().isoformat(timespec="seconds"),
    }
    _save({key: entry})
    return entry


def _row_close_candidates(backend: str, r: int, n: int) -> List[dict]:
    """Candidate lattice for the row-restricted close pass: the panel has r
    rows (often < the smallest row_chunk), so the XLA lattice is the plain
    one clamped to r; the Pallas lattice drops bm (pinned to 1)."""
    if backend == "xla":
        out = []
        for cand in candidates("xla", r, n, n):
            cand = dict(cand, row_chunk=min(cand["row_chunk"], bucket(r)))
            if cand not in out:
                out.append(cand)
        return out
    from .minplus import DEFAULT_KC

    out, seen = [], set()
    for bn in (128, 256):
        for bk in (256, 512):
            bk_ = min(bk, bucket(n))
            cand = (min(bn, max(bucket(n), 128)), bk_, min(DEFAULT_KC, bk_))
            if cand not in seen:
                seen.add(cand)
                out.append(dict(zip(_ROWCLOSE_PALLAS_KEYS, cand)))
    return out


def tune_row_close(
    r: int,
    n: int,
    *,
    dtype=jnp.float32,
    backend: Optional[str] = None,
    reps: int = 2,
    force: Optional[bool] = None,
    semiring: str = "tropical",
) -> dict:
    """Measure the row-restricted close lattice for one (r, n) bucket and
    persist the winner under the ``rowclose|...`` key.  Semantics mirror
    :func:`tune` (cache reuse unless forced, disabled under
    ``REPRO_AUTOTUNE=0``)."""
    from repro.core.semiring import get_semiring

    from . import ops

    b = backend or ops.backend()
    sr = get_semiring(semiring)
    md = mode()
    if md == "off":
        return {"params": {}, "source": "disabled"}
    key = key_for_row_close(b, dtype, r, n, semiring=sr.name)
    _touched.add(key)
    refresh = (md == "force") if force is None else force
    if not refresh:
        cached = load_entries().get(key)
        if cached and isinstance(cached.get("params"), dict):
            keys = _XLA_KEYS if b == "xla" else _ROWCLOSE_PALLAS_KEYS
            out = dict(cached)
            out["params"] = {
                k: int(v) for k, v in cached["params"].items() if k in keys
            }
            out["source"] = "cache"
            return out

    rb, nb = max(bucket(r) // 2, 1), bucket(n)   # bucket is next-pow2: undo
    rb = min(max(r, rb), nb)
    d, _, _ = _inputs(nb, nb, nb, 0, dtype, semiring=sr.name)
    idx = jnp.arange(nb)
    d = d.at[idx, idx].set(jnp.asarray(sr.one, dtype))
    rows = jnp.asarray(
        np.random.default_rng(0).choice(nb, size=rb, replace=False), jnp.int32
    )

    def make(params):
        return lambda: ops.row_restricted_close(
            d, rows, semiring=sr, **params
        )[0]

    best_params, best_us = None, float("inf")
    cands = _row_close_candidates(b, rb, nb)
    for params in cands:
        us = measure(make(params), reps)
        if us < best_us:
            best_params, best_us = params, us
    entry = {
        "params": best_params,
        "us": best_us,
        "lattice": len(cands),
        "source": "measured",
        "measured_at": datetime.datetime.now().isoformat(timespec="seconds"),
    }
    _save({key: entry})
    return entry


def tune_blocked_fw(
    n: int,
    block_size: int,
    *,
    g: int = 0,
    dtype=jnp.float32,
    backend: Optional[str] = None,
    reps: int = 2,
    semiring: str = "tropical",
) -> Dict[str, dict]:
    """Tune the three panel-product shapes one blocked-FW pivot step hits:
    row panel (B,B)x(B,N), col panel (N,B)x(B,B), and the fused phase-3
    (N,B)x(B,N) accumulate.  Returns {shape_key: entry}."""
    b = min(block_size, n)
    shapes = {
        "row_panel": (b, b, n),
        "col_panel": (n, b, b),
        "phase3": (n, b, n),
    }
    return {
        name: tune(m, k, nn, g=g, dtype=dtype, backend=backend, reps=reps,
                   semiring=semiring)
        for name, (m, k, nn) in shapes.items()
    }


def tune_fw_round(
    n: int,
    *,
    dtype=jnp.float32,
    backend: Optional[str] = None,
    reps: int = 2,
    force: Optional[bool] = None,
    semiring: str = "tropical",
    blocks: Optional[tuple] = None,
) -> dict:
    """Sweep the blocked-FW *round* space — block size x fused-vs-split
    round x dtype — with whole solves on an in-domain matrix, and persist
    the winning (block_size, round_mode) under the ``fwround|...`` key.

    Per-product chunk winners for each candidate's dominant stage-3 shape
    are warmed first (``tune`` on miss), so the sweep measures each round
    shape with the same chunking its dispatch will actually use.  The
    bf16 space is keyed (and tuned) separately from f32.
    """
    from repro.core.semiring import get_semiring

    from . import ops

    b = backend or ops.backend()
    sr = get_semiring(semiring)
    md = mode()
    if md == "off":
        return {"params": {}, "source": "disabled"}
    key = key_for_fw_round(b, dtype, n, semiring=sr.name)
    _touched.add(key)
    refresh = (md == "force") if force is None else force
    if not refresh:
        cached = load_entries().get(key)
        if cached and isinstance(cached.get("params"), dict):
            out = dict(cached)
            out["source"] = "cache"
            return out

    from repro.core.blocked_fw import blocked_fw  # lazy: no import cycle

    nb = bucket(n)
    cand_blocks = tuple(
        bb for bb in (blocks or _FW_ROUND_BLOCKS) if bb <= nb
    ) or (min(nb, 32),)
    for bb in cand_blocks:
        tune(nb, bb, nb, dtype=dtype, backend=b, reps=1, semiring=sr.name)
    x, _, _ = _inputs(nb, nb, nb, 0, dtype, semiring=sr.name)
    idx = jnp.arange(nb)
    h = x.at[idx, idx].set(jnp.asarray(sr.one, dtype))

    cands = [
        {"block_size": bb, "round_mode": rm}
        for bb in cand_blocks
        for rm in _FW_ROUND_MODES
    ]

    def make(params):
        return lambda: blocked_fw(
            h, block_size=params["block_size"],
            round_mode=params["round_mode"], semiring=sr,
        )[0]

    # Interleaved sweeps (candidate-major, not rep-major): whole solves are
    # long enough that container load drifts *within* a sequential sweep and
    # crowns whichever candidate ran in the calm moment — round-robin puts
    # every candidate in every weather window and the min tracks the code.
    fns = [make(p) for p in cands]
    for fn in fns:
        jax.block_until_ready(fn())                    # compile/warm all
    best_by_cand = [float("inf")] * len(cands)
    for _ in range(max(reps, 2)):
        for i, fn in enumerate(fns):
            best_by_cand[i] = min(best_by_cand[i], measure(fn, 1))
    best_us = min(best_by_cand)
    best_params = cands[best_by_cand.index(best_us)]
    entry = {
        "params": best_params,
        "us": best_us,
        "lattice": len(cands),
        "source": "measured",
        "measured_at": datetime.datetime.now().isoformat(timespec="seconds"),
    }
    _save({key: entry})
    return entry
