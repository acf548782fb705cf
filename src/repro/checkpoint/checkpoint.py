"""Atomic pytree checkpoints.

Layout per step:
    <dir>/step_000123/
        arrays.npz        key-path-flattened leaves
        manifest.json     step, leaf keys and dtypes, caller's extra metadata

Write protocol (fault tolerant):
    1. write everything into  <dir>/.tmp_step_000123
    2. fsync, then os.replace -> step_000123       (atomic on POSIX)
    3. update <dir>/LATEST (tmp+replace again)
A crash mid-write leaves only a .tmp_ directory, which restore ignores and
the next save overwrites.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any, Dict, Optional

import jax
import numpy as np

__all__ = [
    "save_checkpoint", "load_checkpoint",
    "save_engine_checkpoint", "load_engine_checkpoint",
]

_SEP = "/"


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_path_str(p) for p in path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def save_checkpoint(
    directory: str,
    step: int,
    state,
    *,
    extra: Optional[dict] = None,
) -> str:
    """Synchronous atomic save. Returns the final checkpoint path."""
    os.makedirs(directory, exist_ok=True)
    name = f"step_{step:09d}"
    tmp = os.path.join(directory, f".tmp_{name}")
    final = os.path.join(directory, name)
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    flat = _flatten(state)
    np.savez(os.path.join(tmp, "arrays.npz"), **flat)
    manifest = {
        "step": step,
        "keys": sorted(flat.keys()),
        "dtypes": {k: str(v.dtype) for k, v in flat.items()},
        "extra": extra or {},
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    # LATEST pointer, also atomic
    ltmp = os.path.join(directory, ".LATEST.tmp")
    with open(ltmp, "w") as f:
        f.write(name)
        f.flush()
        os.fsync(f.fileno())
    os.replace(ltmp, os.path.join(directory, "LATEST"))
    return final


def latest_step(directory: str) -> Optional[int]:
    try:
        with open(os.path.join(directory, "LATEST")) as f:
            return int(f.read().strip().split("_")[-1])
    except (FileNotFoundError, ValueError):
        return None


def load_checkpoint(directory: str, step: Optional[int] = None):
    """-> (flat dict of host arrays, manifest). Picks LATEST if step None."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory}")
    path = os.path.join(directory, f"step_{step:09d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    with np.load(os.path.join(path, "arrays.npz")) as z:
        flat = {k: z[k] for k in z.files}
    return flat, manifest


# -- durable engine snapshots (serving-tier restore path) -------------------
#
# A DynamicAPSP engine's recoverable state is its snapshot() dict
# (dist / pred / h / version) plus the config needed to rebuild an
# equivalent engine (semiring, storage dtype, with_pred, n).  Stored
# through the same atomic step-dir protocol above with step == version,
# so LATEST always names the newest committed state and a crash mid-save
# leaves the previous checkpoint intact.  bf16 states are stored as
# uint16 bit views (np.savez round-trips ml_dtypes unreliably) with the
# true dtype recorded in the manifest for bit-exact reconstruction.


def _bits_of(a: Optional[np.ndarray]):
    """(savable array, true-dtype string) — bf16 goes out as its bit view."""
    if a is None:
        return None, None
    a = np.asarray(a)
    if str(a.dtype) == "bfloat16":
        return a.view(np.uint16), "bfloat16"
    return a, str(a.dtype)


def _unbits(a: Optional[np.ndarray], dtype: Optional[str]):
    if a is None or dtype is None or str(a.dtype) == dtype:
        return a
    if dtype == "bfloat16":
        import ml_dtypes
        return a.view(ml_dtypes.bfloat16)
    return a.astype(np.dtype(dtype))


def save_engine_checkpoint(directory: str, engine, *, extra: Optional[dict] = None) -> str:
    """Atomically checkpoint a ``DynamicAPSP`` engine's solved state.

    Returns the checkpoint path.  Step number == engine version, so the
    LATEST pointer names the newest committed state and
    :func:`load_engine_checkpoint` + journal replay of records with
    ``v0 >= version`` reconstructs any later live state bit-exactly.
    """
    snap = engine.snapshot()
    dist, dist_dt = _bits_of(snap["dist"])
    pred, pred_dt = _bits_of(snap["pred"])
    state = {"dist": dist, "h": snap["h"]}
    if pred is not None:
        state["pred"] = pred
    meta = {
        "kind": "engine",
        "version": int(snap["version"]),
        "n": int(engine.n),
        "semiring": engine.semiring.name,
        "with_pred": pred is not None,
        "state_dtype": dist_dt,
        "pred_dtype": pred_dt,
    }
    if extra:
        meta.update(extra)
    return save_checkpoint(directory, int(snap["version"]), state, extra=meta)


def load_engine_checkpoint(directory: str, step: Optional[int] = None) -> Dict[str, Any]:
    """Load a durable engine snapshot (LATEST if ``step`` is None).

    Returns ``{"dist", "pred", "h", "version", "semiring", "with_pred",
    "state_dtype", "n"}`` — ``dist``/``pred``/``h`` as host arrays in
    their true dtypes, directly consumable as ``DynamicAPSP(h,
    state=...)``'s restore state.
    """
    flat, manifest = load_checkpoint(directory, step)
    meta = manifest.get("extra", {})
    if meta.get("kind") != "engine":
        raise ValueError(
            f"checkpoint under {directory} is not an engine checkpoint "
            f"(kind={meta.get('kind')!r})"
        )
    out = dict(meta)
    out["dist"] = _unbits(flat["dist"], meta.get("state_dtype"))
    out["pred"] = _unbits(flat.get("pred"), meta.get("pred_dtype")) if meta.get("with_pred") else None
    out["h"] = flat["h"]
    out["version"] = int(meta["version"])
    return out
