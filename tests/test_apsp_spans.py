"""The front door's host spans, read back from a profiler trace on the CPU.

``solve`` and ``solve_batch`` mark their phases with
``jax.profiler.TraceAnnotation`` spans (``repro.core.spans``).  These tests
trace calls under ``jax.profiler``, read the ``.xplane.pb`` back and check
the span names, their nesting and their counts against what the inputs
imply, and that no span sits inside a per-graph loop.
"""

from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.core import solve, solve_batch
from repro.core import spans as S


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; its ``apsp.*`` spans in start order as
    ``[start_ns, end_ns, name, counts, parent_name]``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (xp,) = Path(tmp_path).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(xp)).planes:
        for line in plane.lines:
            out += [[int(e.start_ns), int(e.end_ns), e.name,
                     {k: int(v) for k, v in e.stats}]
                    for e in line.events if e.name.startswith("apsp.")]
    out.sort(key=lambda x: (x[0], -x[1]))
    for sp in out:                     # innermost enclosing span
        inside = [p for p in out if p is not sp and p[0] <= sp[0]
                  and sp[1] <= p[1]]
        sp.append(max(inside, key=lambda p: p[0])[2] if inside else None)
    return out


def _ragged(sizes, seed=0):
    rng = np.random.default_rng(seed)
    mats = []
    for k in sizes:
        m = rng.integers(1, 9, (k, k)).astype(np.float32)
        np.fill_diagonal(m, 0.0)
        mats.append(m)
    return mats


def test_solve_spans_and_counts(tmp_path):
    n = 16
    h = jnp.asarray(_ragged([n])[0])
    got = _traced(tmp_path, lambda: solve(h).dist.block_until_ready())
    assert [(s[2], s[4]) for s in got] == [
        (S.SOLVE, None), (S.VALIDATE, S.SOLVE), (S.DISPATCH, S.SOLVE),
        (S.NEGCYCLE, S.SOLVE)]
    counts = {s[2]: s[3] for s in got}
    assert counts[S.SOLVE] == {"n": n, "with_pred": 0}
    # the NaN scan runs on the device: its one-byte verdict crosses
    assert counts[S.VALIDATE] == {"bytes_to_host": 1, "on_device": 1}
    # already on device; no predecessor state asked for
    assert counts[S.DISPATCH] == {"bytes_to_device": 0, "pred_bytes": 0}
    # the negative-cycle check reads the solved diagonal alone
    assert counts[S.NEGCYCLE] == {"bytes_to_host": n * 4, "on_device": 1}
    moved = sum(c.get("bytes_to_host", 0) for c in counts.values())
    assert moved == 1 + n * 4


def test_solve_host_input_counts_its_upload(tmp_path):
    h = _ragged([12])[0]
    got = _traced(tmp_path, lambda: solve(h))
    counts = {s[2]: s[3] for s in got}
    assert counts[S.VALIDATE] == {"bytes_to_host": 0, "on_device": 0}
    assert counts[S.DISPATCH] == {"bytes_to_device": 12 * 12 * 4, "pred_bytes": 0}
    assert counts[S.NEGCYCLE] == {"bytes_to_host": 12 * 4, "on_device": 1}


def test_bucketed_solve_batch_spans_and_counts(tmp_path):
    sizes = [5, 12, 20, 9, 30]
    # buckets: edge 8 {5} in 1 slot, 16 {12, 9} in 2, 32 {20, 30} in 2
    mats = _ragged(sizes)
    got = _traced(tmp_path / "checked",
                  lambda: solve_batch(mats, bucket_by_size=True))
    # each bucket's program writes its results on the device: no read-back
    per_bucket = [S.PACK, S.DISPATCH]
    assert [s[2] for s in got] == (
        [S.SOLVE_BATCH, S.VALIDATE, S.FETCH] + per_bucket * 3
        + [S.SCATTER, S.NEGCYCLE])
    assert all(s[4] == S.SOLVE_BATCH for s in got[1:])
    assert got[0][3] == {"graphs": 5, "buckets": 3, "with_pred": 0}
    assert [s[3] for s in got if s[2] == S.DISPATCH] == [{"pred_bytes": 0}] * 3
    assert got[1][3] == {"graphs": 5, "bytes_to_host": 0, "on_device": 0}
    packs = [s[3] for s in got if s[2] == S.PACK]
    assert [(p["edge"], p["graphs"], p["slots"]) for p in packs] == [
        (8, 1, 1), (16, 2, 2), (32, 2, 2)]
    assert sum(p["useful_work"] for p in packs) == sum(k ** 3 for k in sizes)
    assert [p["padded_work"] for p in packs] == [8 ** 3, 2 * 16 ** 3, 2 * 32 ** 3]
    assert [p["bytes_to_device"] for p in packs] == [
        4 * 8 * 8, 4 * 2 * 16 * 16, 4 * 2 * 32 * 32]
    assert got[2][3] == {"bytes_to_host": 0}          # host inputs, as given
    # the frame is gathered on the device; only its row indices go up
    assert got[-2][3] == {"bytes_to_device": 5 * 4, "on_device": 1}
    # the solved diagonals are all that crosses to the host
    assert got[-1][3] == {"bytes_to_host": 5 * 30 * 4, "on_device": 1}
    assert sum(s[3].get("bytes_to_host", 0) for s in got) == 5 * 30 * 4
    # unchecked, nothing at all is read back
    got = _traced(tmp_path / "unchecked", lambda: solve_batch(
        mats, bucket_by_size=True, validate=False))
    assert [s[2] for s in got] == (
        [S.SOLVE_BATCH, S.FETCH] + per_bucket * 3 + [S.SCATTER])
    assert sum(s[3].get("bytes_to_host", 0) for s in got) == 0


def test_stacked_solve_batch_spans_and_counts(tmp_path):
    sizes = [5, 12, 20]
    stack = np.stack([np.pad(m, (0, 20 - m.shape[0]), constant_values=np.inf)
                      for m in _ragged(sizes)])
    dev = jnp.asarray(stack)
    got = _traced(tmp_path, lambda: solve_batch(dev, sizes))
    assert [(s[2], s[4]) for s in got] == [
        (S.SOLVE_BATCH, None), (S.VALIDATE, S.SOLVE_BATCH),
        (S.PACK, S.SOLVE_BATCH), (S.DISPATCH, S.SOLVE_BATCH),
        (S.NEGCYCLE, S.SOLVE_BATCH)]
    assert got[0][3] == {"graphs": 3, "buckets": 1, "with_pred": 0}
    assert got[1][3] == {"graphs": 3, "bytes_to_host": 1, "on_device": 1}
    # a ragged device stack is read on the host and packed again
    assert got[2][3] == {"edge": 20, "graphs": 3, "slots": 3,
                         "useful_work": sum(k ** 3 for k in sizes),
                         "padded_work": 3 * 20 ** 3,
                         "bytes_to_host": stack.nbytes,
                         "bytes_to_device": stack.nbytes}
    assert got[4][3] == {"bytes_to_host": 3 * 20 * 4, "on_device": 1}


def test_pred_counts(tmp_path):
    """A route solve counts its int32 predecessor state: the whole (n, n)
    table for ``solve``, each bucket's padded stack for ``solve_batch``."""
    n = 16
    h = jnp.asarray(_ragged([n])[0])
    got = _traced(tmp_path / "one",
                  lambda: solve(h, with_pred=True).pred.block_until_ready())
    counts = {s[2]: s[3] for s in got}
    assert counts[S.SOLVE] == {"n": n, "with_pred": 1}
    assert counts[S.DISPATCH] == {"bytes_to_device": 0, "pred_bytes": n * n * 4}
    sizes = [5, 12, 9]               # buckets: edge 8 in 1 slot, 16 in 2
    got = _traced(tmp_path / "batch", lambda: solve_batch(
        _ragged(sizes), with_pred=True, bucket_by_size=True))
    assert got[0][3] == {"graphs": 3, "buckets": 2, "with_pred": 1}
    assert [s[3]["pred_bytes"] for s in got if s[2] == S.DISPATCH] == [
        4 * 8 * 8, 4 * 2 * 16 * 16]


@pytest.mark.parametrize("bucket_by_size", [True, False])
def test_no_span_per_graph(tmp_path, bucket_by_size):
    """Eight times the graphs in the same buckets: the same spans."""
    def names(g, sub):
        mats = _ragged(([5, 12, 20] * g)[:g], seed=g)
        return [s[2] for s in _traced(
            tmp_path / sub,
            lambda: solve_batch(mats, bucket_by_size=bucket_by_size))]

    few, many = names(8, "few"), names(64, "many")
    assert few == many
    assert len(few) <= 20
