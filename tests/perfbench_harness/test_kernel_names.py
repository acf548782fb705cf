"""CPU tests of what the benchmark reads of the program's own names.

The kernels carry their own names in the device trace (``fw_round``,
``fw_block``, ...), which ``cold.fw_round_vpu_share`` reads; the front
door's ``apsp.*`` host spans sit in the same trace and must leave the
accepted reduction, which reads ``bench.*`` spans, as it was.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from perfbench import counts, spec, tracing  # noqa: E402

PEAK = {"vpu_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _share(ev, solves=2, n=64):
    run = {"trace": tracing.Summary(ev), "peak": PEAK,
           "out": {"solves": solves, "n": n}}
    return spec.load_module("metrics", "cold.fw_round_vpu_share").read(run)


def test_fw_round_share_by_hand():
    # a round loop (while) around the pivot closure and the stripe grid,
    # and a copy outside it: only the two kernels' self time counts
    ev = tracing.Events(
        device={0: [(0, 50, "%copy.1 = f32[64,64] copy(...)"),
                    (100, 1000, "%while.2 = (f32[64,64]) while(...)"),
                    (110, 150, "%fw_block.7 = f32[1,32,32] custom-call(...)"),
                    (160, 960, "%fw_round.7 = f32[1,64,64] custom-call(...)")]},
        spans=[(0, 1000, "bench.window")])
    least = 2 * counts.least_seconds(counts.fw_ops(64), counts.fw_min_bytes(64), PEAK)
    assert _share(ev) == pytest.approx(100.0 * least / ((40 + 800) * 1e-9))


def test_fw_round_share_silent_without_named_kernels():
    # a program whose kernels are named after their enclosing function
    ev = tracing.Events(
        device={0: [(0, 500, "%fw_round_pallas.14 = f32[1,32,32] custom-call(...)"),
                    (500, 900, "%fw_round_pallas.15 = f32[64,64] custom-call(...)")]},
        spans=[(0, 1000, "bench.window")])
    assert _share(ev) is None
    assert spec.load_module("metrics", "cold.fw_round_vpu_share").read(
        {"trace": None, "peak": PEAK, "out": {"solves": 2, "n": 64}}) is None


def test_program_spans_stay_out_of_the_benchmark_spans(tmp_path):
    """A solve_batch traced inside the benchmark's spans: the reduction
    keeps the two ``bench.*`` spans and none of the program's."""
    import jax
    import numpy as np

    from repro.core import solve_batch

    mats = [np.where(np.eye(k, dtype=bool), 0.0, 1.0).astype(np.float32)
            for k in (5, 12, 20)]
    solve_batch(mats, bucket_by_size=True)           # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.call"):
                jax.block_until_ready(solve_batch(mats, bucket_by_size=True).dist)
    finally:
        jax.profiler.stop_trace()
    ev = tracing.load_xplane(tracing.find_xplane(tmp_path))
    assert sorted(name for _, _, name in ev.spans) == ["bench.call", "bench.window"]
    s = tracing.Summary(ev)
    assert set(s.spans) == {"bench.call"}
    assert {name for name, _ in s.idle_gaps()} <= {"bench.call", "host"}
