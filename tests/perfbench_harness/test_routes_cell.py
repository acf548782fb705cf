"""CPU tests of the route-table cell (``road8k.routes``) at a small size.

A sound run with predecessors is ``correct``; the configuration's bf16
control is not; and each fault a route table can carry (predecessors left
as the solver's initial ones, one predecessor moved to another arc into the
same node, one distance altered) reads ``correct`` false.  The witness rule
itself is checked by hand on a four-node graph.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from perfbench import harness, reference, reference_routes, spec  # noqa: E402

CELL = "road8k.routes"
SMALL_ROAD = {"rows": 8, "cols": 8, "n": 64}


def small_run(seed=12345678901, **kw):
    return harness.run_cell(CELL, seed, 0.5, False, t_process=time.perf_counter(),
                            require_tpu=False, cache=False,
                            config_overrides=SMALL_ROAD,
                            traffic_overrides={"check_rows": 64}, **kw)


def test_witness_rule_by_hand():
    # 0 -> 1 (100), 1 -> 2 (100), 0 -> 2 (300), 3 unreachable from 0
    src, dst, w = np.array([0, 1, 0, 3]), np.array([1, 2, 2, 0]), \
        np.array([100.0, 100.0, 300.0, 50.0])
    table = reference_routes.arc_table(4, src, dst, w)
    ref = reference.rows(reference.graph(4, src, dst, w), [0])
    assert ref.tolist() == [[0.0, 100.0, 200.0, np.inf]]

    def wrong(pred_row):
        return reference_routes.wrong_routes(ref, [0], np.array([pred_row]), table)

    assert wrong([0, 0, 1, -1]) == 0
    # the direct arc into 2 is a real arc but not on a shortest route
    assert wrong([0, 0, 0, -1]) == 1
    # no arc (3, 2) and an unreachable node with a predecessor; an empty one
    assert wrong([0, 0, 3, 0]) == 2
    assert wrong([0, -1, 1, -1]) == 1


def test_sound_run_is_correct():
    line = small_run()
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == {"wrong_entries", "wrong_routes",
                                   "inexact_references", "failed_requests"}
    names = {m["name"] for m in spec.end_to_end(spec.load_benchmark(), CELL)}
    assert set(line["metrics"]) == names == {"solve_s", "setup_s"}


def test_control_is_not_correct():
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, spec.find_cell(bench, CELL)["config"])
    line = small_run(control=cfg["control"])
    assert not line["correct"]
    assert line["checks"]["wrong_entries"]["value"] > 0


def _wrap(mp, change):
    from repro.core import apsp

    orig = apsp.METHODS["blocked_fw"]

    def wrapped(h, with_pred, **kw):
        d, p = orig(h, with_pred, **kw)
        return change(h, d, p)

    mp.setitem(apsp.METHODS, "blocked_fw", wrapped)


def _initial_predecessors(mp):
    from repro.core.floyd_warshall import init_pred

    _wrap(mp, lambda h, d, p: (d, init_pred(h)))


def _predecessor_moved(mp):
    """Row 0: the first node with a second arc into it that is not on any
    shortest route from 0 takes that arc's tail as its predecessor."""
    def move(h, d, p):
        hh, dd = np.asarray(h), np.asarray(d)
        for j in range(1, hh.shape[0]):
            tails = [k for k in np.flatnonzero(np.isfinite(hh[:, j]))
                     if k != j and dd[0, k] + hh[k, j] > dd[0, j]]
            if tails:
                return d, p.at[0, j].set(int(tails[0]))
        raise AssertionError("no arc to move onto")
    _wrap(mp, move)


def _distance_altered(mp):
    _wrap(mp, lambda h, d, p: (d.at[0, -1].add(1.0), p))


FAULTS = [_initial_predecessors, _predecessor_moved, _distance_altered]


@pytest.mark.parametrize("fault", FAULTS, ids=[f.__name__[1:] for f in FAULTS])
def test_broken_route_table_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line = small_run()
    assert not line["correct"], line["checks"]


def test_program_without_the_fused_round_is_refused(monkeypatch):
    import importlib

    kernels = importlib.import_module("repro.kernels.fw_round")
    monkeypatch.delattr(kernels, "fw_round_pred_pallas")
    with pytest.raises(spec.SpecError, match="fused predecessor round"):
        small_run()
