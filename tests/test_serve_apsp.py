"""The batched APSP serving loop (``serve_apsp``): every method and
semiring, with and without predecessors, answers each request exactly as a
single-graph ``solve`` of the same recast matrix."""

import numpy as np
import pytest

from repro.core import METHODS, SEMIRINGS, solve, validate_tree
from repro.launch.serve import serve_apsp


@pytest.fixture(autouse=True)
def _autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


@pytest.mark.parametrize("with_pred", [False, True], ids=["dist", "pred"])
@pytest.mark.parametrize("semiring", sorted(SEMIRINGS))
@pytest.mark.parametrize("method", sorted(METHODS))
def test_serve_apsp_last_batch_matches_solve(method, semiring, with_pred):
    r = {}
    assert serve_apsp(4, batch=4, n_max=16, method=method,
                      with_pred=with_pred, semiring=semiring, report=r) == 0
    res = r["result"]
    assert len(res) == len(r["graphs"]) == 4
    assert res.dist.shape == (4, 16, 16)
    assert (res.pred is not None) == with_pred
    for i, h in enumerate(r["graphs"]):
        got = res.unpadded(i)
        want = solve(h, method=method, with_pred=with_pred, semiring=semiring)
        assert got.dist.shape == h.shape
        assert np.array_equal(np.asarray(got.dist), np.asarray(want.dist)), i
        if with_pred:
            assert np.array_equal(np.asarray(got.pred), np.asarray(want.pred)), i
            assert validate_tree(h, np.asarray(got.dist), np.asarray(got.pred),
                                 semiring), i
