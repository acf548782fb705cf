"""Tests for the concolic Pallas grid verifier (`repro.analysis.kernelcheck`).

Three layers, mirroring the checker's own claims:

* **Lattice clean** — every case in the canonical shape lattice (aligned,
  padded, batched, scalar-prefetch gather) verifies with zero problems on
  the real kernels, and the verifier's differential leg is bit-exact
  against the ``kernels/ref.py`` oracles.
* **Mutation corpus** — each deliberately broken mini-kernel is flagged
  with exactly the theorem it violates (a verifier that passes broken
  kernels is worse than no verifier), and the unmutated control builder is
  clean, guarding the corpus itself against accidental defects.
* **Autotune consistency** — every candidate the tuner would measure for
  the minplus / fw_round / row_close families lies inside the proven-safe
  lattice: the tuner can never promote a tiling the verifier has not
  proven race-free, in-bounds, covering, and padding-sound.
"""

import json
from pathlib import Path

import pytest

from repro.analysis import CHECKERS, Project
from repro.analysis.kernelcheck import (
    case_for_fw_round_params,
    case_for_minplus_params,
    case_for_row_close_params,
    control_case,
    default_cases,
    mutant_cases,
    verify_case,
)
from repro.kernels import autotune

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parents[1]
FIXTURE = REPO / "tests" / "analysis_fixtures" / "badrepo"


def kinds(problems):
    return {p.kind for p in problems}


# ---------------------------------------------------------------------------
# the canonical lattice is clean on the real kernels
# ---------------------------------------------------------------------------

_DEFAULT = default_cases()


@pytest.mark.parametrize("case", _DEFAULT, ids=[c.name for c in _DEFAULT])
def test_default_lattice_clean(case):
    assert verify_case(case) == []


def test_default_lattice_spans_the_claimed_shapes():
    names = " ".join(c.name for c in _DEFAULT)
    # at least one of each claimed lattice point: aligned, padded, batched,
    # fused accumulate, witness tracking, non-tropical semirings, the
    # in-place round with and without predecessors, and the scalar-prefetch
    # row gather
    for tag in ("aligned", "padded", "batched", "accumulate", "argmin",
                "bottleneck", "reliability", "fw_block", "fw_round",
                "fw_round_pred", "row_close"):
        assert tag in names, f"lattice lost its {tag} coverage"


# ---------------------------------------------------------------------------
# mutation corpus: every seeded defect is caught, the control is clean
# ---------------------------------------------------------------------------

_MUTANTS = mutant_cases()


def test_control_mini_kernel_is_clean():
    assert verify_case(control_case()) == []


@pytest.mark.parametrize(
    "mutant", _MUTANTS, ids=[m.case.name for m in _MUTANTS]
)
def test_every_mutant_is_flagged_with_its_kind(mutant):
    problems = verify_case(mutant.case)
    assert problems, f"{mutant.case.name}: seeded defect not flagged at all"
    assert any(
        p.kind == mutant.expect and mutant.match in p.message for p in problems
    ), (
        f"{mutant.case.name}: expected a {mutant.expect!r} problem "
        f"mentioning {mutant.match!r}, got {[str(p) for p in problems]}"
    )


def test_corpus_covers_every_theorem():
    # the corpus must keep at least one mutant per theorem the checker
    # claims to prove (race, bounds, coverage, padding) plus the two
    # differential kinds (uninit canary, value mismatch)
    expected = {m.expect for m in _MUTANTS}
    assert {"race", "bounds", "coverage", "padding",
            "uninit", "mismatch"} <= expected


# ---------------------------------------------------------------------------
# checker surface: registered, gating, skips foreign trees, in the baseline
# ---------------------------------------------------------------------------

def test_kernel_grid_checker_is_registered_and_gating():
    checker = CHECKERS["kernel-grid"]
    assert not checker.advisory        # a refuted theorem must gate
    assert "grid" in checker.description


def test_kernel_grid_skips_trees_without_the_kernels(capsys):
    checker = CHECKERS["kernel-grid"]
    assert list(checker.run(Project(FIXTURE))) == []
    # announced, never silent: a tree without the kernel sources must not
    # masquerade as a verified one
    assert "tier B skipped" in capsys.readouterr().err


def test_baseline_includes_kernel_grid():
    payload = json.loads((REPO / "ANALYZE_baseline.json").read_text())
    assert "kernel-grid" in payload["checks"]
    assert payload["findings"] == []


# ---------------------------------------------------------------------------
# autotune <-> verifier consistency: tuner candidates are in the safe lattice
# ---------------------------------------------------------------------------

def _minplus_consistency_cases():
    out = []
    # aligned power-of-two bucket and a padded non-pow2 shape that forces
    # the clamp path (bucket(48)=64, bucket(80)=128, bucket(200)=256),
    # the batched spelling of the aligned bucket, and a contraction long
    # enough for several 128-lane k windows per block (bk 256 and 512)
    for m, k, n, g in ((64, 64, 64, 0), (48, 80, 200, 0), (64, 64, 64, 2),
                       (16, 512, 256, 0)):
        for i, params in enumerate(autotune.candidates("pallas", m, k, n)):
            out.append(case_for_minplus_params(
                params, m, k, n, g=g, seed=200 + i))
    return out


_MINPLUS_TUNER = _minplus_consistency_cases()


@pytest.mark.parametrize(
    "case", _MINPLUS_TUNER, ids=[c.name for c in _MINPLUS_TUNER]
)
def test_minplus_tuner_candidates_verify(case):
    assert verify_case(case) == []


_FW_ROUND_TUNER = [
    case_for_fw_round_params(b, 64, seed=300 + b)
    for b in autotune._FW_ROUND_BLOCKS
    if b <= 64                        # the solver pads n up to the block
]


@pytest.mark.parametrize(
    "case", _FW_ROUND_TUNER, ids=[c.name for c in _FW_ROUND_TUNER]
)
def test_fw_round_tuner_candidates_verify(case):
    assert verify_case(case) == []


def _row_close_consistency_cases():
    out = []
    for r, n in ((4, 64), (5, 200), (4, 512)):
        for i, params in enumerate(
            autotune._row_close_candidates("pallas", r, n)
        ):
            out.append(case_for_row_close_params(
                params, r, n, seed=400 + i))
    return out


_ROW_CLOSE_TUNER = _row_close_consistency_cases()


@pytest.mark.parametrize(
    "case", _ROW_CLOSE_TUNER, ids=[c.name for c in _ROW_CLOSE_TUNER]
)
def test_row_close_tuner_candidates_verify(case):
    assert verify_case(case) == []
