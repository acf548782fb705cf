"""Kernel grid verifier — concolic proofs over every Pallas kernel's grid.

The repo's performance story rests on four hand-written Pallas kernels
(``kernels.minplus``, ``fw_block``, ``fw_round``, ``row_close``) whose
correctness hinges entirely on grid/BlockSpec index maps: a wrong index map
is a *silent* data race or out-of-bounds tile that differential tests only
catch at the specific shapes they happen to run.  This package machine-
checks the kernels themselves:

* ``intercept``  — replaces ``pl.pallas_call`` at trace time and records
  ``(grid, in_specs, out_specs, index maps, block shapes, scalar-prefetch
  operands, dimension_semantics)`` from every call site, so the proofs see
  exactly what the builder would hand the Mosaic compiler (no source
  parsing).
* ``simulate``   — a pure numpy/eager-jnp Pallas grid interpreter: runs the
  real kernel body once per grid point against block views, with
  ``pl.program_id`` / ``pl.when`` patched to the concrete coordinates and
  output buffers seeded with a canary, checking every tile's bounds before
  it is touched.
* ``verify``     — the four theorems per recorded call: **write-race
  freedom** (output tiles of grid points differing along a ``parallel``
  axis are disjoint; revisit axes must be sequential and innermost),
  **bounds** (every tile of every operand inside its padded extent, the
  ``rows[i]`` scalar-prefetch gather included), **coverage** (output index
  maps tile the output exactly — no holes, no out-of-range tiles), and
  **padding soundness** (the builder's result over the canonical shape
  lattice — block-aligned, non-aligned/padded, batched g>1, gather — is
  bit-compatible with the semiring oracle; a surviving canary is an
  uninitialized accumulate, i.e. a dropped ``pl.when(program_id==0)``
  init).
* ``lattice``    — the canonical cases per kernel, plus parametrized case
  constructors the autotune-consistency tests use to prove every block-size
  candidate the tuner can propose is safe.
* ``mutants``    — the seeded mutation corpus (flipped index map, racy
  semantics, dropped init, shrunk output map, poisoned padding, unchecked
  gather) proving the verifier has teeth.
* ``checker``    — the registered ``kernel-grid`` gating checker
  (``tools/analyze.py --only kernel-grid`` / ``make analyze-kernels``).

Escape hatch: a file-scope ``# repro: allow-kernel-grid  <why>`` pragma in
the flagged kernel module, same contract as every other check.
"""

from .intercept import KernelCall, intercept_pallas_calls
from .simulate import simulate
from .verify import Problem, check_call, verify_case
from .lattice import (
    Case,
    case_for_fw_round_params,
    case_for_fw_round_pred_params,
    case_for_minplus_params,
    case_for_row_close_params,
    default_cases,
)
from .mutants import Mutant, control_case, mutant_cases
from . import checker as _checker  # noqa: F401  (registers "kernel-grid")

__all__ = [
    "KernelCall",
    "intercept_pallas_calls",
    "simulate",
    "Problem",
    "check_call",
    "verify_case",
    "Case",
    "default_cases",
    "case_for_minplus_params",
    "case_for_fw_round_params",
    "case_for_fw_round_pred_params",
    "case_for_row_close_params",
    "Mutant",
    "control_case",
    "mutant_cases",
]
