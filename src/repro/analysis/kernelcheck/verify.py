"""The four kernel-grid theorems, checked per recorded ``pallas_call``.

Static theorems (``check_call``), decided from the captured grid, specs,
``dimension_semantics``, and concrete scalar-prefetch operands — no kernel
execution needed:

* **write-race freedom** — output tiles written from grid points that
  differ along an axis declared ``"parallel"`` are a data race (the
  hardware may run those points in any order or concurrently); revisits
  are only legal along sequential axes, and the revisiting grid steps must
  be *consecutive* in lexicographic order (the TPU holds the live output
  block in VMEM between revisits — an interleaved visitor flushes it).
* **coverage** — the output index map must tile the output exactly: the
  block shape divides the operand, every tile is visited (no holes), and
  no tile index falls outside the operand (flagged as **bounds**).
* **scratch carry** (a **race**) — a VMEM scratch value written at one
  grid point and read at a later one (``simulate`` records each such
  carry) crosses the first axis on which the two points differ.  That
  axis and every axis inside it must be sequential: each core of a chip
  that splits ``"parallel"`` axes owns its own scratch, so a carry across
  a parallel axis reads another core's (never written) buffer.

Dynamic theorems (``verify_case``), decided by running the kernel body on
every grid point via ``simulate`` and comparing the builder's final return
value against the semiring oracle in ``kernels.ref``:

* **bounds** — every input tile (including the scalar-prefetch ``rows[i]``
  gather) stays inside its padded operand; violations are recorded by the
  simulator and surfaced here.
* **padding soundness / init** — a surviving output canary is an
  accumulate-before-init (**uninit**: dropped or mis-gated
  ``pl.when(program_id == 0)``); a value mismatch on a padding-exercising
  case is **padding** (the padded tiles were not inert under the
  semiring); any other divergence from the oracle is **mismatch**.

Static problems suppress the differential comparison for that case — a
mis-tiled kernel produces garbage downstream, and one root-cause finding
beats a cascade.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import List

import jax
import numpy as np

from .intercept import KernelCall, intercept_pallas_calls
from .simulate import INT_CANARY, block_index, simulate

__all__ = ["Problem", "KINDS", "check_call", "verify_case"]

# the closed vocabulary of defect kinds (the mutation corpus keys on these)
KINDS = ("race", "bounds", "coverage", "padding", "uninit", "mismatch")


@dataclass(frozen=True)
class Problem:
    """One refuted theorem: ``kind`` is drawn from :data:`KINDS`."""

    kind: str
    where: str
    message: str

    def __str__(self) -> str:
        return f"[{self.kind}] {self.where}: {self.message}"


def _tile(block_shape) -> tuple:
    """Block shape with squeezed (``None``) dims counted as unit tiles."""
    return tuple(1 if b is None else b for b in block_shape)


def check_call(call: KernelCall, where: str = "pallas_call") -> List[Problem]:
    """Static race/coverage theorems plus the simulator's bounds record."""
    problems: List[Problem] = []
    grid = call.grid
    if not grid or any(d <= 0 for d in grid):
        return [Problem("coverage", where, f"degenerate grid {grid}")]
    for msg in call.errors:
        problems.append(Problem("bounds", where, msg))

    sem = call.dimension_semantics
    if sem is None:
        sem = ("arbitrary",) * len(grid)  # Pallas default: all sequential
    if len(sem) != len(grid):
        problems.append(
            Problem(
                "race", where,
                f"dimension_semantics arity {len(sem)} != grid rank "
                f"{len(grid)}: {sem} vs {grid}",
            )
        )
        sem = ("arbitrary",) * len(grid)
    parallel = [a for a, s in enumerate(sem) if s == "parallel"]

    for ai, (spec, arr) in enumerate(zip(call.in_specs, call.inputs)):
        if len(tuple(spec.block_shape)) != arr.ndim:
            problems.append(
                Problem(
                    "bounds", where,
                    f"input {ai}: block rank {len(tuple(spec.block_shape))} "
                    f"!= operand rank {arr.ndim}",
                )
            )

    points = list(np.ndindex(*grid))
    for oi, (spec, out) in enumerate(zip(call.out_specs, call.out_shapes)):
        bs = _tile(spec.block_shape)
        shape = tuple(out.shape)
        if len(bs) != len(shape):
            problems.append(
                Problem(
                    "bounds", where,
                    f"output {oi}: block rank {len(bs)} != operand rank "
                    f"{len(shape)}",
                )
            )
            continue
        if any(n % b for n, b in zip(shape, bs)):
            problems.append(
                Problem(
                    "coverage", where,
                    f"output {oi}: shape {shape} is not an exact tiling of "
                    f"block {bs} (partial edge tile)",
                )
            )
            continue
        tile_range = tuple(n // b for n, b in zip(shape, bs))
        expected = set(np.ndindex(*tile_range))
        visits = {}
        for pos, pt in enumerate(points):
            idx = block_index(spec, pt, call.prefetch)
            visits.setdefault(idx, []).append((pos, pt))
        for idx in sorted(set(visits) - expected):
            problems.append(
                Problem(
                    "bounds", where,
                    f"output {oi}: tile {idx} outside the {tile_range} tile "
                    f"range of shape {shape}",
                )
            )
        for idx in sorted(expected - set(visits)):
            problems.append(
                Problem(
                    "coverage", where,
                    f"output {oi}: tile {idx} of {tile_range} is never "
                    f"written (hole)",
                )
            )
        for idx, pps in sorted(visits.items()):
            pts = [pt for _, pt in pps]
            for a in parallel:
                coords = sorted({pt[a] for pt in pts})
                if len(coords) > 1:
                    problems.append(
                        Problem(
                            "race", where,
                            f"output {oi}: tile {idx} written from grid "
                            f"coordinates {coords} along axis {a} declared "
                            f"'parallel' — write race (revisit axes must be "
                            f"'arbitrary')",
                        )
                    )
            poss = sorted(pos for pos, _ in pps)
            if poss[-1] - poss[0] != len(poss) - 1:
                problems.append(
                    Problem(
                        "race", where,
                        f"output {oi}: tile {idx} revisited at "
                        f"non-consecutive grid steps {poss} — revisit axes "
                        f"must be the innermost sequential dims",
                    )
                )
    problems.extend(_carry_problems(call, sem, where))
    return problems


def _carry_problems(call: KernelCall, sem, where: str) -> List[Problem]:
    """The scratch-carry theorem, one problem per (scratch, carry axis)."""
    found = {}
    for si, src, dst in sorted(call.carries):
        a_pt = tuple(int(c) for c in np.unravel_index(src, call.grid))
        b_pt = tuple(int(c) for c in np.unravel_index(dst, call.grid))
        axis = next(d for d in range(len(a_pt)) if a_pt[d] != b_pt[d])
        bad = [d for d in range(axis, len(sem)) if sem[d] == "parallel"]
        if bad and (si, axis) not in found:
            found[(si, axis)] = Problem(
                "race", where,
                f"scratch {si}: written at grid point {a_pt}, read at "
                f"{b_pt} — carried along axis {axis}, but axes {bad} are "
                f"declared 'parallel' (a carry axis and every axis inside "
                f"it must be 'arbitrary': each core owns its scratch)",
            )
    return list(found.values())


def _resolve_builder(case):
    if case.builder_fn is not None:
        return case.builder_fn
    # package __init__ re-exports shadow the submodule names, so go through
    # importlib rather than attribute access on repro.kernels
    mod = importlib.import_module(f"repro.kernels.{case.module}")
    return mod.PALLAS_BUILDERS[case.builder]


def verify_case(case) -> List[Problem]:
    """Run one lattice case end to end; [] means every theorem holds."""
    fn = _resolve_builder(case)
    with intercept_pallas_calls(executor=simulate) as calls:
        got = case.run(fn)
    where = case.name
    if not calls:
        return [
            Problem(
                "coverage", where,
                "builder made no pallas_call — nothing to verify",
            )
        ]
    problems: List[Problem] = []
    for ci, call in enumerate(calls):
        label = where if len(calls) == 1 else f"{where}#call{ci}"
        problems.extend(check_call(call, where=label))
    if problems:
        return problems

    exp_leaves = [np.asarray(v) for v in jax.tree_util.tree_leaves(case.expected())]
    got_leaves = [np.asarray(v) for v in jax.tree_util.tree_leaves(got)]
    if len(got_leaves) != len(exp_leaves):
        return [
            Problem(
                "mismatch", where,
                f"builder returned {len(got_leaves)} leaves, oracle "
                f"{len(exp_leaves)}",
            )
        ]
    for li, (g, e) in enumerate(zip(got_leaves, exp_leaves)):
        if g.shape != e.shape:
            problems.append(
                Problem(
                    "mismatch", where,
                    f"output {li}: shape {g.shape} != oracle {e.shape}",
                )
            )
            continue
        if g.dtype.kind in "iu":
            canary = (g == INT_CANARY) & (e != INT_CANARY)
        else:
            canary = np.isnan(g) & ~np.isnan(e)
        if canary.any():
            at = tuple(int(v) for v in np.argwhere(canary)[0])
            problems.append(
                Problem(
                    "uninit", where,
                    f"output {li}: canary survived at {at} "
                    f"({int(canary.sum())} sites) — tile accumulated before "
                    f"its init ran (missing or mis-gated "
                    f"pl.when(program_id == 0) init)",
                )
            )
            continue
        if g.dtype.kind == "f":
            bad = ~np.isclose(g, e, rtol=0.0, atol=case.atol, equal_nan=True)
        else:
            bad = g != e
        if bad.any():
            at = tuple(int(v) for v in np.argwhere(bad)[0])
            kind = "padding" if case.padded else "mismatch"
            tail = " — padded tiles are not inert under the semiring" if case.padded else ""
            problems.append(
                Problem(
                    kind, where,
                    f"output {li}: {int(bad.sum())} entries differ from the "
                    f"semiring oracle (first at {at}: got {g[at]!r}, want "
                    f"{e[at]!r}){tail}",
                )
            )
    return problems
