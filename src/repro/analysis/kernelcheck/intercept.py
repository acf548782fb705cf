"""``pl.pallas_call`` interception: capture grid/spec/index-map structure.

The verifier never parses kernel source for its grid facts — it swaps
``pl.pallas_call`` for a recorder while the raw (unjitted) builder runs, so
the captured ``(grid, in_specs, out_specs, dimension_semantics)`` are
exactly the objects the builder would hand the Mosaic compiler, after all
of the builder's own clamping/padding/spec derivation.  Both call styles
are normalized here: plain ``grid=``/``in_specs=``/``out_specs=`` and
``grid_spec=pltpu.PrefetchScalarGridSpec`` (whose leading
``num_scalar_prefetch`` operands are the scalar-prefetch arrays that index
maps receive as trailing arguments).

A recorded call is *executed* by the simulator (``simulate.simulate``), so
the builder's post-processing (slice-back, batch squeeze) runs on real
simulated outputs and the final return value is comparable to the semiring
oracle.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Set, Tuple

import jax
import numpy as np
from jax.experimental import pallas as _pallas

__all__ = ["KernelCall", "intercept_pallas_calls"]


@dataclass
class KernelCall:
    """One recorded ``pallas_call`` site plus its invocation operands."""

    kernel: Callable                       # the kernel body (often a partial)
    grid: Tuple[int, ...]
    in_specs: List[Any]                    # BlockSpec per non-prefetch input
    out_specs: List[Any]                   # BlockSpec leaves (tree-flattened)
    out_tree: Any                          # treedef of out_shape
    out_shapes: List[Any]                  # ShapeDtypeStruct leaves
    num_scalar_prefetch: int
    dimension_semantics: Optional[Tuple[str, ...]]
    interpret: bool
    scratch_shapes: List[Any] = field(default_factory=list)  # VMEM scratch
    operands: Tuple[np.ndarray, ...] = ()  # concrete, prefetch-first
    results: Tuple[np.ndarray, ...] = ()   # simulated output leaves
    errors: List[str] = field(default_factory=list)  # simulation-time bounds
    # (scratch index, writer step, reader step): a scratch value read at a
    # later grid step than the one that wrote it (steps in C order)
    carries: Set[Tuple[int, int, int]] = field(default_factory=set)

    @property
    def prefetch(self) -> Tuple[np.ndarray, ...]:
        return self.operands[: self.num_scalar_prefetch]

    @property
    def inputs(self) -> Tuple[np.ndarray, ...]:
        return self.operands[self.num_scalar_prefetch:]


def _is_spec(x) -> bool:
    return hasattr(x, "block_shape") and hasattr(x, "index_map")


@contextlib.contextmanager
def intercept_pallas_calls(executor: Optional[Callable] = None):
    """Swap ``pallas.pallas_call`` for a recorder; yields the call list.

    ``executor(call) -> [np.ndarray leaves]`` produces each call's outputs
    (default: canary-free zeros, for record-only uses).  The recorder's
    return value mirrors the real API: a function of the operands returning
    the out_shape pytree (as jnp arrays), so builders run unmodified.
    """
    calls: List[KernelCall] = []
    real = _pallas.pallas_call

    def fake_pallas_call(
        kernel,
        *,
        grid=None,
        in_specs=None,
        out_specs=None,
        out_shape=None,
        grid_spec=None,
        interpret=False,
        compiler_params=None,
        scratch_shapes=(),
        **_kw,
    ):
        g, isp, osp, nsp = grid, in_specs, out_specs, 0
        if grid_spec is not None:
            g = grid_spec.grid
            isp = grid_spec.in_specs
            osp = grid_spec.out_specs
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
            scratch_shapes = getattr(grid_spec, "scratch_shapes", ()) or ()
        out_leaves, out_tree = jax.tree_util.tree_flatten(out_shape)
        osp_leaves = jax.tree_util.tree_leaves(osp, is_leaf=_is_spec)
        isp_leaves = jax.tree_util.tree_leaves(isp, is_leaf=_is_spec)
        sem = getattr(compiler_params, "dimension_semantics", None)
        call = KernelCall(
            kernel=kernel,
            grid=tuple(int(d) for d in (g or ())),
            in_specs=list(isp_leaves),
            out_specs=list(osp_leaves),
            out_tree=out_tree,
            out_shapes=list(out_leaves),
            num_scalar_prefetch=nsp,
            dimension_semantics=tuple(sem) if sem is not None else None,
            interpret=bool(interpret),
            scratch_shapes=list(scratch_shapes),
        )
        calls.append(call)

        def run(*operands):
            import jax.numpy as jnp

            call.operands = tuple(np.asarray(o) for o in operands)
            if executor is None:
                leaves = [
                    np.zeros(s.shape, np.dtype(s.dtype)) for s in call.out_shapes
                ]
            else:
                leaves = executor(call)
            call.results = tuple(leaves)
            return jax.tree_util.tree_unflatten(
                out_tree, [jnp.asarray(leaf) for leaf in leaves]
            )

        return run

    _pallas.pallas_call = fake_pallas_call
    try:
        yield calls
    finally:
        _pallas.pallas_call = real
