"""Distributed APSP runner — the paper's technique on a real mesh.

Generates a random cost matrix with the paper's generator, places it on the
mesh as a 2D block grid, solves with the selected distributed method, and
verifies against the single-device oracle for sizes where that is feasible.

The mesh is built from ``jax.devices()``: on a four-chip TPU v5e host the
default ``--mesh 2x2`` uses all four chips.  On a CPU, ask XLA for host
devices first (the flag must be set before JAX starts):
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python -m repro.launch.apsp_run --n 96 --method fw --block-size 16 --verify
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=96)
    ap.add_argument("--method", default="fw", choices=["squaring", "fw", "rkleene"])
    ap.add_argument("--mesh", default="2x2", help="e.g. 2x2, 4x1, 2x2x1")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--rho", type=float, default=50.0)
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--semiring", default="tropical",
                    help="path semiring (see repro.core.SEMIRINGS)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dims = tuple(int(x) for x in args.mesh.split("x"))
    import jax

    from repro.caches import enable_compile_cache
    from repro.core.distributed import apsp_distributed

    enable_compile_cache()
    from repro.core.graphgen import generate_np

    multi_pod = len(dims) == 3
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    mesh = jax.make_mesh(dims, axes)
    print(f"[mesh] {dict(zip(axes, dims))} = {mesh.size} devices")

    from repro.core import get_semiring
    from repro.launch.serve import _recast_graph

    sr = get_semiring(args.semiring)
    g = generate_np(np.random.default_rng(args.seed), args.n, rho=args.rho)
    h = _recast_graph(g.h, sr.name)
    print(f"[graph] N={g.n_nodes} edges={g.n_edges} density={g.density:.3f} "
          f"semiring={sr.name}")

    t0 = time.time()
    out = apsp_distributed(
        jax.numpy.asarray(h), mesh=mesh, method=args.method,
        multi_pod=multi_pod, block_size=args.block_size, semiring=sr,
    )
    out = np.asarray(out)
    reach = float((~np.asarray(sr.is_zero(out))).mean())
    print(f"[solve] method={args.method} wall={time.time()-t0:.2f}s "
          f"reachable-pairs={reach:.3f}")

    if args.verify:
        add = {"tropical": np.minimum}.get(sr.name, np.maximum)
        mul = {"tropical": np.add, "reliability": np.multiply}.get(
            sr.name, np.minimum
        )
        d = h.copy()
        for k in range(args.n):
            d = add(d, mul(d[:, k][:, None], d[k, :][None, :]))
        ok = np.allclose(out, d, equal_nan=True)
        print(f"[verify] vs numpy FW oracle: {'OK' if ok else 'MISMATCH'}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
