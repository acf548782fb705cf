"""Closed loop, one client: back-to-back ``repro.core.solve(h,
with_pred=True)`` with the front door's defaults, cycling over ``graphs``
cost matrices made on the device in set-up.  Each solve ends in
``block_until_ready`` of its distances and predecessors.

Every solve's answer is checked: ``check_rows`` rows drawn from the seed
are gathered from both arrays on the device after the solve and compared,
after the window, with host Dijkstra rows of the same graph: the distances
entry by entry, the predecessors by the witness rule of
``reference_routes``.

The cell measures the fused predecessor round (one Pallas kernel a round).
A program without it composes the round from argmin products at about a
minute a solve at n=8192, longer than the window: set-up refuses it at
once rather than run a window that holds no measurement.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench import inputs, reference, reference_routes, spec
from perfbench.harness import log


def _solver(ctx):
    import importlib

    import jax.numpy as jnp
    from repro.core import solve

    # the package re-exports ops.fw_round under the module's name
    kernels = importlib.import_module("repro.kernels.fw_round")
    if not hasattr(kernels, "fw_round_pred_pallas"):
        raise spec.SpecError(
            "the program has no fused predecessor round "
            "(repro.kernels.fw_round.fw_round_pred_pallas): a route solve "
            "would outlast the window")
    if ctx.control == "bf16":
        # the program's own lower-precision path: bfloat16 distance state
        return lambda h: solve(h, with_pred=True, dtype=jnp.bfloat16)
    return lambda h: solve(h, with_pred=True)


def setup(ctx):
    import jax
    import jax.numpy as jnp

    solve = _solver(ctx)
    gen = spec.load_module("generators", ctx.config["generator"])
    arcs = [gen.generate(ctx.config, ctx.seed, salt=i)
            for i in range(int(ctx.traffic["graphs"]))]
    n = arcs[0]["n"]
    hs = [inputs.dense_device(n, a["src"], a["dst"], a["w"]) for a in arcs]
    k = min(int(ctx.traffic["check_rows"]), n)
    take = jax.jit(lambda d, r: d[r])
    # every graph has the same shape: one solve and one gather of each
    # array warm the window
    res = solve(hs[0])
    rows = jnp.zeros(k, jnp.int32)
    jax.block_until_ready([take(res.dist, rows), take(res.pred, rows)])
    del res
    jax.block_until_ready(hs)
    return {"arcs": arcs, "hs": hs, "take": take, "solve": solve, "n": n, "k": k,
            "rng": np.random.default_rng([ctx.seed % 2**63, 23])}


def window(ctx, st):
    import jax
    import jax.numpy as jnp

    hs, take, solve, rng = st["hs"], st["take"], st["solve"], st["rng"]
    n, k = st["n"], st["k"]
    got = []
    i = 0
    t0 = time.perf_counter()
    end = t0 + ctx.seconds
    while True:
        g = i % len(hs)
        rows = rng.choice(n, k, replace=False).astype(np.int32)
        with ctx.span("bench.call"):
            res = solve(hs[g])
            jax.block_until_ready([res.dist, res.pred])
        r = jnp.asarray(rows)
        got.append((g, rows, take(res.dist, r), take(res.pred, r)))
        i += 1
        if time.perf_counter() >= end:
            break
    t1 = time.perf_counter()
    jax.block_until_ready([(d, p) for _, _, d, p in got])
    return {"attempted": i, "failed": 0, "window_s": t1 - t0, "solves": i,
            "n": n, "e2e": {"solve_s": (t1 - t0) / i}, "got": got}


def check(ctx, st, out):
    got = [(g, rows, np.asarray(d, np.float32), np.asarray(p))
           for g, rows, d, p in out.pop("got")]
    arcs = st["arcs"]
    st.clear()                                   # frees the device graphs
    need = {}
    for g, rows, _, _ in got:
        need.setdefault(g, set()).update(int(r) for r in rows)
    ref, tables = {}, {}
    inexact = 0
    for g, rows in need.items():
        a = arcs[g]
        order = sorted(rows)
        d = reference.rows(reference.graph(a["n"], a["src"], a["dst"], a["w"]), order)
        inexact += not reference.exact_in_f32(d)
        ref[g] = dict(zip(order, d))
        tables[g] = reference_routes.arc_table(a["n"], a["src"], a["dst"], a["w"])
    wrong = routes = 0
    for g, rows, dist, pred in got:
        want = np.stack([ref[g][int(r)] for r in rows])
        wrong += int(np.sum(dist != want))
        routes += reference_routes.wrong_routes(want, rows, pred, tables[g])
    compared = sum(d.size for _, _, d, _ in got)
    log(f"[compare] {compared} distances and as many predecessors of "
        f"{len(got)} solves against host Dijkstra")
    return {"wrong_entries": (wrong, 0), "wrong_routes": (routes, 0),
            "inexact_references": (inexact, 0)}
