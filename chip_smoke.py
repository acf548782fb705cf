#!/usr/bin/env python3
"""Chip smoke test: run the served APSP path once on a TPU, at real sizes,
through the entry points a user calls, and check every answer.

    python chip_smoke.py                # one chip: phases a-d
    python chip_smoke.py --four-chips   # 2x2 mesh: distributed FW vs one chip

Phases, all in this one process (no child process touches the chip):

  a  cold ``repro.core.solve(method="blocked_fw")``, f32 tropical, n=16384,
     on a ``graphgen`` graph with integer weights; 8 sampled source rows
     bit-exact against ``scipy.sparse.csgraph.dijkstra`` on the host.
  b  the same solve with ``with_pred=True`` at n=8192: the predecessor
     trees pass ``paths.validate_tree``, sampled rows match Dijkstra, and
     reconstructed paths cost what ``dist`` says.  The round is one Pallas
     kernel (``fw_round_pred``); the benchmark's ``road8k.routes`` cell
     reads 0.59-0.62 s a solve at this n on a TPU v5 lite (a new reading,
     compile excluded), where the earlier composed argmin round took
     55.5 s in this phase, compile included.
  c  ``launch.serve.serve_apsp``: batched ``solve_batch`` over ragged
     graphs, n_max=128, batch 256; every graph of the last batch bit-exact
     against Dijkstra.
  d  ``launch.serve.serve_apsp_dynamic``: 4 supervised engines of n=4096
     under decrease and worsening update batches with ``verify_every`` on.
     The pool must report no drift, retry, quarantine, failed update or
     non-live answer, and both the rank-k update and the row-restricted
     close must have run.

``--four-chips`` runs only the distributed blocked FW
(``core.distributed`` through ``apsp_distributed``) on a 2x2 mesh built
from ``jax.devices()`` at n=16384, compares it bit-exact with the one-chip
``blocked_fw`` of the same graph, and prints the bytes each device holds.
It also counts, before the one-chip reference compiles anything, the
four-device programs that hold a Pallas kernel: the sharded body must run
one.

Each phase prints one line: the kernel backend, how many of the programs it
compiled hold a Pallas TPU kernel (``tpu_custom_call``), compile seconds,
one wall time (a smoke timing, not a metric), the verdict and the device's
``peak_bytes_in_use``.  The last line of standard output is the JSON
result, printed only when every phase passed.  With no TPU, or without the
rest of the repository beside it, the script exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import functools
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.caches import CACHE_ROOT, enable_compile_cache  # noqa: E402
from repro.kernels import ops  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
IR_DIR = CACHE_ROOT / "smoke-ir"    # one MLIR file per program compiled
RHO = 1.0                           # graphgen density knob: ~n/200 out-edges


# -- host reference -----------------------------------------------------------


def _csr(h: np.ndarray):
    """Dense cost matrix (inf = no edge, zero diagonal) -> scipy CSR graph."""
    from scipy.sparse import csr_matrix

    off = np.isfinite(h)
    np.fill_diagonal(off, False)
    ii, jj = np.nonzero(off)
    return csr_matrix((h[ii, jj], (ii, jj)), shape=h.shape)


def dijkstra_rows(h: np.ndarray, rows) -> np.ndarray:
    """Exact shortest-path rows from host Dijkstra, as float32 (integer
    weights: every distance is an exactly representable integer)."""
    from scipy.sparse.csgraph import dijkstra

    return dijkstra(_csr(h), directed=True, indices=rows).astype(np.float32)


def make_graph(seed: int, n: int, sharding=None) -> jax.Array:
    """Seeded ``graphgen`` cost matrix, generated on the device(s)."""
    from repro.core.graphgen import generate

    gen = jax.jit(lambda k: generate(k, n, rho=RHO)[0], out_shardings=sharding)
    return gen(jax.random.PRNGKey(seed))


# -- phases -------------------------------------------------------------------


def phase_cold(seed: int, n: int = 16384, n_rows: int = 8) -> dict:
    from repro.core import solve

    h = make_graph(seed, n)
    h_host = np.asarray(h)
    t = time.perf_counter()
    res = solve(h, method="blocked_fw")
    dist = jax.block_until_ready(res.dist)
    wall = time.perf_counter() - t
    rows = np.random.default_rng(seed).choice(n, n_rows, replace=False)
    got = np.asarray(dist[jnp.asarray(rows)])
    want = dijkstra_rows(h_host, rows)
    return {"wall_s": wall, "n": n,
            "checks": {"rows_bit_exact": bool(np.array_equal(got, want))}}


def phase_pred(seed: int, n: int = 8192, n_rows: int = 8) -> dict:
    from repro.core import solve
    from repro.core.paths import path_cost, reconstruct_path, validate_tree

    h = make_graph(seed + 1, n)
    h_host = np.asarray(h)
    t = time.perf_counter()
    res = solve(h, method="blocked_fw", with_pred=True)
    jax.block_until_ready((res.dist, res.pred))
    wall = time.perf_counter() - t
    dist, pred = np.asarray(res.dist), np.asarray(res.pred)
    rng = np.random.default_rng(seed)
    rows = rng.choice(n, n_rows, replace=False)
    paths_ok = True
    for i, j in rng.integers(0, n, (n_rows, 2)):
        path = reconstruct_path(pred, int(i), int(j))
        if not np.isfinite(dist[i, j]):
            paths_ok &= path is None
            continue
        paths_ok &= (path is not None and path[0] == i and path[-1] == j
                     and path_cost(h_host, path) == float(dist[i, j]))
    return {"wall_s": wall, "n": n, "checks": {
        "pred_tree_valid": validate_tree(h_host, dist, pred),
        "rows_bit_exact": bool(np.array_equal(dist[rows],
                                              dijkstra_rows(h_host, rows))),
        "paths_cost_dist": bool(paths_ok),
    }}


def phase_batch(seed: int, n_max: int = 128, batch: int = 256) -> dict:
    from repro.launch.serve import serve_apsp

    rep: dict = {}
    t = time.perf_counter()
    rc = serve_apsp(2 * batch, batch=batch, n_max=n_max, method="blocked_fw",
                    seed=seed, report=rep)
    wall = time.perf_counter() - t
    dist = np.asarray(rep["result"].dist)
    exact = all(
        np.array_equal(dist[i, :len(g), :len(g)],
                       dijkstra_rows(g, np.arange(len(g))))
        for i, g in enumerate(rep["graphs"])
    )
    return {"wall_s": wall, "n": n_max, "checks": {
        "exit_zero": rc == 0, "graphs_bit_exact": exact}}


def phase_dynamic(seed: int, n: int = 4096, graphs: int = 4,
                  requests: int = 48, verify_every: int = 12) -> dict:
    from repro.launch.serve import serve_apsp_dynamic

    rep: dict = {}
    t = time.perf_counter()
    rc = serve_apsp_dynamic(
        requests, n_max=n, graphs=graphs, mutate_rate=0.5, mutate_k=8,
        method="blocked_fw", verify_every=verify_every, seed=seed, rho=RHO,
        worsen_frac=0.5, report=rep,
    )
    wall = time.perf_counter() - t
    s = rep["summary"]
    pool, slots, eng = s["pool"], s["slots"], s["engines"]
    bad = {k: pool.get(k, 0) for k in (
        "verify_drift", "updates_failed", "queries_snapshot", "queries_shed",
        "deadline_misses", "poison_blocked")}
    bad.update({k: slots.get(k, 0) for k in ("retries", "quarantines")})
    return {"wall_s": wall, "n": n, "paths": eng, "checks": {
        "exit_zero": rc == 0,
        "verified": pool.get("verify_ok", 0) > 0,
        "no_faults": not any(bad.values()),
        "rank_k_ran": eng.get("rank_k", 0) > 0,
        "row_close_ran": eng.get("row_iters", 0) > 0,
    }}


def phase_four_chips(seed: int, compiles: "_Compiles", n: int = 16384,
                     block_size: int = 512) -> dict:
    from jax.sharding import Mesh, NamedSharding

    from repro.core import solve
    from repro.core.distributed import apsp_distributed, dist_spec

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    h = make_graph(seed, n, NamedSharding(mesh, dist_spec()))
    before = compiles.programs()
    t = time.perf_counter()
    out = apsp_distributed(h, mesh=mesh, method="fw", block_size=block_size)
    jax.block_until_ready(out)
    wall = time.perf_counter() - t
    # counted before the one-chip reference compiles its own kernels
    new = compiles.programs() - before
    sharded = compiles.with_kernels(new, partitions=4)
    held = {str(s.device.id): int(s.data.nbytes) for s in out.addressable_shards}
    peak = {str(d.id): int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.devices()}
    got = np.asarray(out)
    one = solve(jax.device_put(h, jax.devices()[0]), method="blocked_fw")
    want = np.asarray(one.dist)
    quarter = n * n * 4 // 4                  # f32 bytes over 4 devices
    return {"wall_s": wall, "n": n, "bytes_per_device": held,
            "peak_bytes_per_device_before_one_chip_solve": peak,
            "distributed_programs_with_tpu_custom_call":
                f"{sharded}/{len(new)}",
            "checks": {
                "distributed_kernels_compiled": sharded > 0,
                "bit_exact_vs_one_chip": bool(np.array_equal(got, want)),
                "quarter_per_device": sorted(held.values()) == [quarter] * 4,
            }}


# -- harness ------------------------------------------------------------------


class _Compiles:
    """Backend compile seconds (JAX's monitoring events) and the programs
    lowered (JAX's IR dump), read per phase."""

    def __init__(self):
        self.seconds = 0.0
        shutil.rmtree(IR_DIR, ignore_errors=True)
        IR_DIR.mkdir(parents=True)
        jax.config.update("jax_dump_ir_to", str(IR_DIR))
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.seconds += duration

    def programs(self) -> set:
        return set(IR_DIR.glob("*.mlir"))

    @staticmethod
    def with_kernels(paths, partitions: int = 0) -> int:
        """How many of ``paths`` hold a Pallas TPU kernel (and, given
        ``partitions``, were compiled for that many devices)."""
        tag = f"mhlo.num_partitions = {partitions} " if partitions else ""
        return sum(
            "tpu_custom_call" in t and tag in t
            for t in (p.read_text(errors="replace") for p in paths)
        )


def run_phase(name: str, fn, compiles: _Compiles, **kw) -> bool:
    before, s0 = compiles.programs(), compiles.seconds
    try:
        out = fn(**kw)
        err = None
    except Exception as e:  # a phase that raises fails; the others still run
        out, err = {"checks": {}}, f"{type(e).__name__}: {e}"
    new = compiles.programs() - before
    kernels = compiles.with_kernels(new)
    checks = dict(out.get("checks", {}), tpu_kernels_compiled=kernels > 0)
    ok = err is None and all(checks.values())
    stats = jax.devices()[0].memory_stats() or {}
    line = {
        "phase": name, "ok": ok, "backend": ops.backend(),
        "programs_with_tpu_custom_call": f"{kernels}/{len(new)}",
        "compile_s": round(compiles.seconds - s0, 3),
        "smoke_wall_s_not_a_metric": round(out.get("wall_s", 0.0), 3),
        "checks": checks,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        **{k: v for k, v in out.items() if k not in ("checks", "wall_s")},
    }
    if err:
        line["error"] = err[:2000]
    print(f"[phase] {json.dumps(line, sort_keys=True)}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only distributed FW on a 2x2 mesh vs one chip")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"[smoke] no TPU: JAX found {platform!r} devices", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"[smoke] need {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    if ops.backend() != "pallas":
        print(f"[smoke] kernel backend is {ops.backend()!r}, not 'pallas' "
              "(is REPRO_KERNELS set?)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"[smoke] {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{cache}", flush=True)
    compiles = _Compiles()

    if args.four_chips:
        phases = [("four_chips",
                   functools.partial(phase_four_chips, compiles=compiles))]
    else:
        phases = [("a_cold_solve", phase_cold), ("b_pred_solve", phase_pred),
                  ("c_serve_batch", phase_batch),
                  ("d_serve_dynamic", phase_dynamic)]
    ok = True
    for name, fn in phases:
        ok &= run_phase(name, fn, compiles, seed=args.seed)
    if not ok:
        print("[smoke] FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
