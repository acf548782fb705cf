"""Benchmark harness: one module per paper table/figure.  CSV to stdout,
machine-readable ``BENCH_apsp.json`` to disk (perf trajectory across PRs).

    PYTHONPATH=src python -m benchmarks.run [--quick|--smoke] [--json PATH]

``--smoke`` is the tier-1 canary (``make bench-smoke``): autotune + the
benchmark sweeps at N<=128, a few seconds total, so dispatch regressions
surface without the full sweep.
"""

from __future__ import annotations

import argparse
import csv
import json
import sys
import time


def _apsp_summary(rows):
    """Per-method ms / graphs-per-sec from the fig10 sweep rows."""
    methods = {
        "us_squaring_fw_accel": "squaring",
        "us_rkleene_accel": "rkleene",
        "us_blocked_fw_accel": "blocked_fw",
    }
    out = {}
    for r in rows:
        if r.get("bench") != "fig10_apsp_runtime":
            continue
        for col, method in methods.items():
            if col in r:
                ms = r[col] / 1e3
                out.setdefault(method, {})[str(r["n"])] = {
                    "ms": ms,
                    "graphs_per_s": 1e3 / ms if ms > 0 else None,
                }
    return out


def _check_rkleene_monotone(rows, tol: float = 0.25, base: int = 64):
    """The monotonicity smoke assertion (ISSUE 5): R-Kleene runtime must be
    non-decreasing in N across the fig10 sweep, up to ``tol`` jitter —
    the pow-2 padding pathology (N=384 solving a padded 512 problem,
    slower than true N=512) trips this immediately.  Pairs whose *padded*
    edges coincide (e.g. the smoke run's N=32 and N=64 both close one
    base-64 leaf) do identical work and carry no ordering expectation, so
    they are skipped rather than left to jitter-fail the gate.  Returns
    the check row and raises on violation."""
    from repro.core.rkleene import padded_size

    pts = sorted(
        (r["n"], r["us_rkleene_accel"])
        for r in rows
        if r.get("bench") == "fig10_apsp_runtime" and "us_rkleene_accel" in r
    )
    violations = [
        {"n_small": n0, "n_large": n1, "us_small": t0, "us_large": t1}
        for (n0, t0), (n1, t1) in zip(pts, pts[1:])
        if padded_size(n0, base) < padded_size(n1, base)
        and t1 < t0 * (1.0 - tol)
    ]
    row = {
        "bench": "rkleene_monotonicity",
        "ok": not violations,
        "tolerance": tol,
        "sweep": {str(n): t for n, t in pts},
        "violations": violations,
    }
    assert not violations, (
        f"R-Kleene runtime not monotone in N (pad/split rule regressed?): "
        f"{violations}"
    )
    return row


def _write_json(path, *, mode, all_rows, fused_rows):
    from repro.kernels import autotune, ops

    fused = next(
        (r for r in fused_rows if r.get("bench") == "fused_vs_unfused_blocked_fw"),
        None,
    )
    fused_round = next(
        (r for r in all_rows if r.get("bench") == "fused_round"), None
    )
    dynamic = next(
        (r for r in all_rows if r.get("bench") == "dynamic_update_vs_resolve"),
        None,
    )
    worsening = next(
        (r for r in all_rows if r.get("bench") == "dynamic_worsening"), None
    )
    resilience = next(
        (r for r in all_rows if r.get("bench") == "serve_resilience"), None
    )
    concurrent = next(
        (r for r in all_rows if r.get("bench") == "serve_concurrent"), None
    )
    payload = {
        "schema": 1,
        "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "mode": mode,
        "backend": ops.backend(),
        "autotune": {
            "mode": autotune.mode(),
            "cache": str(autotune.cache_path()),
            # only the entries this run consulted/tuned — the machine-wide
            # cache may hold unrelated shapes that would make cross-PR
            # trajectory diffs spurious
            "entries": autotune.touched_entries(),
        },
        "apsp": _apsp_summary(all_rows),
        "fused_vs_unfused": fused,
        "fused_round": fused_round,
        "dynamic_update_vs_resolve": dynamic,
        "dynamic_worsening": worsening,
        "serve_resilience": resilience,
        "serve_concurrent": concurrent,
        "rows": all_rows,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True, default=str)
    print(f"# wrote {path}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller sweeps")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (N<=128) — the tier-1 dispatch canary")
    ap.add_argument("--json", default=None,
                    help="machine-readable output path ('' to skip; default "
                         "BENCH_apsp.json, or BENCH_apsp_smoke.json under "
                         "--smoke so the canary never clobbers the tracked "
                         "full-run trajectory)")
    args = ap.parse_args(argv)
    from repro.caches import enable_compile_cache

    enable_compile_cache()
    if args.json is None:
        args.json = "BENCH_apsp_smoke.json" if args.smoke else "BENCH_apsp.json"

    from benchmarks import (
        bench_apsp,
        bench_blocksize,
        bench_dynamic,
        bench_fused,
        bench_graphgen,
        bench_minplus,
        bench_round,
        bench_serve_resilience,
    )

    if args.smoke:
        mode = "smoke"
        suites = [
            ("fig10_apsp", lambda: bench_apsp.run(
                sizes=(32, 64, 128), py_cpu_max=64)),
            ("fused_round", lambda: bench_round.run(n=128, reps=2)),
            ("fused_dispatch", lambda: bench_fused.run(
                n=128, block=32, reps=1)),
            ("dynamic_update", lambda: bench_dynamic.run(
                n=128, k=8, reps=2, block_size=64)),
            ("dynamic_worsening", lambda: bench_dynamic.run_worsening(
                n=128, k=8, reps=2, block_size=64)),
            ("serve_resilience", lambda: bench_serve_resilience.run(
                n=64, graphs=2, requests=60, k=4, budget_engines=1,
                deadline_ms=100.0)),
            ("serve_concurrent", lambda: bench_serve_resilience.run_concurrent(
                n=64, graphs=2, requests=60, k=4, block_size=32)),
        ]
    else:
        mode = "quick" if args.quick else "full"
        suites = [
            ("fig9_graphgen", lambda: bench_graphgen.run(
                n_graphs=60 if args.quick else 200, v_max=200 if args.quick else 400)),
            ("fig10_apsp", lambda: bench_apsp.run(
                sizes=(64, 128, 256) if args.quick else (64, 128, 256, 384, 512),
                py_cpu_max=128 if args.quick else 192)),
            ("fused_round", lambda: bench_round.run(
                n=256 if args.quick else 512, reps=2 if args.quick else 3)),
            ("minplus_wall", lambda: bench_minplus.run(
                sizes=(128, 256) if args.quick else (128, 256, 512, 1024))),
            ("blocked_fw_tiles", lambda: bench_blocksize.run(
                n=256 if args.quick else 512,
                blocks=(32, 64, 128) if args.quick else (32, 64, 128, 256))),
            ("fused_dispatch", lambda: bench_fused.run(
                n=256 if args.quick else 1024,
                block=64 if args.quick else 128,
                reps=2 if args.quick else 3)),
            ("dynamic_update", lambda: bench_dynamic.run(
                n=256 if args.quick else 512, k=16,
                reps=3 if args.quick else 5,
                block_size=64 if args.quick else 128)),
            ("dynamic_worsening", lambda: bench_dynamic.run_worsening(
                n=256 if args.quick else 512, k=16,
                reps=3 if args.quick else 5,
                block_size=64 if args.quick else 128)),
            ("serve_resilience", lambda: bench_serve_resilience.run(
                n=128 if args.quick else 256,
                graphs=3, requests=120 if args.quick else 300,
                budget_engines=2, deadline_ms=50.0,
                block_size=64 if args.quick else 128)),
            ("serve_concurrent", lambda: bench_serve_resilience.run_concurrent(
                n=256 if args.quick else 512,
                graphs=2, requests=120 if args.quick else 200,
                block_size=64 if args.quick else 128)),
        ]

    all_rows, fused_rows = [], []
    for name, fn in suites:
        t0 = time.time()
        rows = fn()
        print(f"# {name}: {len(rows)} rows in {time.time()-t0:.1f}s",
              file=sys.stderr)
        all_rows.extend(rows)
        if name == "fused_dispatch":
            fused_rows = rows

    all_rows.append(_check_rkleene_monotone(all_rows))

    if args.json:
        _write_json(args.json, mode=mode, all_rows=all_rows,
                    fused_rows=fused_rows)

    csv_rows = [
        {k: v for k, v in r.items() if not isinstance(v, dict)}
        for r in all_rows
    ]
    keys = []
    for r in csv_rows:
        for k in r:
            if k not in keys:
                keys.append(k)
    w = csv.DictWriter(sys.stdout, fieldnames=keys)
    w.writeheader()
    for r in csv_rows:
        w.writerow(r)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
