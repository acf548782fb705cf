"""Serving driver: batched APSP over a synthetic stream of graph requests.

Demonstrates the production serving shape: one compiled program reused
across requests, continuous batch slots with per-slot raggedness (the true
graph size per slot).  Incoming ragged graphs are packed into fixed
(G, N_max, N_max) inf-padded slots (padding is inert under (min, +)) so every
batch hits the same compiled ``solve_batch`` program; results are unpadded
per graph before returning.

With ``--mutate-rate > 0`` the driver switches to the *incremental*
serving shape: a supervised pool (``repro.launch.pool``) of persistent
``repro.core.DynamicAPSP`` engines behind health-checked slots, serving an
interleaved stream of edge-update batches (queued, coalesced, applied
without full re-solve) and distance queries (live under a deadline, or
bounded-staleness snapshot answers when a slot is degraded / the pool is
backlogged).  ``--fault-spec`` turns on the deterministic chaos layer
(``repro.launch.faults``); the run exits non-zero on verify drift, a
poisoned answer, or an unrecovered slot.

Usage:
    python -m repro.launch.serve --requests 64 --batch 16 \\
        --n-max 128 --method squaring
    python -m repro.launch.serve --requests 64 --n-max 128 \\
        --mutate-rate 0.5 --graphs 4 --verify-every 16
    python -m repro.launch.serve --requests 128 --n-max 64 \\
        --mutate-rate 0.5 --graphs 3 --verify-every 16 \\
        --fault-spec nan:0.1,crash:0.08:3,poison:0.05 --deadline-ms 50
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

import jax
import numpy as np

#: semirings the synthetic tropical request stream can be recast into.
RECASTABLE = ("tropical", "bottleneck", "reliability", "boolean")


def _recast_graph(h: np.ndarray, semiring: str) -> np.ndarray:
    """Recast a tropical cost matrix into another semiring's domain, keeping
    the same edge structure: no-edge -> semiring zero, diagonal -> one,
    costs -> capacities (bottleneck), probabilities 1/(1+cost)
    (reliability), or 1.0 (boolean).

    All arithmetic runs on the edge mask only — evaluating over the full
    matrix (inf no-edge entries included) raised spurious overflow/invalid
    numpy warnings."""
    if semiring == "tropical":
        return h
    _check_recastable(semiring)
    edge = np.isfinite(h) & ~np.eye(h.shape[0], dtype=bool)
    if semiring == "bottleneck":
        out = np.full(h.shape, -np.inf, np.float32)
        out[edge] = h[edge]
        np.fill_diagonal(out, np.inf)
    elif semiring == "reliability":
        out = np.zeros(h.shape, np.float32)
        out[edge] = 1.0 / (1.0 + h[edge])
        np.fill_diagonal(out, 1.0)
    else:  # boolean (guarded by _check_recastable)
        out = np.zeros(h.shape, np.float32)
        out[edge] = 1.0
        np.fill_diagonal(out, 1.0)
    return out


def _check_recastable(semiring: str) -> None:
    """Fail fast (before any serving work) with an actionable message for
    semirings the synthetic request stream has no domain mapping for."""
    if semiring not in RECASTABLE:
        raise ValueError(
            f"--semiring {semiring!r} has no request-recast rule: the serve "
            "loop generates tropical cost matrices and only maps them into "
            f"the built-in instances {RECASTABLE}.  Serve a custom "
            "registered semiring by feeding repro.core.solve_batch requests "
            "already expressed in that instance's domain."
        )


def serve_apsp(
    n_requests: int,
    *,
    batch: int = 16,
    n_max: int = 128,
    method: str = "squaring",
    with_pred: bool = False,
    semiring: str = "tropical",
    seed: int = 0,
    report: Optional[dict] = None,
) -> int:
    """Continuous-batched APSP serving over a synthetic graph-request stream.

    Requests are ragged (sizes ~ U[4, n_max]); each cycle fills ``batch``
    slots, pads into the fixed (batch, n_max, n_max) buffer, and runs the
    one compiled batched solver.  The first cycle pays compilation; every
    later cycle reuses it — that amortization is the whole point of the
    batched engine.  ``semiring`` serves any registry instance (widest
    path, reliability, reachability) from the same loop — the request
    stream is recast into that semiring's domain.  ``report``, when given,
    receives the last batch's cost matrices (``"graphs"``) and its
    :class:`repro.core.BatchAPSPResult` (``"result"``) for checking.
    """
    from repro.core import solve_batch
    from repro.core.graphgen import generate_np
    from repro.kernels import autotune

    _check_recastable(semiring)
    # Warm the autotune cache for the shapes this method's dispatch will
    # actually look up, *before* the solver first traces — dispatch reads
    # the cache at trace time, so tuning after the first batch would only
    # help the next process.  blocked_fw is natively batched (its panel
    # products are (G,·,·) -> g-bucketed keys); squaring is vmapped, so its
    # per-slice products dispatch as 2D (g=0 keys); rkleene's quadrant
    # products halve from n_max down to its leaf; classic does rank-1
    # updates and has nothing to tune.
    if autotune.mode() != "off":
        t_tune = time.time()
        src = "nothing to tune"
        if method == "blocked_fw":
            # round-shape winner (block size x fused-vs-split) first — it
            # decides which panel shapes the dispatch will look up at all
            e = autotune.tune_fw_round(n_max, reps=1, semiring=semiring)
            b = e.get("params", {}).get("block_size", 256)
            tuned = autotune.tune_blocked_fw(
                n_max, b, g=batch, reps=1, semiring=semiring
            )
            src = {"fw_round": e.get("source"),
                   **{k: e2.get("source") for k, e2 in tuned.items()}}
        elif method in ("squaring", "squaring_3d"):
            e = autotune.tune(n_max, n_max, n_max, reps=1, semiring=semiring)
            src = e.get("source")
        elif method == "rkleene":
            # quadrant-product edges are the *children* of each split along
            # the multiple-of-base chain — the root edge itself is never a
            # product operand, so don't pay its (largest) tune sweep
            from repro.core.rkleene import padded_size, split_point

            srcs = []
            seen = set()
            root = padded_size(n_max, 64)
            stack = [split_point(root, 64), root - split_point(root, 64)] \
                if root > 64 else []
            while stack:
                s = stack.pop()
                if s <= 64 or s in seen:
                    continue
                seen.add(s)
                srcs.append(
                    autotune.tune(s, s, s, reps=1, semiring=semiring)
                    .get("source")
                )
                m = split_point(s, 64)
                stack += [m, s - m]
            src = srcs or "leaf-only (closure kernel)"
        print(f"[autotune] dispatch warm for n_max={n_max} "
              f"({src}, {time.time()-t_tune:.2f}s)")

    rng = np.random.default_rng(seed)
    done = 0
    t0 = time.time()
    t_compile = None
    from repro.core import get_semiring

    sr = get_semiring(semiring)
    mats = res = None
    while done < n_requests:
        sizes = rng.integers(4, n_max + 1, size=batch)
        graphs = [generate_np(rng, int(n)) for n in sizes]
        mats = [_recast_graph(g.h, sr.name) for g in graphs]
        res = solve_batch(
            mats, method=method, with_pred=with_pred, n_max=n_max, semiring=sr,
        )
        jax.block_until_ready(res.dist)
        if t_compile is None:
            t_compile = time.time() - t0
        reach = [
            int((~np.asarray(sr.is_zero(res.unpadded(i).dist))).sum())
            for i in range(min(2, batch))
        ]
        done += batch
        print(f"[serve] batch of {batch} graphs (sizes {sizes.min()}-{sizes.max()}) "
              f"-> dist {tuple(res.dist.shape)} (reachable entries sample: {reach})")
    dt = time.time() - t0
    msg = f"[done] {done} graphs, {done / dt:.1f} graphs/s end-to-end"
    if t_compile is not None:
        if done > batch:               # steady-state needs a post-compile cycle
            steady = max(dt - t_compile, 1e-9)
            msg += f" ({(done - batch) / steady:.1f} graphs/s steady-state)"
        msg += f" (compile {t_compile:.2f}s, method={method})"
    print(msg)
    if report is not None:
        report.update(graphs=mats, result=res)
    return 0


def serve_apsp_dynamic(
    n_requests: int,
    *,
    n_max: int = 128,
    graphs: int = 4,
    mutate_rate: float = 0.5,
    mutate_k: int = 8,
    method: str = "blocked_fw",
    with_pred: bool = False,
    semiring: str = "tropical",
    verify_every: int = 0,
    seed: int = 0,
    fault_spec: str = "",
    deadline_ms: float = 0.0,
    mem_budget_mb: float = 0.0,
    backlog_watermark: int = 8,
    max_retries: int = 2,
    async_updates: bool = False,
    executor_workers: int = 1,
    reader_workers: int = 0,
    durability_dir: str = "",
    checkpoint_every: int = 0,
    rho: float = 60.0,
    worsen_frac: float = 0.05,
    report: Optional[dict] = None,
) -> int:
    """Incremental APSP serving on the supervised engine pool.

    Every persistent graph lives behind a health-checked
    :class:`repro.launch.pool.EngineSlot` (lifecycle warming -> healthy ->
    degraded -> quarantined -> evicted; see ``repro.launch.pool`` and
    COMPAT.md §Serving resilience).  The interleaved request stream: with
    probability ``mutate_rate`` a request is a batch of up to ``mutate_k``
    edge updates *queued* against a slot (coalesced into one rank-k
    dispatch at drain); otherwise it is a distance query served live under
    ``deadline_ms`` — or, when the slot is unhealthy / the backlog exceeds
    ``backlog_watermark`` / the deadline is missed, a bounded-staleness
    answer from the last-known-good snapshot with an explicit staleness
    tag.  ``verify_every`` > 0 differentially checks a slot against a cold
    solve every that-many requests; drift degrades the slot, triggers
    re-solve-on-drift, and fails the run (non-zero exit + structured error
    summary) so CI can gate on it.

    ``fault_spec`` turns on the deterministic chaos layer
    (``repro.launch.faults`` — injected NaN updates, slot crashes, latency
    spikes, state poison, memory-budget squeezes, plus the PR 10
    correlated kinds: whole-backend loss, compile-cache invalidation
    storms, crash-restore drills).  The exit code asserts the resilience
    contract: zero poisoned answers served, no unrecovered drift, and
    every slot back to healthy (or deliberately evicted under the memory
    budget) at the end of the run.

    ``async_updates`` moves drains onto the background executor
    (``executor_workers`` threads): submits/drain_all enqueue, queries
    read published snapshots with exact staleness tags, and the end of
    the run flushes the executor before verification.  ``durability_dir``
    (``"auto"`` = a fresh temp dir) gives every slot a write-ahead journal
    + atomic checkpoints every ``checkpoint_every`` drains, making the
    ``crash_restore:R`` drill an end-to-end checkpoint + replay exercise.
    ``reader_workers`` sizes the sync-path deadline readers (0 = one per
    slot).  ``rho`` is the graph generator's density knob and
    ``worsen_frac`` the share of update edges that get worse (the rest
    decrease or insert).  ``report``, when given, receives the pool
    summary (``"summary"``) and the drift reports (``"drift"``).
    """
    import json
    import tempfile

    from repro.core import get_semiring
    from repro.core.graphgen import generate_edge_updates, generate_np
    from repro.launch.faults import FaultInjector, FaultSpec
    from repro.launch.pool import EnginePool, SlotState

    _check_recastable(semiring)
    sr = get_semiring(semiring)
    spec = FaultSpec.parse(fault_spec)
    if durability_dir == "auto":
        durability_dir = tempfile.mkdtemp(prefix="repro-serve-dur-")
        print(f"[durability] journal + checkpoints under {durability_dir}")
    if spec.crash_restore > 0 and not durability_dir:
        raise ValueError(
            "crash_restore chaos needs --durability-dir (the drill restores "
            "from checkpoint + journal; pass 'auto' for a temp dir)"
        )
    pool = EnginePool(
        method=method, with_pred=with_pred, semiring=sr,
        max_retries=max_retries, deadline_s=deadline_ms / 1e3,
        mem_budget_bytes=int(mem_budget_mb * 2**20),
        backlog_watermark=backlog_watermark,
        injector=FaultInjector(spec, seed=seed), seed=seed,
        async_updates=async_updates, executor_workers=executor_workers,
        reader_workers=reader_workers,
        durability_dir=durability_dir or None,
        checkpoint_every=checkpoint_every,
    )
    rng = np.random.default_rng(seed)
    t0 = time.time()
    for gid in range(graphs):
        g = generate_np(rng, n_max, rho=rho)
        pool.admit(gid, _recast_graph(g.h, sr.name))
    t_warm = time.time() - t0
    print(f"[dynamic] {graphs} supervised slots of n={n_max} warmed "
          f"({t_warm:.2f}s incl. compile; states {pool.state_counts()})")
    if spec.any():
        print(f"[chaos] fault spec active: {fault_spec} (seed {seed})")

    n_updates = n_queries = 0
    t_update = t_query = 0.0
    drift_reports = []
    t0 = time.time()
    for req in range(n_requests):
        gi = int(rng.integers(0, graphs))
        slot = pool.slots[gi]
        if rng.uniform() < mutate_rate:
            # mostly decreases/inserts (the fast exact path), a sprinkle of
            # worsenings (exercises the bounded re-solve)
            u, v, w = generate_edge_updates(
                rng, slot.engine.h if slot.engine is not None else slot._h,
                int(rng.integers(1, mutate_k + 1)), worsen_frac=worsen_frac,
            )
            if semiring != "tropical":
                w = _recast_edge_weights(w, semiring)
            t = time.time()
            pool.submit_update(gi, u, v, w)
            if pool.backlog() > pool.backlog_watermark:
                # saturated: drain the queues (coalesced) so admission
                # control sheds at most a bounded query window
                pool.drain_all()
            t_update += time.time() - t
            n_updates += 1
            if req < 3 or req % max(n_requests // 4, 1) == 0:
                print(f"[mutate] slot {gi}: queued {u.size} edges "
                      f"(backlog {pool.backlog()}, state {slot.state}, "
                      f"req {req})")
        else:
            qi = rng.integers(0, n_max, 8)
            qj = rng.integers(0, n_max, 8)
            t = time.time()
            r = pool.query(gi, qi, qj)
            t_query += time.time() - t
            n_queries += 1
            assert r.values.shape == (8,)
            if r.source != "live" and (req < 3 or req % max(n_requests // 4, 1) == 0):
                print(f"[degraded] slot {gi}: {r.source} answer, staleness "
                      f"{r.staleness} (shed={r.shed} "
                      f"deadline_missed={r.deadline_missed}, req {req})")
        if verify_every and (req + 1) % verify_every == 0:
            check = pool.verify(gi)
            print(f"[verify] slot {gi} vs cold solve: "
                  f"{'OK' if check['ok'] else 'DRIFT'}"
                  + ("" if check["ok"] else f" (recovered={check['recovered']})"))
            if not check["ok"]:
                drift_reports.append(check)
    dt = time.time() - t0
    pool.recover_all(readmit=True)

    summary = pool.summary()
    print(f"[done] {n_requests} requests in {dt:.2f}s — "
          f"{n_updates} update batches ({1e3 * t_update / max(n_updates, 1):.1f} ms/submit+drain), "
          f"{n_queries} queries ({1e3 * t_query / max(n_queries, 1):.2f} ms/query)")
    print(f"[pool] {json.dumps(summary, sort_keys=True, default=str)}")
    pool.close()
    if report is not None:
        report.update(summary=summary, drift=drift_reports)

    # resilience contract: structured failure summary + non-zero exit so CI
    # can gate on drift / poison / unrecovered slots
    states = summary["states"]
    unrecovered = states[SlotState.DEGRADED] + states[SlotState.QUARANTINED]
    failures = {}
    if drift_reports:
        failures["verify_drift"] = drift_reports
    if summary["pool"]["poisoned_served"]:
        failures["poisoned_served"] = summary["pool"]["poisoned_served"]
    if unrecovered:
        failures["unrecovered_slots"] = {
            gid: s.state for gid, s in pool.slots.items()
            if s.state in (SlotState.DEGRADED, SlotState.QUARANTINED)
        }
    if failures:
        print(f"[serve-error] {json.dumps(failures, sort_keys=True, default=str)}")
        return 1
    return 0


def _recast_edge_weights(w: np.ndarray, semiring: str) -> np.ndarray:
    """Per-edge analogue of _recast_graph for streamed update weights.

    Non-tropical streams lose the generator's mostly-decrease guarantee
    (the engine classifies each batch itself, so results stay exact —
    only the update/re-solve mix shifts)."""
    if semiring == "bottleneck":
        return w
    if semiring == "reliability":
        return (1.0 / (1.0 + w)).astype(np.float32)
    return np.ones_like(w)  # boolean


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=16,
                    help="graph slots per serving cycle")
    ap.add_argument("--n-max", type=int, default=128,
                    help="padded graph edge (compiled shape)")
    ap.add_argument("--method", default="squaring",
                    help="solver (see repro.core.METHODS)")
    ap.add_argument("--with-pred", action="store_true",
                    help="also compute predecessor matrices")
    ap.add_argument("--semiring", default="tropical",
                    help="path semiring (see repro.core.SEMIRINGS)")
    ap.add_argument("--mutate-rate", type=float, default=0.0,
                    help="fraction of requests that are edge-update "
                         "batches against persistent graph state (> 0 "
                         "selects the incremental DynamicAPSP serving mode)")
    ap.add_argument("--graphs", type=int, default=4,
                    help="dynamic mode: persistent graph count")
    ap.add_argument("--mutate-k", type=int, default=8,
                    help="dynamic mode: max edges per update batch")
    ap.add_argument("--verify-every", type=int, default=0,
                    help="dynamic mode: differentially check an engine "
                         "against a cold solve every N requests (0 = off; "
                         "detected drift exits non-zero)")
    ap.add_argument("--fault-spec", default="",
                    help="dynamic mode: chaos layer, e.g. "
                         "'nan:0.1,crash:0.08:3,latency:0.1:20,poison:0.05,"
                         "mem:0.1:0.5' (see repro.launch.faults)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="dynamic mode: per-query deadline; a miss is "
                         "answered from the last-known-good snapshot (0 = off)")
    ap.add_argument("--mem-budget-mb", type=float, default=0.0,
                    help="dynamic mode: device-state budget; admissions "
                         "beyond it evict LRU slots (0 = unlimited)")
    ap.add_argument("--backlog-watermark", type=int, default=8,
                    help="dynamic mode: pending update batches above "
                         "which queries are shed to snapshots")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="dynamic mode: transient apply failures "
                         "retried (with backoff) before quarantine")
    ap.add_argument("--async-updates", action="store_true",
                    help="dynamic mode: apply update batches on the "
                         "background executor; queries read published "
                         "snapshots and never wait on an in-flight pass")
    ap.add_argument("--executor-workers", type=int, default=1,
                    help="dynamic mode: background drain threads "
                         "(with --async-updates)")
    ap.add_argument("--reader-workers", type=int, default=0,
                    help="dynamic mode: deadline-reader sizing for the "
                         "sync path (0 = one dedicated worker per slot)")
    ap.add_argument("--durability-dir", default="",
                    help="dynamic mode: per-slot write-ahead journal + "
                         "atomic engine checkpoints under this directory "
                         "('auto' = fresh temp dir); required by the "
                         "crash_restore chaos drill")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="dynamic mode: checkpoint a durable slot every "
                         "N successful drains (0 = only the build-time "
                         "checkpoint)")
    args = ap.parse_args(argv)
    from repro.caches import enable_compile_cache

    enable_compile_cache()
    if args.mutate_rate > 0.0:
        return serve_apsp_dynamic(
            args.requests, n_max=args.n_max, graphs=args.graphs,
            mutate_rate=args.mutate_rate, mutate_k=args.mutate_k,
            method=args.method, with_pred=args.with_pred,
            semiring=args.semiring, verify_every=args.verify_every,
            seed=args.seed, fault_spec=args.fault_spec,
            deadline_ms=args.deadline_ms,
            mem_budget_mb=args.mem_budget_mb,
            backlog_watermark=args.backlog_watermark,
            max_retries=args.max_retries,
            async_updates=args.async_updates,
            executor_workers=args.executor_workers,
            reader_workers=args.reader_workers,
            durability_dir=args.durability_dir,
            checkpoint_every=args.checkpoint_every,
        )
    return serve_apsp(
        args.requests, batch=args.batch, n_max=args.n_max,
        method=args.method, with_pred=args.with_pred,
        semiring=args.semiring, seed=args.seed,
    )

if __name__ == "__main__":
    sys.exit(main())
