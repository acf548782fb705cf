"""Resilience suite: validation boundary, slot lifecycle, chaos recovery.

Covers the PR 7 serving-tier contract end to end at small N (CPU-fast,
runs under ``make test-fast``): typed input validation at ``solve`` /
``solve_batch`` / ``DynamicAPSP``, negative-cycle detection on the solved
diagonal, the fault-spec grammar and the injector's seeded determinism,
the slot lifecycle under injected crashes / NaN updates / state poison,
bounded-staleness snapshot answers (every degraded answer tagged), LRU
eviction + deterministic re-admission, deadline misses, backlog shedding,
and drift detection with re-solve-on-drift.
"""

import numpy as np
import pytest

from repro.core import (
    DynamicAPSP,
    InputValidationError,
    NegativeCycleError,
    UpdateError,
    domain_violations,
    solve,
    solve_batch,
)
from repro.core.graphgen import generate_edge_updates, generate_np
from repro.launch.faults import FaultInjector, FaultSpec, InjectedCrash
from repro.launch.pool import EnginePool, EngineSlot, SlotState

pytestmark = pytest.mark.resilience


def graph(n=16, seed=0):
    return generate_np(np.random.default_rng(seed), n, rho=60.0).h


# ---------------------------------------------------------------------------
# satellite 1: typed validation at the solve boundary
# ---------------------------------------------------------------------------

def test_solve_rejects_nan_input():
    h = graph()
    h[2, 3] = np.nan
    with pytest.raises(InputValidationError, match=r"NaN.*\(2, 3\)"):
        solve(h)


def test_solve_validate_false_escape_hatch():
    h = graph()
    h[2, 3] = np.nan
    r = solve(h, validate=False)          # caller owns the consequences
    assert np.isnan(np.asarray(r.dist)).any()


def test_solve_detects_negative_cycle():
    h = graph(8)
    h[1, 2], h[2, 1] = -5.0, 2.0          # closed walk of weight -3
    with pytest.raises(NegativeCycleError, match="negative cycle"):
        solve(h)
    r = solve(h, validate=False)          # diagnostic access still possible
    assert float(np.asarray(r.dist)[1, 1]) < 0


def test_negative_edges_without_cycle_pass():
    h = np.full((4, 4), np.inf, np.float32)
    np.fill_diagonal(h, 0.0)
    h[0, 1], h[1, 2], h[2, 3] = -1.0, -2.0, 4.0   # DAG: no cycle at all
    d = np.asarray(solve(h).dist)
    assert d[0, 3] == pytest.approx(1.0)


def test_solve_batch_rejects_nan_stack():
    hs = np.stack([graph(8, s) for s in range(3)])
    hs[1, 4, 5] = np.nan
    with pytest.raises(InputValidationError, match=r"\(1, 4, 5\)"):
        solve_batch(hs)


def test_solve_batch_negative_cycle_in_one_graph():
    hs = [graph(8, s) for s in range(3)]
    hs[2][1, 2], hs[2][2, 1] = -5.0, 2.0
    with pytest.raises(NegativeCycleError):
        solve_batch(hs)
    r = solve_batch(hs, validate=False)
    assert np.asarray(r.dist).shape[0] == 3


def test_dynamic_ctor_validates():
    h = graph()
    h[0, 5] = np.nan
    with pytest.raises(InputValidationError):
        DynamicAPSP(h)
    DynamicAPSP(h, validate=False)        # escape hatch reaches the engine


# ---------------------------------------------------------------------------
# satellite 3: DynamicAPSP failure paths
# ---------------------------------------------------------------------------

def test_update_rejects_nan_batch_state_unchanged():
    eng = DynamicAPSP(graph())
    before = np.asarray(eng.dist).copy()
    v0 = eng.version
    with pytest.raises(UpdateError, match="outside the 'tropical' domain"):
        eng.update([(0, 1, 1.0), (2, 3, np.nan)])
    np.testing.assert_array_equal(np.asarray(eng.dist), before)
    assert eng.version == v0


def test_update_rejects_out_of_domain_weight():
    with pytest.raises(UpdateError, match="domain"):
        DynamicAPSP(graph()).update([(0, 1, -2.0)])
    # ...but the semiring zero (= delete edge) is always legal
    eng = DynamicAPSP(graph())
    eng.update([(0, 1, np.inf)])


def test_update_validate_false_accepts_nan():
    # the escape hatch admits the garbage weight (it lands in the cost
    # matrix); NaN compares false under the semiring order so the closure
    # itself treats it as a no-op rather than crashing
    eng = DynamicAPSP(graph(), validate=False)
    info = eng.update([(0, 1, np.nan)])
    assert info["path"] == "noop"
    assert np.isnan(eng.h[0, 1])


def test_resolve_threshold_zero_always_full_resolves():
    h = graph(12, seed=3)
    eng = DynamicAPSP(h, resolve_threshold=0.0)
    # a worsening at threshold 0 must take the full-solve path, and the
    # result must still match a cold solve exactly
    rng = np.random.default_rng(1)
    for _ in range(3):
        u, v, w = generate_edge_updates(rng, eng.h, 4, worsen_frac=1.0)
        eng.update(u, v, w)
    ref = solve(eng.h)
    np.testing.assert_allclose(
        np.asarray(eng.dist), np.asarray(ref.dist), rtol=1e-5, atol=1e-5)
    assert eng.stats["full_resolve"] >= 1


# ---------------------------------------------------------------------------
# fault-spec grammar + injector determinism
# ---------------------------------------------------------------------------

def test_fault_spec_parse_roundtrip():
    s = FaultSpec.parse("nan:0.1,crash:0.2:3,latency:0.3:25,mem:0.05:0.25")
    assert s.nan == 0.1 and s.crash == 0.2 and s.crash_count == 3
    assert s.latency == 0.3 and s.latency_ms == 25.0
    assert s.mem == 0.05 and s.mem_frac == 0.25
    assert s.any() and not FaultSpec.parse("").any()
    assert not FaultSpec.parse(None).any()


@pytest.mark.parametrize("bad", [
    "nan",                 # missing rate
    "explode:0.5",         # unknown kind
    "nan:1.5",             # rate out of range
    "nan:0.1:7",           # nan takes no parameter
    "crash:0.1:2:9",       # too many fields
    "latency:abc",         # non-numeric rate
])
def test_fault_spec_parse_rejects(bad):
    with pytest.raises(ValueError):
        FaultSpec.parse(bad)


def test_injector_deterministic_and_streams_independent():
    spec = FaultSpec.parse("nan:0.3,latency:0.4:0")

    def trace(s):
        inj = FaultInjector(s, seed=7)
        return [
            (inj.corrupt_update(np.ones(4, np.float32))[1],
             inj.maybe_latency() > 0)
            for _ in range(50)
        ]

    assert trace(spec) == trace(spec)     # same spec + seed => same schedule
    # turning a kind off must not shift the other kind's stream
    nan_only = [a for a, _ in trace(FaultSpec.parse("nan:0.3"))]
    assert nan_only == [a for a, _ in trace(spec)]


def test_injector_sticky_crash_count():
    inj = FaultInjector(FaultSpec(crash=1.0, crash_count=3), seed=0)
    for _ in range(3):
        with pytest.raises(InjectedCrash):
            inj.maybe_crash()
    assert inj.counts["crash"] == 1       # one injection, three raises


# ---------------------------------------------------------------------------
# slot lifecycle under faults
# ---------------------------------------------------------------------------

def make_pool(n=16, graphs=1, seed=0, **kw):
    pool = EnginePool(method="blocked_fw", solve_kw={"block_size": 8},
                      seed=seed, **kw)
    for gid in range(graphs):
        pool.admit(gid, graph(n, seed + gid))
    return pool


def test_crash_beyond_retries_quarantines_then_recovers():
    # a burst of 4 consecutive crashes: exhausts the retry budget (2),
    # quarantines, recovers, and the post-recovery retry applies cleanly
    inj = FaultInjector(FaultSpec(), seed=0)
    inj._pending_crashes = 4
    pool = make_pool(max_retries=2, injector=inj)
    slot = pool.slots[0]
    pool.submit_update(0, [0], [1], [0.5])
    infos = pool.drain(0)
    assert infos[0].get("path") != "failed"
    assert slot.stats["quarantines"] == 1
    assert slot.stats["retries"] == 4
    assert slot.state == SlotState.HEALTHY          # recovered in-line
    trans = [(e["from"], e["to"]) for e in pool.events if "from" in e]
    assert (SlotState.HEALTHY, SlotState.QUARANTINED) in trans
    assert any("recovery_s" in e for e in pool.events)
    # the recovered state actually contains the update
    assert float(slot.engine.h[0, 1]) == 0.5
    ref = solve(slot.engine.h, method="blocked_fw", block_size=8)
    np.testing.assert_allclose(
        np.asarray(slot.engine.dist), np.asarray(ref.dist), rtol=1e-5, atol=1e-5)


def test_persistent_crash_stays_quarantined_and_requeues():
    # crash rate 1.0 never clears: the slot must give up after one
    # recovery cycle (no infinite retry loop), requeue the batch, and keep
    # serving snapshot answers until the fault clears
    inj = FaultInjector(FaultSpec(crash=1.0), seed=0)
    pool = make_pool(max_retries=1, injector=inj)
    slot = pool.slots[0]
    pool.submit_update(0, [0], [1], [0.5])
    infos = pool.drain(0)
    assert infos[0]["path"] == "failed"
    assert slot.state == SlotState.QUARANTINED
    assert len(slot.pending) == 1                   # requeued, not lost
    assert pool.stats["updates_failed"] == 1
    r = pool.query(0, np.array([0]), np.array([1]))
    assert r.source == "snapshot" and r.staleness >= 1
    # fault clears -> the requeued batch applies and the slot heals
    inj.spec = FaultSpec()
    pool.drain(0)
    assert slot.state == SlotState.HEALTHY and not slot.pending
    assert float(slot.engine.h[0, 1]) == 0.5


def _refuse_lowering(*_a, **_k):
    raise NotImplementedError("Unimplemented primitive in lowering")


def _refuse_compile(*_a, **_k):
    # a real XLA compile failure: a custom call with no registered handler
    import jax
    import jax.numpy as jnp

    x = jnp.ones(3)
    jax.jit(lambda x: jax.ffi.ffi_call(
        "repro_no_such_kernel", jax.ShapeDtypeStruct(x.shape, x.dtype))(x))(x)


@pytest.mark.parametrize("refuse", [_refuse_lowering, _refuse_compile],
                         ids=["lowering", "compile"])
@pytest.mark.parametrize("batches", [1, 2])
def test_lowering_failure_propagates_without_retry(monkeypatch, batches, refuse):
    # a kernel the backend cannot lower or compile fails identically on
    # every attempt: it must surface to the caller, not be retried,
    # quarantined and papered over with snapshot answers (single and
    # coalesced drains) — and the batches stay queued, so staleness is exact
    pool = make_pool(max_retries=3)
    slot = pool.slots[0]
    monkeypatch.setattr(slot.engine, "update", refuse)
    for b in range(batches):
        pool.submit_update(0, [0], [1 + b], [0.5])
    with pytest.raises(RuntimeError):
        pool.drain(0)
    assert slot.stats["retries"] == 0
    assert slot.stats["quarantines"] == 0
    assert pool.stats["updates_failed"] == 0
    assert slot.state == SlotState.HEALTHY
    assert len(slot.pending) == batches and slot._inflight == 0
    assert slot.staleness() == batches
    with pytest.raises(RuntimeError):                # no answer hides it
        pool.query(0, np.array([0]), np.array([1]))
    assert len(slot.pending) == batches


def test_execution_runtime_error_stays_transient(monkeypatch):
    # an XLA error raised while *running* (e.g. an exhausted allocator) is
    # not a compile failure: it keeps bounded retry + quarantine
    import jax

    pool = make_pool(max_retries=1)
    slot = pool.slots[0]

    def oom(*_a, **_k):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(slot.engine, "update", oom)
    monkeypatch.setattr(slot, "recover", lambda: False)
    pool.submit_update(0, [0], [1], [0.5])
    infos = pool.drain(0)
    assert infos[0]["path"] == "failed"
    assert slot.stats["retries"] == 2 and slot.stats["quarantines"] == 1
    assert len(slot.pending) == 1


def test_compile_failure_requeues_batched_drain(monkeypatch):
    # the stacked cross-graph drain: a program that fails to compile puts
    # every popped batch back before the error surfaces
    import repro.launch.pool as pool_mod

    pool = make_pool(graphs=2)
    monkeypatch.setattr(pool_mod, "apply_updates_batched",
                        lambda *_a, **_k: _refuse_compile())
    for gid in range(2):
        pool.submit_update(gid, [0], [1], [0.5])
    with pytest.raises(RuntimeError):
        pool.drain_all()
    for gid in range(2):
        slot = pool.slots[gid]
        assert len(slot.pending) == 1 and slot._inflight == 0
        assert slot.staleness() == 1 and slot.state == SlotState.HEALTHY


def test_compile_failure_reaches_async_caller(monkeypatch):
    # async pools: the worker neither swallows the failure nor spins on the
    # slot; the next flush re-raises it and the batch stays queued
    pool = make_pool(async_updates=True)
    try:
        slot = pool.slots[0]
        monkeypatch.setattr(slot.engine, "update", _refuse_compile)
        pool.submit_update(0, [0], [1], [0.5])
        with pytest.raises(RuntimeError):
            pool.flush(timeout=60.0)
        assert pool.executor.flush(timeout=60.0)    # raised once, no spin
        assert pool.executor.stats["drain_errors"] == 1
        assert pool.executor.stats["requeues"] == 0
        assert len(slot.pending) == 1 and slot.staleness() == 1
        assert slot.stats["retries"] == 0 and slot.state == SlotState.HEALTHY
        r = pool.query(0, np.array([0]), np.array([1]))
        assert r.source == "snapshot" and r.staleness == 1
        with pytest.raises(RuntimeError):           # persistent: every barrier
            pool.flush(timeout=60.0)
    finally:
        pool.close()


def test_injected_nan_update_rejected_slot_stays_healthy():
    inj = FaultInjector(FaultSpec(nan=1.0), seed=0)
    pool = make_pool(injector=inj)
    pool.submit_update(0, [0], [1], [0.5])
    infos = pool.drain(0)
    assert infos[0]["path"] == "rejected"
    assert pool.slots[0].state == SlotState.HEALTHY
    assert pool.stats["updates_rejected"] == 1
    assert not bool(domain_violations(
        np.asarray(pool.slots[0].engine.dist), "tropical").any())


def test_poisoned_state_probed_degraded_and_recovered():
    inj = FaultInjector(FaultSpec(poison=1.0), seed=0)
    pool = make_pool(injector=inj)
    slot = pool.slots[0]
    pool.submit_update(0, [0], [1], [0.5])
    pool.drain(0)
    # the probe caught the injected NaN, degraded, and recover() re-solved
    assert slot.stats["probe_failures"] >= 1
    assert slot.state == SlotState.HEALTHY
    assert not np.isnan(np.asarray(slot.engine.dist)).any()
    trans = [(e["from"], e["to"]) for e in pool.events if "from" in e]
    assert (SlotState.HEALTHY, SlotState.DEGRADED) in trans


def test_query_blocks_poison_and_serves_snapshot():
    pool = make_pool()
    slot = pool.slots[0]
    # poison the live state directly, past the update-path probes
    slot.engine._dist = slot.engine._dist.at[0, 5].set(np.nan)
    r = pool.query(0, np.array([0]), np.array([5]))
    assert r.source == "snapshot" and not np.isnan(r.values).any()
    assert pool.stats["poison_blocked"] == 1
    assert pool.stats["poisoned_served"] == 0
    assert slot.state == SlotState.HEALTHY          # recovered after blocking


def test_query_against_quarantined_slot_uses_snapshot_with_staleness():
    pool = make_pool()
    slot = pool.slots[0]
    slot._transition(SlotState.QUARANTINED, "forced by test")
    pool.submit_update(0, [0], [1], [0.5])          # pending => stale by 1+
    r = pool.query(0, np.array([1]), np.array([2]))
    # drain readmits/recovers; but a *forced* quarantine without recovery
    # path must never have served live values silently — the answer is
    # either a tagged snapshot or a healthy live read
    assert r.source in ("live", "snapshot")
    if r.source == "snapshot":
        assert r.staleness >= 1 and r.slot_state != SlotState.HEALTHY


def test_snapshot_staleness_counts_versions_behind():
    pool = make_pool()
    slot = pool.slots[0]
    v0 = slot.snapshot["version"]
    slot.engine.update([(0, 1, 0.25)])              # behind by one version
    slot.engine.update([(1, 2, 0.25)])              # ...two
    assert slot.engine.version == v0 + 2
    assert slot.staleness() == 2
    slot._commit_snapshot()
    assert slot.staleness() == 0


def test_deadline_miss_falls_back_to_snapshot():
    inj = FaultInjector(FaultSpec(latency=1.0, latency_ms=80.0), seed=0)
    pool = make_pool(injector=inj, deadline_s=0.01)
    r = pool.query(0, np.array([0]), np.array([1]))
    assert r.deadline_missed and r.source == "snapshot"
    assert pool.stats["deadline_misses"] == 1
    pool.close()


def test_backlog_watermark_sheds_to_snapshot():
    pool = make_pool(backlog_watermark=0)
    pool.submit_update(0, [0], [1], [0.5])
    r = pool.query(0, np.array([2]), np.array([3]))
    assert r.shed and r.source == "snapshot" and r.staleness >= 1
    assert pool.stats["queries_shed"] == 1
    # after draining, queries go live again
    pool.drain_all()
    assert pool.query(0, np.array([2]), np.array([3])).source == "live"


# ---------------------------------------------------------------------------
# memory budget: LRU eviction + deterministic re-admission
# ---------------------------------------------------------------------------

def test_lru_eviction_and_deterministic_readmission():
    n = 16
    per = n * n * 4
    pool = make_pool(n=n, graphs=1, mem_budget_bytes=per)  # exactly one engine
    pool.admit(1, graph(n, 1))
    s0, s1 = pool.slots[0], pool.slots[1]
    assert s0.state == SlotState.EVICTED and s0.engine is None
    assert s1.state == SlotState.HEALTHY
    # evicted slot still answers (stale, tagged)
    r = pool.query(0, np.array([0]), np.array([1]))
    assert r.source == "snapshot" and r.slot_state == SlotState.EVICTED
    # re-admission rebuilds from the retained cost matrix and replays the
    # queue: state must equal a cold solve of the same mutated matrix
    pool.submit_update(0, [2], [3], [0.125])
    pool.drain(0)
    assert s0.engine is not None
    assert s0.stats["readmissions"] == 1
    assert s1.state == SlotState.EVICTED            # LRU swapped the victim
    ref = solve(s0.engine.h, method="blocked_fw", block_size=8)
    np.testing.assert_allclose(
        np.asarray(s0.engine.dist), np.asarray(ref.dist), rtol=1e-5, atol=1e-5)
    assert s0.engine.version > 0                    # versions stay monotone


def test_versions_monotone_across_eviction():
    pool = make_pool()
    slot = pool.slots[0]
    slot.engine.update([(0, 1, 0.5)])
    v = slot.engine.version
    slot.evict()
    slot.readmit()
    assert slot.engine.version > v


# ---------------------------------------------------------------------------
# drift detection (verify) + coalescing
# ---------------------------------------------------------------------------

def test_verify_detects_drift_and_resolves():
    pool = make_pool()
    slot = pool.slots[0]
    # corrupt the live state without NaN so probes can't see it — only the
    # differential cold-solve compare can
    slot.engine._dist = slot.engine._dist + 7.0
    report = pool.verify(0)
    assert not report["ok"] and report["recovered"]
    assert pool.stats["verify_drift"] == 1
    assert slot.stats["drift_detected"] == 1
    assert slot.state == SlotState.HEALTHY


def test_drain_coalesces_batches_last_wins():
    pool = make_pool()
    slot = pool.slots[0]
    pool.submit_update(0, [0], [1], [0.75])
    pool.submit_update(0, [0], [1], [0.25])         # same edge, later wins
    infos = pool.drain(0)
    assert len(infos) == 1                          # one coalesced dispatch
    assert pool.stats["drain_coalesced"] == 1
    assert float(slot.engine.h[0, 1]) == 0.25


def test_drain_per_batch_fallback_keeps_clean_batches():
    pool = make_pool()
    pool.submit_update(0, [0], [1], [np.nan])       # poisoned batch
    pool.submit_update(0, [1], [2], [0.5])          # clean batch
    infos = pool.drain(0)
    assert pool.stats["drain_fallbacks"] == 1
    assert [i["path"] == "rejected" for i in infos] == [True, False]
    assert float(pool.slots[0].engine.h[1, 2]) == 0.5


# ---------------------------------------------------------------------------
# update atomicity under retry + batched drains
# ---------------------------------------------------------------------------

def test_update_atomic_under_midflight_crash_retry(monkeypatch):
    """Regression (tentpole satellite): a crash *after* the engine has
    started applying a batch must not lose the batch on retry.

    The old ordering wrote ``h[u, v] = w`` before dispatching, so a retry
    re-read ``old`` from the already-mutated matrix, classified the batch
    as a no-op, and silently dropped the update — the engine then served
    the stale closure forever.  With the atomic ordering (h rolls back on
    any dispatch failure) the retried batch re-applies for real."""
    import repro.core.dynamic as dyn

    pool = make_pool()
    slot = pool.slots[0]
    real = dyn._rank_k_fixpoint_donate
    fired = {"n": 0}

    def crash_once(*args, **kwargs):
        if fired["n"] == 0:
            fired["n"] += 1
            raise RuntimeError("injected mid-update crash")
        return real(*args, **kwargs)

    monkeypatch.setattr(dyn, "_rank_k_fixpoint_donate", crash_once)
    info = slot.apply_update(
        np.array([0], np.int32), np.array([1], np.int32),
        np.array([0.5], np.float32))
    assert fired["n"] == 1                      # the crash actually fired
    assert slot.stats["retries"] == 1
    assert info["path"] == "rank_k"             # retry re-applied, not noop
    assert float(slot.engine.h[0, 1]) == 0.5
    ref = solve(slot.engine.h, method="blocked_fw", block_size=8)
    np.testing.assert_array_equal(
        np.asarray(slot.engine.dist), np.asarray(ref.dist))


def test_update_state_unchanged_when_dispatch_raises(monkeypatch):
    """The engine-level half of atomicity: if the jitted dispatch raises,
    ``h`` must roll back so the engine still matches its own closure."""
    import repro.core.dynamic as dyn

    eng = DynamicAPSP(graph(), block_size=8)
    h_before = eng.h.copy()

    def boom(*args, **kwargs):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(dyn, "_rank_k_fixpoint_donate", boom)
    monkeypatch.setattr(dyn, "_rank_k_fixpoint", boom)
    with pytest.raises(RuntimeError, match="injected"):
        eng.update([(0, 1, 0.5)])
    np.testing.assert_array_equal(eng.h, h_before)
    ref = solve(eng.h, block_size=8)
    np.testing.assert_array_equal(np.asarray(eng.dist), np.asarray(ref.dist))


def test_health_probe_bf16_tolerance():
    """Satellite: the probe tolerance must scale with the state dtype — a
    healthy bf16 engine (~2³ ulp ≈ 2-3% triangle slack) must not be
    quarantined by the f32 tolerance."""
    import jax.numpy as jnp

    eng = DynamicAPSP(graph(24, seed=5), block_size=8, dtype=jnp.bfloat16)
    assert eng.dist.dtype == jnp.bfloat16
    probe = eng.health_probe(256, np.random.default_rng(0))
    assert probe["ok"], probe
    eng.update([(0, 1, 0.25)])
    probe = eng.health_probe(256, np.random.default_rng(1))
    assert probe["ok"], probe


def test_drain_all_batches_same_shape_slots():
    """Tentpole rider: drain_all coalesces same-shape healthy slots into
    one stacked rank-k dispatch and the result matches per-slot drains."""
    pool = make_pool(n=16, graphs=3)
    rng = np.random.default_rng(7)
    expect = {}
    for gid in range(3):
        h = pool.slots[gid].engine.h
        u, v, w = generate_edge_updates(rng, h, 4)
        h2 = np.array(h)
        h2[u, v] = np.minimum(h2[u, v], w)
        expect[gid] = h2
        pool.submit_update(gid, u, v, w)
    pool.drain_all()
    assert pool.stats["drain_batched"] == 1
    for gid in range(3):
        slot = pool.slots[gid]
        assert slot.state == SlotState.HEALTHY and not slot.pending
        assert slot.stats["updates_applied"] == 1
        ref = solve(expect[gid], method="blocked_fw", block_size=8)
        np.testing.assert_array_equal(
            np.asarray(slot.engine.dist), np.asarray(ref.dist))


def test_drain_all_batched_defers_worsenings_to_sequential():
    """A slot whose coalesced batch contains a worsening is deferred by
    the batcher and handled by its own sequential drain — same final
    state, batched dispatch still fires for the clean slots."""
    pool = make_pool(n=16, graphs=3)
    rng = np.random.default_rng(11)
    for gid in range(3):
        h = pool.slots[gid].engine.h
        u, v, w = generate_edge_updates(rng, h, 4)
        if gid == 0:                         # worsen an existing edge
            fin = np.argwhere(np.isfinite(h) & (h > 0))
            i, j = fin[0]
            u, v = np.array([i], np.int32), np.array([j], np.int32)
            w = np.array([float(h[i, j]) + 100.0], np.float32)
        pool.submit_update(gid, u, v, w)
    pool.drain_all()
    assert pool.stats["drain_batched"] == 1
    for gid in range(3):
        slot = pool.slots[gid]
        assert slot.state == SlotState.HEALTHY and not slot.pending
        ref = solve(slot.engine.h, method="blocked_fw", block_size=8)
        np.testing.assert_array_equal(
            np.asarray(slot.engine.dist), np.asarray(ref.dist))


def test_drain_all_under_chaos_skips_batched_path():
    """Fault injection must keep flowing through the per-slot apply stack:
    with any chaos configured the batched fast path is disabled."""
    inj = FaultInjector(FaultSpec(nan=0.0, crash=0.5, crash_count=1), seed=3)
    pool = make_pool(n=16, graphs=2, injector=inj, max_retries=3)
    for gid in range(2):
        pool.submit_update(gid, [0], [1], [0.5])
    pool.drain_all()
    assert pool.stats["drain_batched"] == 0


# ---------------------------------------------------------------------------
# end-to-end: chaos serving run keeps the contract
# ---------------------------------------------------------------------------

def test_chaos_run_zero_poison_and_full_recovery():
    inj = FaultInjector(
        FaultSpec.parse("nan:0.2,crash:0.15:3,poison:0.15,latency:0.1:5"),
        seed=42,
    )
    pool = make_pool(n=16, graphs=2, injector=inj, deadline_s=0.2,
                     backlog_watermark=3, seed=42)
    rng = np.random.default_rng(42)
    for _ in range(60):
        gid = int(rng.integers(0, 2))
        if rng.uniform() < 0.5:
            slot = pool.slots[gid]
            h = slot.engine.h if slot.engine is not None else slot._h
            u, v, w = generate_edge_updates(rng, h, 3)
            pool.submit_update(gid, u, v, w)
            if pool.backlog() > pool.backlog_watermark:
                pool.drain_all()
        else:
            r = pool.query(gid, rng.integers(0, 16, 4), rng.integers(0, 16, 4))
            assert not bool(domain_violations(r.values, "tropical").any())
            if r.source == "snapshot":
                assert r.staleness >= 0 and r.slot_state in SlotState.ALL
    pool.recover_all(readmit=True)
    summary = pool.summary()
    assert summary["pool"]["poisoned_served"] == 0
    assert summary["states"][SlotState.DEGRADED] == 0
    assert summary["states"][SlotState.QUARANTINED] == 0
    assert sum(inj.counts.values()) > 0             # chaos actually fired
    for gid in (0, 1):
        assert pool.verify(gid)["ok"]
    pool.close()


def test_serve_apsp_dynamic_chaos_smoke_exit_zero():
    from repro.launch.serve import serve_apsp_dynamic

    rc = serve_apsp_dynamic(
        24, n_max=16, graphs=2, mutate_rate=0.5, mutate_k=3,
        verify_every=8, seed=3,
        fault_spec="nan:0.2,crash:0.1:3,poison:0.1",
        deadline_ms=200.0, backlog_watermark=3,
    )
    assert rc == 0
