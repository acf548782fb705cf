"""Serving tier v2 suite: async executor, durability, correlated chaos.

Covers the PR 10 contract at small N (CPU-fast, runs with the resilience
suite under ``make test-fast``): the update journal's durability semantics
(torn tails, truncation, replay), engine checkpoints round-tripping
bit-exactly, crash + restore converging to the uncrashed twin, correlated
fault kinds (whole-backend loss, cache storm, crash-restore drill) and
their seeded determinism, queries racing an in-flight background drain
(every answer current-version exact or correctly staleness-tagged — no
torn reads), the per-slot deadline readers, and the locked stats counters
under threaded contention.
"""

import os
import threading

import numpy as np
import pytest

from oracle import generate
from repro.core import DynamicAPSP
from repro.core.dynamic import UpdateJournal
from repro.core.graphgen import generate_np
from repro.checkpoint import load_engine_checkpoint, save_engine_checkpoint
from repro.launch.faults import FaultInjector, FaultSpec, InjectedCrash
from repro.launch.pool import EnginePool, SlotState
from repro.launch.stats import Counters

pytestmark = pytest.mark.resilience


def graph(n=16, seed=0):
    return generate_np(np.random.default_rng(seed), n, rho=60.0).h


def updates(n, count, seed, lo=0.5, hi=8.0):
    """``count`` random non-self-loop edge updates as (u, v, w) arrays."""
    r = np.random.default_rng(seed)
    u = r.integers(0, n, count)
    v = r.integers(0, n, count)
    v = np.where(v == u, (v + 1) % n, v)
    w = r.uniform(lo, hi, count).astype(np.float32)
    return u.astype(np.int32), v.astype(np.int32), w


# ---------------------------------------------------------------------------
# update journal
# ---------------------------------------------------------------------------

def test_journal_append_records_roundtrip(tmp_path):
    j = UpdateJournal(str(tmp_path / "g.wal"))
    assert len(j) == 0
    j.append([0], [1], [2.0], version_before=0)
    j.append([3, 4], [5, 6], [1.0, 7.0], version_before=1)
    recs = j.records()
    assert [r["seq"] for r in recs] == [0, 1]
    assert [r["v0"] for r in recs] == [0, 1]
    assert recs[1]["u"] == [3, 4] and recs[1]["w"] == [1.0, 7.0]
    # records() filters by v0, not seq
    assert [r["seq"] for r in j.records(min_version=1)] == [1]
    j.close()
    # a reopened journal resumes the seq counter past what's on disk
    j2 = UpdateJournal(str(tmp_path / "g.wal"))
    assert j2.append([7], [8], [3.0], version_before=2) == 2
    j2.close()


def test_journal_ignores_torn_tail(tmp_path):
    path = str(tmp_path / "g.wal")
    j = UpdateJournal(path)
    j.append([0], [1], [2.0], version_before=0)
    j.append([2], [3], [4.0], version_before=1)
    j.close()
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"seq": 2, "v0": 2, "u": [5')    # crash mid-append
    j2 = UpdateJournal(path)
    # the torn record was never acked: invisible, and its seq is reused
    assert [r["seq"] for r in j2.records()] == [0, 1]
    assert j2.append([5], [6], [1.0], version_before=2) == 2
    j2.close()


def test_journal_truncate_and_clear(tmp_path):
    j = UpdateJournal(str(tmp_path / "g.wal"))
    for k in range(5):
        j.append([k], [k + 1], [1.0], version_before=k)
    assert j.truncate(3) == 3                      # v0 in {0,1,2} dropped
    assert [r["v0"] for r in j.records()] == [3, 4]
    j.clear()
    assert len(j) == 0
    j.close()


def test_engine_journals_every_committed_update(tmp_path):
    n = 12
    h = graph(n)
    j = UpdateJournal(str(tmp_path / "g.wal"))
    eng = DynamicAPSP(h, journal=j)
    u, v, w = updates(n, 6, seed=1)
    for k in range(6):
        eng.update([int(u[k])], [int(v[k])], [float(w[k])])
    # replay the journal onto a twin built from the same initial costs:
    # bit-exact state and matching version
    twin = DynamicAPSP(h)
    replayed = j.replay_onto(twin)
    assert replayed == len(j.records())
    assert twin.version == eng.version
    np.testing.assert_array_equal(np.asarray(twin.dist), np.asarray(eng.dist))
    np.testing.assert_array_equal(twin.h, eng.h)
    j.close()


def test_journal_rejected_batch_never_journaled(tmp_path):
    j = UpdateJournal(str(tmp_path / "g.wal"))
    eng = DynamicAPSP(graph(12), journal=j)
    with pytest.raises(Exception):
        eng.update([(0, 1, np.nan)])
    assert len(j) == 0                             # validation ran first
    eng.update([(0, 1, 1.5)])
    assert len(j) >= 1
    j.close()


# ---------------------------------------------------------------------------
# engine checkpoints
# ---------------------------------------------------------------------------

# update weights in each semiring's domain, from tropical costs in [0.5, 8)
DOMAIN_WEIGHTS = {
    "tropical": lambda w: w,
    "bottleneck": lambda w: w,
    "reliability": lambda w: (1.0 / (1.0 + w)).astype(np.float32),
    "boolean": np.ones_like,
}


@pytest.mark.parametrize("with_pred", [False, True])
@pytest.mark.parametrize("semiring", sorted(DOMAIN_WEIGHTS))
def test_engine_checkpoint_roundtrip_bit_exact(tmp_path, semiring, with_pred):
    n = 12
    h = (graph(n) if semiring == "tropical"
         else generate(np.random.default_rng(0), n, semiring))
    eng = DynamicAPSP(h, with_pred=with_pred, semiring=semiring)
    u, v, w = updates(n, 4, seed=2)
    eng.update(u, v, DOMAIN_WEIGHTS[semiring](w))
    save_engine_checkpoint(str(tmp_path), eng)
    st = load_engine_checkpoint(str(tmp_path))
    assert st["version"] == eng.version
    assert st["n"] == n and st["with_pred"] is with_pred
    assert st["semiring"] == semiring
    np.testing.assert_array_equal(st["dist"], np.asarray(eng.dist))
    np.testing.assert_array_equal(st["h"], eng.h)
    if with_pred:
        np.testing.assert_array_equal(st["pred"], np.asarray(eng.pred))
    # the loaded state boots an engine with no cold solve, bit-identical
    twin = DynamicAPSP(st["h"], with_pred=with_pred, semiring=st["semiring"],
                       state=st)
    assert twin.version == eng.version
    np.testing.assert_array_equal(np.asarray(twin.dist), np.asarray(eng.dist))
    if with_pred:
        np.testing.assert_array_equal(np.asarray(twin.pred),
                                      np.asarray(eng.pred))


def test_engine_checkpoint_roundtrip_bfloat16(tmp_path):
    jnp = pytest.importorskip("jax.numpy")
    eng = DynamicAPSP(graph(12), dtype=jnp.bfloat16)
    save_engine_checkpoint(str(tmp_path), eng)
    st = load_engine_checkpoint(str(tmp_path))
    assert st["state_dtype"] == "bfloat16"
    a, b = st["dist"], np.asarray(eng.dist)
    assert str(a.dtype) == "bfloat16"
    np.testing.assert_array_equal(
        a.view(np.uint16), b.view(np.uint16))     # bit view: exact round-trip


# ---------------------------------------------------------------------------
# crash + restore
# ---------------------------------------------------------------------------

def make_pool(n=16, graphs=1, seed=0, **kw):
    pool = EnginePool(method="blocked_fw", solve_kw={"block_size": 8},
                      seed=seed, **kw)
    for gid in range(graphs):
        pool.admit(gid, graph(n, seed + gid))
    return pool


def test_crash_restore_bit_exact_vs_uncrashed_twin(tmp_path):
    n = 16
    pool = make_pool(n, durability_dir=str(tmp_path), checkpoint_every=2)
    twin = DynamicAPSP(graph(n), method="blocked_fw", block_size=8)
    u, v, w = updates(n, 9, seed=3)
    for k in range(9):                             # odd count: head past the last checkpoint
        pool.submit_update(0, [int(u[k])], [int(v[k])], [float(w[k])])
        pool.drain(0)
        twin.update([int(u[k])], [int(v[k])], [float(w[k])])
    slot = pool.slots[0]
    assert slot.stats["checkpoints"] >= 2          # periodic checkpointing ran
    live = np.asarray(slot.engine.dist).copy()
    v_live = slot.engine.version
    slot.crash()
    assert slot.engine is None and slot.snapshot is None
    assert slot.state == SlotState.QUARANTINED
    assert slot.restore()
    assert slot.state == SlotState.HEALTHY
    # bit-exact against both the pre-crash state and the never-crashed twin
    assert slot.engine.version == v_live == twin.version
    np.testing.assert_array_equal(np.asarray(slot.engine.dist), live)
    np.testing.assert_array_equal(
        np.asarray(slot.engine.dist), np.asarray(twin.dist))
    np.testing.assert_array_equal(slot.engine.h, twin.h)
    assert slot.stats["restores"] == 1
    assert slot.stats["replayed_records"] >= 1     # journal past the checkpoint replayed
    pool.close()


def test_restore_without_checkpoint_cold_builds(tmp_path):
    pool = make_pool(12, durability_dir=str(tmp_path), checkpoint_every=0)
    slot = pool.slots[0]
    # drop the initial checkpoint so restore() has nothing durable to load
    import shutil
    shutil.rmtree(slot._ck_dir)
    slot.crash()
    assert slot.restore()
    assert slot.state == SlotState.HEALTHY
    assert slot.stats["cold_rebuilds"] == 1
    pool.close()


def test_crashed_slot_update_path_restores(tmp_path):
    # an update arriving at a crashed durable slot triggers restore, not a
    # cold readmit — and the update then applies on the restored state
    n = 12
    pool = make_pool(n, durability_dir=str(tmp_path))
    slot = pool.slots[0]
    slot.crash()
    pool.submit_update(0, [0], [1], [0.75])
    infos = pool.drain(0)
    assert infos and infos[0].get("path") != "failed"
    assert slot.state == SlotState.HEALTHY
    assert slot.stats["restores"] == 1
    assert float(slot.engine.h[0, 1]) == 0.75
    pool.close()


# ---------------------------------------------------------------------------
# correlated fault kinds
# ---------------------------------------------------------------------------

def test_fault_spec_parses_correlated_kinds():
    s = FaultSpec.parse("backend_loss:0.3:4,cache_storm:0.2:5,crash_restore:0.25")
    assert s.backend_loss == 0.3 and s.backend_count == 4
    assert s.cache_storm == 0.2 and s.storm_count == 5
    assert s.crash_restore == 0.25
    with pytest.raises(ValueError, match="no parameter"):
        FaultSpec.parse("crash_restore:0.5:2")


def test_backend_loss_window_fails_every_attempt():
    inj = FaultInjector(FaultSpec(backend_loss=1.0, backend_count=3), seed=0)
    inj.begin_drain()
    assert inj.backend_down()
    for _ in range(3):
        with pytest.raises(InjectedCrash, match="backend loss"):
            inj.maybe_crash()
    assert not inj.backend_down()
    inj.maybe_crash()                              # window drained: clean
    assert inj.counts["backend_denied"] == 3
    assert inj.counts["backend_loss"] == 1


def test_cache_storm_charges_recompile_penalty():
    inj = FaultInjector(
        FaultSpec(cache_storm=1.0, storm_count=2, latency_ms=1.0), seed=0)
    inj.begin_drain()
    assert inj.maybe_latency() > 0
    assert inj.maybe_latency() > 0
    assert inj.maybe_latency() == 0.0              # budget drained
    assert inj.counts["storm_recompiles"] == 2


def test_correlated_schedule_is_seed_deterministic():
    def run(seed):
        inj = FaultInjector(
            FaultSpec(backend_loss=0.4, cache_storm=0.4, crash_restore=0.4),
            seed=seed)
        out = []
        for _ in range(30):
            inj.begin_drain()
            out.append((inj.backend_down(), inj.maybe_crash_restore()))
            # drain any opened window so the next round starts clean
            while inj.backend_down():
                with pytest.raises(InjectedCrash):
                    inj.maybe_crash()
        return out, inj.counts.as_dict()

    a, ca = run(7)
    b, cb = run(7)
    c, _ = run(8)
    assert a == b and ca == cb
    assert a != c                                  # schedule is seed-driven


def test_backend_loss_quarantines_multiple_slots_then_pool_heals(tmp_path):
    # whole-backend loss mid-drain: with the window wider than the retry
    # budget, several slots quarantine together; recover_all heals the
    # whole pool and the queued batches land
    inj = FaultInjector(
        FaultSpec(backend_loss=1.0, backend_count=100), seed=0)
    pool = make_pool(12, graphs=2, max_retries=1, injector=inj,
                     durability_dir=str(tmp_path))
    for gid in range(2):
        pool.submit_update(gid, [0], [1], [0.5])
    pool.drain_all()
    assert all(s.state == SlotState.QUARANTINED for s in pool.slots.values())
    assert all(s.pending for s in pool.slots.values())   # batches requeued
    inj.spec = FaultSpec()                         # outage over (and no re-fire)
    inj._backend_left = 0
    pool.recover_all()
    for gid in range(2):
        slot = pool.slots[gid]
        assert slot.state == SlotState.HEALTHY
        assert float(slot.engine.h[0, 1]) == 0.5
        assert pool.verify(gid)["ok"]
    pool.close()


# ---------------------------------------------------------------------------
# background executor
# ---------------------------------------------------------------------------

def test_async_submit_is_enqueue_and_flush_applies(tmp_path):
    pool = make_pool(12, async_updates=True)
    pool.submit_update(0, [0], [1], [0.5])
    assert pool.flush(timeout=30.0)
    slot = pool.slots[0]
    assert float(slot.engine.h[0, 1]) == 0.5
    assert slot.pending == []
    assert pool.executor.backlog() == 0
    assert pool.executor.stats["drains"] >= 1
    assert pool.executor.stats["drain_errors"] == 0
    pool.close()


def test_executor_enqueue_dedups_and_stop_drops_queue():
    pool = make_pool(12, async_updates=True)
    ex = pool.executor
    # Condition's default lock is re-entrant: holding it keeps the workers
    # parked so the dedup decision is deterministic
    with ex._cond:
        assert ex.enqueue(0) is True
        assert ex.enqueue(0) is False              # already queued: coalesced
    assert ex.flush(timeout=30.0)
    ex.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        ex.enqueue(0)
    pool.close()


def test_async_drain_all_enqueues_backlog(tmp_path):
    pool = make_pool(12, graphs=2, async_updates=True)
    for gid in range(2):
        pool.submit_update(gid, [0], [1], [0.25])
    pool.drain_all()                               # returns immediately
    assert pool.flush(timeout=30.0)
    for gid in range(2):
        assert float(pool.slots[gid].engine.h[0, 1]) == 0.25
        assert pool.verify(gid)["ok"]
    pool.close()


# ---------------------------------------------------------------------------
# queries racing an in-flight background drain (no torn reads)
# ---------------------------------------------------------------------------

def test_async_queries_racing_drain_no_torn_reads(tmp_path):
    """Queries hammer a slot while background drains mutate it.  Every
    answer must be the bit-exact state of *some* committed version (no
    torn reads), tagged live only at staleness 0, and in-domain."""
    n = 16
    pool = make_pool(n, async_updates=True, durability_dir=str(tmp_path),
                     backlog_watermark=10_000)
    slot = pool.slots[0]
    h0 = slot._h.copy()
    u, v, w = updates(n, 40, seed=5)
    qi = np.arange(n, dtype=np.int64)
    qj = (qi + 3) % n

    answers = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            r = pool.query(0, qi, qj)
            answers.append((r.version, r.source, r.staleness,
                            np.asarray(r.values).copy()))

    t = threading.Thread(target=reader)
    t.start()
    try:
        for k in range(40):
            pool.submit_update(0, [int(u[k])], [int(v[k])], [float(w[k])])
        assert pool.flush(timeout=60.0)
    finally:
        stop.set()
        t.join(30.0)
    r = pool.query(0, qi, qj)                      # quiescent: live at the head
    answers.append((r.version, r.source, r.staleness,
                    np.asarray(r.values).copy()))

    # reconstruct the state at every committed version by journal replay
    dist_at = {}
    twin = DynamicAPSP(h0, method="blocked_fw", block_size=8)
    dist_at[twin.version] = np.asarray(twin.dist)[qi, qj].copy()
    for rec in slot.journal.records():
        twin.update(np.asarray(rec["u"], np.int32),
                    np.asarray(rec["v"], np.int32),
                    np.asarray(rec["w"], np.float32))
        dist_at[twin.version] = np.asarray(twin.dist)[qi, qj].copy()
    assert twin.version == slot.engine.version

    assert len(answers) > 0
    for version, source, staleness, values in answers:
        assert version in dist_at, f"answer at uncommitted version {version}"
        np.testing.assert_array_equal(values, dist_at[version])
        if source == "live":
            assert staleness == 0
    head = slot.engine.version
    assert answers[-1][0] == head and answers[-1][1] == "live"
    assert pool.stats["poisoned_served"] == 0
    pool.close()


def test_async_correlated_chaos_zero_poisoned(tmp_path):
    """The acceptance drill: async + durable pool under correlated chaos
    (backend loss, cache storms, crash-restore drills) with queries racing
    the drains — every slot ends healthy, zero poisoned answers, every
    answer staleness-tagged or current-version exact."""
    n = 12
    inj = FaultInjector(
        FaultSpec(backend_loss=0.25, backend_count=4,
                  cache_storm=0.25, storm_count=3, latency_ms=1.0,
                  crash_restore=0.3),
        seed=11)
    pool = make_pool(n, graphs=3, seed=1, injector=inj, max_retries=2,
                     async_updates=True, durability_dir=str(tmp_path),
                     checkpoint_every=2, backlog_watermark=10_000)
    u, v, w = updates(n, 30, seed=6)
    bad = 0
    for k in range(30):
        gid = k % 3
        pool.submit_update(gid, [int(u[k])], [int(v[k])], [float(w[k])])
        r = pool.query(gid, [0], [n - 1])
        if r.source == "live" and r.staleness != 0:
            bad += 1
    assert pool.flush(timeout=120.0)
    pool.recover_all()
    assert bad == 0
    assert pool.stats["poisoned_served"] == 0
    drills = pool.stats["crash_restores"]
    for gid in range(3):
        slot = pool.slots[gid]
        assert slot.state == SlotState.HEALTHY
        assert pool.verify(gid)["ok"]
        # and the restored slots converged to the sequential-update truth
        # (allclose, not bit-equal: recoveries re-solve and drains coalesce,
        # so the float op order legitimately differs from the twin's)
        twin = DynamicAPSP(graph(n, 1 + gid), method="blocked_fw", block_size=8)
        sel = np.arange(30) % 3 == gid
        for uu, vv, ww in zip(u[sel], v[sel], w[sel]):
            twin.update([int(uu)], [int(vv)], [float(ww)])
        np.testing.assert_allclose(
            np.asarray(slot.engine.dist), np.asarray(twin.dist),
            rtol=1e-5, atol=1e-5)
    # the drill actually fired at this seed (otherwise the test is vacuous)
    assert drills >= 1
    assert sum(s.stats["restores"] for s in pool.slots.values()) >= drills
    pool.close()


# ---------------------------------------------------------------------------
# per-slot deadline readers (PR 10 regression)
# ---------------------------------------------------------------------------

def _slow_slot(slot, seconds):
    import time as _time
    orig = slot.live_values

    def slow(qi, qj):
        _time.sleep(seconds)
        return orig(qi, qj)

    slot.live_values = slow


def test_per_slot_readers_isolate_slow_dispatch():
    # slot 0's dispatch is slow; with per-slot readers (default) slot 1's
    # live read does not queue behind it and meets its deadline
    pool = make_pool(12, graphs=2, deadline_s=0.05)
    for gid in range(2):                           # pay the gather compile up front
        pool.query(gid, [0], [5], deadline_s=0)
    _slow_slot(pool.slots[0], 0.5)
    r0 = pool.query(0, [0], [5])
    assert r0.deadline_missed and r0.source == "snapshot"
    r1 = pool.query(1, [0], [5])
    assert not r1.deadline_missed and r1.source == "live"
    pool.close()


def test_shared_reader_pool_still_serializes():
    # regression contrast: reader_workers=1 restores the old shared-worker
    # behavior — slot 1's read queues behind slot 0's abandoned dispatch
    # and misses its deadline too
    pool = make_pool(12, graphs=2, deadline_s=0.05, reader_workers=1)
    for gid in range(2):
        pool.query(gid, [0], [5], deadline_s=0)
    _slow_slot(pool.slots[0], 0.5)
    r0 = pool.query(0, [0], [5])
    assert r0.deadline_missed
    r1 = pool.query(1, [0], [5])
    assert r1.deadline_missed and r1.source == "snapshot"
    pool.close()


# ---------------------------------------------------------------------------
# locked stats counters
# ---------------------------------------------------------------------------

def test_counters_threaded_increments_lose_nothing():
    c = Counters({"x": 0})
    threads = [
        threading.Thread(target=lambda: [c.inc("x") for _ in range(10_000)])
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c["x"] == 80_000


def test_counters_refuse_subscript_store():
    c = Counters({"x": 1})
    with pytest.raises(TypeError):
        c["x"] = 2
    with pytest.raises(TypeError):
        c["x"] += 1
    assert c["x"] == 1
    assert dict(c.items()) == {"x": 1}
    assert c.get("missing") == 0 and "missing" not in c


def test_pool_summary_counts_consistent_under_async_load(tmp_path):
    pool = make_pool(12, async_updates=True, executor_workers=2)
    u, v, w = updates(12, 20, seed=9)
    for k in range(20):
        pool.submit_update(0, [int(u[k])], [int(v[k])], [float(w[k])])
        pool.query(0, [0], [1])
    assert pool.flush(timeout=60.0)
    s = pool.summary()
    assert s["pool"]["updates_submitted"] == 20
    assert (s["pool"]["queries_live"] + s["pool"]["queries_snapshot"]) == 20
    assert s["executor"]["drain_errors"] == 0
    pool.close()
