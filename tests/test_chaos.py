"""Fault-tolerant IO stack: deterministic chaos injection, bounded
retries with virtual-time backoff, hedged remote reads, degraded-mode
tiers, crash-consistent flush recovery, checkpoint corruption fallback."""
import os

import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.core.hetero_cache import HeteroCache
from repro.core.iostack import (AsyncIOEngine, FeatureStore, SyncIOEngine,
                                make_engine)
from repro.core.simulator import VirtualClock
from repro.core.writeback import FlushJournal
from repro.distributed.partition import (PartitionedFeatureStore,
                                         make_partition)
from repro.distributed.remote_engine import RemoteIOEngine
from repro.ft.chaos import (ChaosSchedule, FatalIOError, RetriesExhausted,
                            RetryPolicy, SimulatedCrash)
from repro.ft.failures import Coordinator

N_ROWS, ROW_DIM, N_SHARDS = 4096, 16, 4


@pytest.fixture()
def wstore(tmp_path):
    return FeatureStore(str(tmp_path / "w"), n_rows=N_ROWS, row_dim=ROW_DIM,
                        n_shards=N_SHARDS, create=True, rng_seed=0,
                        writable=True)


@pytest.fixture(scope="module")
def rstore(tmp_path_factory):
    p = tmp_path_factory.mktemp("chaos_feats")
    return FeatureStore(str(p), n_rows=N_ROWS, row_dim=ROW_DIM,
                        n_shards=N_SHARDS, create=True, rng_seed=0)


# ---------------------------------------------------------------------------
# schedule determinism + env parsing
# ---------------------------------------------------------------------------

def test_schedule_deterministic_and_keyed():
    ch = ChaosSchedule(seed=7, read_error_rate=0.3, write_error_rate=0.1,
                       stuck=((1, 5, 9),), slow=((2, 0, 4, 3.0),),
                       fatal_at=((0, 3),), torn_at=((0, 4),))
    for stream in range(3):
        for seq in range(12):
            for attempt in range(3):
                a = ch.decide(stream, "r", seq, attempt)
                b = ch.decide(stream, "r", seq, attempt)
                assert a == b                   # pure function of the key
    assert ch.decide(0, "r", 3, 0).error == "fatal"
    assert ch.decide(0, "w", 4, 0).torn         # torn applies to writes
    assert ch.decide(0, "r", 4, 0) is None or \
        not ch.decide(0, "r", 4, 0).torn        # ...never to reads
    assert ch.decide(1, "r", 5, 0).stuck
    assert not (ChaosSchedule(seed=7, stuck=((1, 5, 9),))
                .decide(1, "r", 9, 0) or False)  # window excludes hi
    assert ch.decide(2, "r", 1, 0).slow == 3.0
    # a retry re-rolls the error hash (attempt is part of the key)
    rolls = {ch.decide(0, "r", 50, a) is not None for a in range(8)}
    assert len(rolls) == 2                      # some hit, some miss


def test_schedule_from_env(monkeypatch):
    monkeypatch.delenv("HELIOS_CHAOS", raising=False)
    assert ChaosSchedule.from_env() is None
    monkeypatch.setenv("HELIOS_CHAOS", "off")
    assert ChaosSchedule.from_env() is None
    monkeypatch.setenv("HELIOS_CHAOS",
                       "seed=7,read_error_rate=0.01,write_error_rate=0.005")
    ch = ChaosSchedule.from_env()
    assert (ch.seed, ch.read_error_rate, ch.write_error_rate) == \
        (7, 0.01, 0.005)
    monkeypatch.setenv("HELIOS_CHAOS", "bogus_knob=1")
    with pytest.raises(ValueError):
        ChaosSchedule.from_env()


def test_backoff_bounded_and_jittered():
    rp = RetryPolicy(backoff_base_s=1e-3, backoff_cap_s=4e-3)
    b0 = rp.backoff(0, 0, 0)
    b5 = rp.backoff(0, 0, 5)
    assert 0.5e-3 <= b0 < 1.5e-3                # jitter in [0.5x, 1.5x)
    assert b5 == 4e-3                           # capped
    assert rp.backoff(0, 0, 1) != rp.backoff(0, 1, 1)   # jitter keyed


# ---------------------------------------------------------------------------
# engine recovery: bit-identical retries, visible accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["striped", "legacy", "sync"])
def test_transient_errors_recover_bit_identical(rstore, kind):
    ids = np.arange(0, N_ROWS, 7)
    want = rstore.read_rows(ids)
    ch = ChaosSchedule(seed=3, read_error_rate=0.08)
    if kind == "sync":
        eng = SyncIOEngine(rstore, chaos=ch)
    else:
        eng = AsyncIOEngine(rstore, striped=kind == "striped", chaos=ch)
    for _ in range(20):
        data, virt = eng.submit(ids).wait()
        np.testing.assert_array_equal(data, want)
        assert virt > 0
    st = eng.stats
    assert st.retries > 0 and st.transient_errors > 0
    assert st.virtual_backoff_s > 0
    eng.close()


def test_training_epoch_under_read_faults_is_bit_identical(tmp_path):
    """5% transient read errors on every storage read of a training run:
    the retries show in the IO report, and the loss of every step is the
    fault-free run's, bit for bit (retried reads return the same bytes)."""
    from repro.gnn.graph import synth_graph
    from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig
    g = synth_graph(3000, 8, skew=1.2, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=3000, row_dim=ROW_DIM,
                         n_shards=N_SHARDS, create=True, rng_seed=4)
    runs = {}
    for name, chaos in (("clean", None),
                        ("chaos", ChaosSchedule(seed=7,
                                                read_error_rate=0.05))):
        cfg = TrainerConfig(batch_size=64, fanouts=(4, 3), hidden=16,
                            presample_batches=2, prefetch_depth=1,
                            chaos=chaos, io_backoff_s=2e-5)
        with OutOfCoreGNNTrainer(g, store, cfg) as tr:
            out = tr.train(6)
            runs[name] = ([m["loss"] for m in tr.metrics_log], out["io"])
    assert runs["chaos"][0] == runs["clean"][0]
    assert runs["chaos"][1]["retries"] > 0
    assert runs["clean"][1]["retries"] == 0


def test_write_retries_recover(wstore):
    ids = np.arange(0, N_ROWS, 5)
    rows = np.random.default_rng(1).standard_normal(
        (len(ids), ROW_DIM)).astype(np.float32)
    eng = AsyncIOEngine(wstore, chaos=ChaosSchedule(seed=5,
                                                    write_error_rate=0.1))
    for _ in range(10):
        eng.submit_write(ids, rows).wait()
    np.testing.assert_array_equal(wstore.read_rows(ids), rows)
    assert eng.stats.retries > 0
    eng.close()


def test_stuck_window_times_out_then_passes(rstore):
    # shard 1's first service attempts are stuck; the deadline abandons
    # them, and the retried seq eventually leaves the window
    ch = ChaosSchedule(seed=0, stuck=((1, 0, 2),))
    eng = AsyncIOEngine(rstore, chaos=ch,
                        retry=RetryPolicy(deadline_s=5e-3))
    ids = np.arange(N_ROWS)                     # touches every shard
    data, virt = eng.submit(ids).wait()
    np.testing.assert_array_equal(data, rstore.read_rows(ids))
    assert eng.stats.timeouts >= 2
    # abandoned attempts charge the full deadline + backoff
    assert eng.stats.virtual_backoff_s > 0
    eng.close()


def test_stuck_without_deadline_raises_instead_of_hanging(rstore):
    ch = ChaosSchedule(seed=0, stuck=((0, 0, 10 ** 9),))
    eng = AsyncIOEngine(rstore, chaos=ch)       # no deadline configured
    tk = eng.submit(np.arange(0, N_ROWS, N_SHARDS))     # shard 0 only
    with pytest.raises(FatalIOError, match="deadline"):
        tk.wait()
    eng.close()


def test_retries_exhausted_escalates(rstore):
    ch = ChaosSchedule(seed=0, stuck=((0, 0, 10 ** 9),))
    eng = AsyncIOEngine(rstore, chaos=ch,
                        retry=RetryPolicy(deadline_s=1e-3, max_retries=2))
    tk = eng.submit(np.arange(0, N_ROWS, N_SHARDS))
    with pytest.raises(RetriesExhausted):
        tk.wait()
    assert eng.stats.fatal_errors == 1
    assert eng.stats.timeouts == 3              # initial + 2 retries
    eng.close()


def test_fatal_fault_partial_ticket_and_worker_survives(rstore):
    """A fatal CQE fails the ticket with partial-completion accounting —
    and the worker thread survives to service the next submit (the
    L679-class silent-swallow fix, now covered)."""
    ch = ChaosSchedule(seed=0, fatal_at=((1, 0),))
    eng = AsyncIOEngine(rstore, chaos=ch)
    tk = eng.submit(np.arange(N_ROWS))          # all four shards
    with pytest.raises(FatalIOError) as ei:
        tk.wait()
    assert ei.value.completed_shards == N_SHARDS - 1
    assert ei.value.failed_shards == 1
    # engine still fully functional: shard 1's next seq is past the fault
    ids = np.arange(0, N_ROWS, 3)
    data, _ = eng.submit(ids).wait()
    np.testing.assert_array_equal(data, rstore.read_rows(ids))
    assert not eng.worker_errors
    eng.close()


def test_legacy_worker_survives_fatal(rstore):
    eng = AsyncIOEngine(rstore, striped=False,
                        chaos=ChaosSchedule(seed=0, fatal_at=((0, 0),)))
    with pytest.raises(FatalIOError):
        eng.submit(np.arange(64)).wait()
    data, _ = eng.submit(np.arange(64)).wait()  # worker still alive
    np.testing.assert_array_equal(data, rstore.read_rows(np.arange(64)))
    eng.close()


def test_slow_window_inflates_virtual_time(rstore):
    ids = np.arange(0, N_ROWS, N_SHARDS)        # shard 0 only
    clean = AsyncIOEngine(rstore, chaos=None)
    _, v0 = clean.submit(ids).wait()
    clean.close()
    slow = AsyncIOEngine(rstore, chaos=ChaosSchedule(
        seed=0, slow=((0, 0, 10 ** 9, 4.0),)))
    data, v1 = slow.submit(ids).wait()
    np.testing.assert_array_equal(data, rstore.read_rows(ids))
    assert v1 == pytest.approx(4.0 * v0)
    assert slow.stats.retries == 0              # slow is not an error
    slow.close()


def test_make_engine_passes_chaos_through(rstore):
    ch = ChaosSchedule(seed=1, read_error_rate=0.2)
    for mode in ("helios", "gids", "cpu"):
        eng = make_engine(mode, rstore, chaos=ch,
                          retry=RetryPolicy(max_retries=8))
        assert eng.chaos is ch and eng.retry.max_retries == 8
        data, _ = eng.submit(np.arange(128)).wait()
        np.testing.assert_array_equal(data, rstore.read_rows(np.arange(128)))
        eng.close()


# ---------------------------------------------------------------------------
# remote engine: hedged reads reroute a stuck peer to owner storage
# ---------------------------------------------------------------------------

@pytest.fixture()
def fleet(tmp_path):
    part = make_partition("hash", N_ROWS, 4)
    ps = PartitionedFeatureStore(str(tmp_path / "fleet"), N_ROWS, ROW_DIM,
                                 part, create=True, writable=True)
    rows = np.random.default_rng(2).standard_normal(
        (N_ROWS, ROW_DIM)).astype(np.float32)
    ps.write_rows(np.arange(N_ROWS), rows)
    return ps, rows


def test_hedged_read_reroutes_stuck_peer(fleet):
    ps, rows = fleet
    ch = ChaosSchedule(seed=11, stuck=((2, 0, 10 ** 9),))
    eng = RemoteIOEngine(ps, me=0, chaos=ch,
                         retry=RetryPolicy(deadline_s=2e-3))
    ids = np.arange(0, N_ROWS, 5)
    for _ in range(4):
        data, _ = eng.submit(ids).wait()
        np.testing.assert_array_equal(data, rows[ids])
    assert eng.stats.hedged_reads > 0
    assert eng.stats.timeouts > 0
    assert eng.rerouted_batches > 0             # hedge = reroute pricing
    eng.close()


def test_remote_transient_errors_recover(fleet):
    ps, rows = fleet
    eng = RemoteIOEngine(ps, me=0,
                         chaos=ChaosSchedule(seed=4, read_error_rate=0.1))
    ids = np.arange(0, N_ROWS, 3)
    for _ in range(8):
        data, _ = eng.submit(ids).wait()
        np.testing.assert_array_equal(data, rows[ids])
    assert eng.stats.retries > 0
    eng.close()


# ---------------------------------------------------------------------------
# graceful degradation: failing shards drop out of prefetch traffic
# ---------------------------------------------------------------------------

def test_degraded_shard_suppresses_prefetch(rstore):
    ch = ChaosSchedule(seed=0, stuck=((2, 0, 10 ** 9),))
    eng = AsyncIOEngine(rstore, chaos=ch,
                        retry=RetryPolicy(deadline_s=1e-3, max_retries=3),
                        degrade_after=3)
    cache = HeteroCache(rstore, device_rows=0, host_rows=256, io_engine=eng)
    shard2 = np.arange(2, N_ROWS, N_SHARDS)
    # demand gather against the stuck shard: clear fatal error (not a
    # hang), and the failure streak marks the shard degraded
    with pytest.raises(RetriesExhausted):
        eng.submit(shard2[:64]).wait()
    assert list(eng.degraded_shards()) == [2]
    assert eng.stats.degraded_events == 1
    # optional prefetch traffic to the degraded shard is suppressed...
    res = cache.prefetch_rows(shard2[200:300])
    assert res is None
    assert cache.stats.degraded_skipped_rows == 100
    # ...while other shards' prefetch is not counted as degraded (it may
    # still lose the score-based admission, but not to the fault filter)
    shard0 = np.arange(0, N_ROWS, N_SHARDS)
    cache.prefetch_rows(shard0[200:232])
    assert cache.stats.degraded_skipped_rows == 100
    # recovery: a clean op on the shard resets the streak
    eng._fail_streak[2] = 0
    assert len(eng.degraded_shards()) == 0
    cache.close()


def test_checkpoint_defers_degraded_shards(tmp_path, wstore):
    cm = CheckpointManager(str(tmp_path / "ckpt"), keep=4)
    vers = np.zeros(N_ROWS, np.int64)
    cm.save_embeddings(1, wstore, versions=vers)
    wstore.write_rows(np.arange(N_ROWS),
                      np.ones((N_ROWS, ROW_DIM), np.float32))
    wstore.flush()
    m = cm.save_embeddings(2, wstore, versions=vers + 1,
                           skip_shards=np.array([1, 3]))
    assert m["shards_deferred"] == [1, 3]
    assert m["shards_written"] == N_SHARDS - 2
    # deferred shards reference the base's (stale) bytes — restore works
    live = FeatureStore(str(tmp_path / "live"), n_rows=N_ROWS,
                        row_dim=ROW_DIM, n_shards=N_SHARDS, create=True,
                        writable=True)
    out = cm.restore_embeddings(live, step=2)
    assert out["restored_step"] == 2
    got = live.read_rows(np.arange(N_ROWS))
    assert (got[np.arange(0, N_ROWS, N_SHARDS)] == 1.0).all()
    assert not (got[np.arange(1, N_ROWS, N_SHARDS)] == 1.0).all()


# ---------------------------------------------------------------------------
# coordinator on virtual time (deterministic failure detection)
# ---------------------------------------------------------------------------

def test_coordinator_virtual_clock():
    vc = VirtualClock()
    c = Coordinator(2, heartbeat_timeout=5.0, clock=vc)
    c.heartbeat(0)
    c.heartbeat(1)
    assert c.workers[0].last_heartbeat == 0.0   # virtual time starts at 0
    assert c.dead_workers() == []               # makespan still 0
    vc.schedule("io", 0.0, 10.0)
    assert sorted(c.dead_workers()) == [0, 1]
    c.heartbeat(0)                              # at makespan = 10
    assert c.dead_workers() == [1]
    assert c.step_plan(7)["action"] == "restore_and_reshape"


def test_coordinator_explicit_zero_now():
    # now=0.0 must be honored, not silently replaced by wall-clock
    # (the `now or time.monotonic()` falsy-zero bug)
    c = Coordinator(1, heartbeat_timeout=5.0, clock=lambda: 100.0)
    c.heartbeat(0, now=0.0)
    assert c.workers[0].last_heartbeat == 0.0
    assert c.dead_workers(now=3.0) == []
    assert c.dead_workers() == [0]              # clock says 100


# ---------------------------------------------------------------------------
# crash-consistent flush: write-intent journal + torn-write recovery
# ---------------------------------------------------------------------------

def test_flush_journal_lifecycle(wstore):
    c = HeteroCache(wstore, device_rows=0, host_rows=N_ROWS)
    assert c.journal_recovery == {"action": "none"}
    ids = np.arange(0, N_ROWS, 3)
    c.write_planned(ids, np.full((len(ids), ROW_DIM), 7.0, np.float32))
    c.flush()
    # committed: no journal left behind after a completed barrier
    assert not os.path.exists(os.path.join(wstore.path, "flush.journal"))
    c.close()


def test_crash_mid_flush_replays_barrier(tmp_path):
    store = FeatureStore(str(tmp_path / "t"), n_rows=N_ROWS,
                         row_dim=ROW_DIM, n_shards=N_SHARDS, create=True,
                         rng_seed=0, writable=True)
    ids = np.arange(0, N_ROWS, 3)
    new = np.full((len(ids), ROW_DIM), 9.0, np.float32)
    # torn write on the flush barrier: SimulatedCrash fires after a
    # PREFIX of the sorted batch landed — exactly the torn state the
    # journal must repair
    eng = SyncIOEngine(store, chaos=ChaosSchedule(
        seed=0, torn_at=tuple((0, q) for q in range(64))))
    c = HeteroCache(store, device_rows=0, host_rows=N_ROWS, io_engine=eng)
    c.write_planned(ids, new)
    with pytest.raises(SimulatedCrash):
        c.flush()
    # the intent journal survived the "crash"
    assert os.path.exists(os.path.join(store.path, "flush.journal"))
    # restart: reopen the store; the new cache replays the barrier
    # before anything reads the torn rows
    store2 = FeatureStore(str(tmp_path / "t"), n_rows=N_ROWS,
                          row_dim=ROW_DIM, n_shards=N_SHARDS,
                          writable=True)
    c2 = HeteroCache(store2, device_rows=0, host_rows=N_ROWS)
    assert c2.journal_recovery == {"action": "replayed", "rows": len(ids)}
    np.testing.assert_array_equal(store2.read_rows(ids), new)
    assert not os.path.exists(os.path.join(store2.path, "flush.journal"))
    c2.close()


def test_torn_journal_detected_and_discarded(tmp_path):
    store = FeatureStore(str(tmp_path / "t"), n_rows=256, row_dim=8,
                         n_shards=2, create=True, rng_seed=0, writable=True)
    before = store.read_rows(np.arange(256))
    j = FlushJournal(store.path)
    j.record(np.arange(10), np.ones((10, 8), np.float32))
    # truncate the journal mid-payload: crc/length check must catch it
    path = os.path.join(store.path, "flush.journal")
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[:len(blob) - 17])
    assert j.pending()[0] == "torn"
    c = HeteroCache(store, device_rows=0, host_rows=64)
    assert c.journal_recovery == {"action": "discarded"}
    np.testing.assert_array_equal(store.read_rows(np.arange(256)), before)
    assert not os.path.exists(path)
    c.close()


def test_journal_bitflip_detected(tmp_path):
    store = FeatureStore(str(tmp_path / "t"), n_rows=256, row_dim=8,
                         n_shards=2, create=True, rng_seed=0, writable=True)
    j = FlushJournal(store.path)
    j.record(np.arange(10), np.ones((10, 8), np.float32))
    path = os.path.join(store.path, "flush.journal")
    blob = bytearray(open(path, "rb").read())
    blob[-5] ^= 0x40
    open(path, "wb").write(bytes(blob))
    assert j.pending()[0] == "torn"             # crc mismatch
    assert j.recover(store) == {"action": "discarded"}


def test_stale_journal_removed_on_create(tmp_path):
    store = FeatureStore(str(tmp_path / "t"), n_rows=64, row_dim=4,
                         n_shards=2, create=True, writable=True)
    FlushJournal(store.path).record(np.arange(4), np.ones((4, 4),
                                                          np.float32))
    del store
    # re-CREATING the store is a fresh table: the old intent is garbage
    store2 = FeatureStore(str(tmp_path / "t"), n_rows=64, row_dim=4,
                          n_shards=2, create=True, writable=True)
    assert not os.path.exists(os.path.join(store2.path, "flush.journal"))


# ---------------------------------------------------------------------------
# checkpoint corruption fallback (manifest mid-chain)
# ---------------------------------------------------------------------------

def test_restore_falls_back_past_corrupt_manifest(tmp_path, wstore):
    cm = CheckpointManager(str(tmp_path / "ckpt"), keep=5)
    marks = {}
    for step in (1, 2, 3):
        wstore.write_rows(np.arange(N_ROWS),
                          np.full((N_ROWS, ROW_DIM), float(step),
                                  np.float32))
        wstore.flush()
        cm.save_embeddings(step, wstore)
        marks[step] = float(step)
    # corrupt newest SHARD and mid-chain MANIFEST: restore walks back
    # to the newest fully-intact step and reports both skips
    p3 = os.path.join(str(tmp_path / "ckpt"), f"emb_{3:010d}",
                      "table", "shard_2.bin")
    blob = bytearray(open(p3, "rb").read())
    blob[100] ^= 0x01
    open(p3, "wb").write(bytes(blob))
    m2 = os.path.join(str(tmp_path / "ckpt"), f"emb_{2:010d}",
                      "manifest.json")
    open(m2, "w").write("{not json")
    live = FeatureStore(str(tmp_path / "live"), n_rows=N_ROWS,
                        row_dim=ROW_DIM, n_shards=N_SHARDS, create=True,
                        writable=True)
    out = cm.restore_embeddings(live)
    assert out["restored_step"] == 1
    assert [s["step"] for s in out["skipped"]] == [3, 2]
    assert (live.read_rows(np.arange(N_ROWS)) == 1.0).all()


def test_restore_all_corrupt_raises_with_report(tmp_path, wstore):
    cm = CheckpointManager(str(tmp_path / "ckpt"), keep=5)
    cm.save_embeddings(1, wstore)
    p = os.path.join(str(tmp_path / "ckpt"), f"emb_{1:010d}",
                     "table", "shard_0.bin")
    os.remove(p)                                # missing referenced file
    live = FeatureStore(str(tmp_path / "live"), n_rows=N_ROWS,
                        row_dim=ROW_DIM, n_shards=N_SHARDS, create=True,
                        writable=True)
    with pytest.raises(IOError, match="step 1"):
        cm.restore_embeddings(live)


def test_restore_geometry_mismatch_still_raises(tmp_path, wstore):
    # a geometry mismatch is a CALLER error: no older checkpoint fixes
    # the wrong store, so fallback must not mask it
    cm = CheckpointManager(str(tmp_path / "ckpt"), keep=5)
    cm.save_embeddings(1, wstore)
    other = FeatureStore(str(tmp_path / "other"), n_rows=N_ROWS,
                         row_dim=ROW_DIM + 1, n_shards=N_SHARDS,
                         create=True, writable=True)
    with pytest.raises(ValueError, match="geometry"):
        cm.restore_embeddings(other)


# ---------------------------------------------------------------------------
# e2e: chaos run of the unified gather path stays bit-identical
# ---------------------------------------------------------------------------

def test_cache_gathers_bit_identical_under_chaos(rstore):
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, N_ROWS, 512) for _ in range(12)]
    clean = HeteroCache(rstore, device_rows=128, host_rows=512,
                        io_engine=AsyncIOEngine(rstore, chaos=None))
    want = [np.asarray(clean.gather(b)) for b in batches]
    clean.close()
    ch = ChaosSchedule(seed=7, read_error_rate=0.02, stuck=((1, 3, 6),))
    eng = AsyncIOEngine(rstore, chaos=ch,
                        retry=RetryPolicy(deadline_s=5e-3))
    chaotic = HeteroCache(rstore, device_rows=128, host_rows=512,
                          io_engine=eng)
    got = [np.asarray(chaotic.gather(b)) for b in batches]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert eng.stats.retries > 0                # faults really fired
    chaotic.close()
