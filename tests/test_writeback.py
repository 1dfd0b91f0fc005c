"""Write path: writable FeatureStore, engine submit_write, write-back
mutable cache tiers, flush-on-demote, trainable embeddings, sharded
embedding checkpoints."""
import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.core.hetero_cache import HeteroCache
from repro.core.iostack import (AsyncIOEngine, CPUManagedEngine, FeatureStore,
                                SyncIOEngine, keep_last_writer,
                                pick_coalesce_gap)
from repro.core.writeback import MutableTierTable

N_ROWS, ROW_DIM, N_SHARDS = 2048, 16, 4


@pytest.fixture()
def wstore(tmp_path):
    return FeatureStore(str(tmp_path / "w"), n_rows=N_ROWS, row_dim=ROW_DIM,
                        n_shards=N_SHARDS, create=True, rng_seed=0,
                        writable=True)


def _rows(rng, n):
    return rng.standard_normal((n, ROW_DIM)).astype(np.float32)


# ---------------------------------------------------------------------------
# FeatureStore write path
# ---------------------------------------------------------------------------

def test_store_write_rows_roundtrip_and_guard(tmp_path, wstore):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, N_ROWS, 100)
    rows = _rows(rng, 100)
    wstore.write_rows(ids, rows)
    ki, kr = keep_last_writer(ids, rows)
    np.testing.assert_array_equal(wstore.read_rows(ki), kr)
    wstore.flush()
    ro = FeatureStore(str(tmp_path / "w"), n_rows=N_ROWS, row_dim=ROW_DIM,
                      n_shards=N_SHARDS)
    np.testing.assert_array_equal(ro.read_rows(ki), kr)  # durable
    with pytest.raises(PermissionError):
        ro.write_rows(ids, rows)


def test_keep_last_writer_semantics():
    ids = np.array([3, 1, 3, 2, 1])
    rows = np.arange(5, dtype=np.float32)[:, None]
    ki, kr = keep_last_writer(ids, rows)
    got = dict(zip(ki.tolist(), kr[:, 0].tolist()))
    assert got == {3: 2.0, 2: 3.0, 1: 4.0}   # last occurrence wins
    e_ids, e_rows = keep_last_writer(np.empty(0, np.int64),
                                     np.empty((0, 1), np.float32))
    assert len(e_ids) == 0 and len(e_rows) == 0


# ---------------------------------------------------------------------------
# engine submit_write: every engine, every gap, matches write_rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda s: AsyncIOEngine(s),
    lambda s: AsyncIOEngine(s, striped=False),
    lambda s: AsyncIOEngine(s, coalesce_gap=0),
    lambda s: AsyncIOEngine(s, coalesce_gap="adaptive"),
    lambda s: SyncIOEngine(s),
    lambda s: CPUManagedEngine(s),
], ids=["striped", "legacy-1q", "gap0", "adaptive", "gids", "cpu"])
def test_submit_write_matches_write_rows(wstore, make):
    rng = np.random.default_rng(1)
    eng = make(wstore)
    for ids in (rng.integers(0, N_ROWS, 500),       # duplicates included
                np.arange(N_ROWS),
                np.array([N_ROWS - 1]),
                np.array([], np.int64)):
        rows = _rows(rng, len(ids))
        _, virt = eng.submit_write(ids, rows).wait()
        assert virt >= 0.0
        ki, kr = keep_last_writer(ids, rows)
        if len(ki):
            np.testing.assert_array_equal(wstore.read_rows(ki), kr)
    assert eng.stats.write_batches == 4
    assert eng.stats.write_requests > 0
    eng.close()


def test_submit_write_readonly_store_raises(tmp_path):
    ro = FeatureStore(str(tmp_path / "ro"), n_rows=64, row_dim=4,
                      n_shards=2, create=True)
    with AsyncIOEngine(ro) as eng:
        with pytest.raises(PermissionError):
            eng.submit_write(np.array([0]), np.zeros((1, 4), np.float32))
    with pytest.raises(PermissionError):
        SyncIOEngine(ro).submit_write(np.array([0]),
                                      np.zeros((1, 4), np.float32))


def test_submit_write_shape_mismatch_raises(wstore):
    with AsyncIOEngine(wstore) as eng:
        with pytest.raises(ValueError):
            eng.submit_write(np.array([0, 1]), np.zeros((2, 3), np.float32))


def test_striped_coalesced_write_beats_legacy_2x_on_skew(wstore):
    """Acceptance: >= 2x effective write bandwidth (virtual time) over the
    single-queue write path on a skewed update workload."""
    rng = np.random.default_rng(0)
    p = 1.0 / (np.arange(N_ROWS) + 1.0) ** 1.1
    p /= p.sum()
    batches = [np.unique(rng.choice(N_ROWS, size=4 * N_ROWS, p=p))
               for _ in range(2)]
    bw = {}
    for label, kw in (("legacy", dict(striped=False)),
                      ("coalesced", dict(striped=True, coalesce_gap=8))):
        eng = AsyncIOEngine(wstore, **kw)
        for b in batches:
            eng.submit_write(b, _rows(rng, len(b))).wait()
        bw[label] = eng.stats.write_bw()
        eng.close()
    assert bw["coalesced"] >= 2.0 * bw["legacy"]


def test_adaptive_gap_picker_contract():
    # degenerate inputs
    assert pick_coalesce_gap(np.empty(0, np.int64)) == 0
    assert pick_coalesce_gap(np.array([7])) == 0
    # adjacent/duplicate offsets cost nothing -> no gap needed
    assert pick_coalesce_gap(np.array([4, 5, 5, 6])) == 0
    # amplification cap is exact: joining every waste-1 gap here doubles
    # the span (50% density), which a 1.5x cap must refuse...
    assert pick_coalesce_gap(np.arange(0, 200, 2), amp_cap=1.5) == 0
    # ...but a 2.1x cap affords it
    assert pick_coalesce_gap(np.arange(0, 200, 2), amp_cap=2.1) == 1
    # dense head + sparse tail: the head is runs of adjacent rows with an
    # occasional 1-row hole (cheap joins that fit the budget), the tail's
    # 99-row holes exceed max_gap and never count
    base = np.arange(0, 130)
    head = base[base % 10 != 9]
    offs = np.concatenate([head, np.arange(1000, 5000, 100)])
    g = pick_coalesce_gap(offs, max_gap=64, amp_cap=1.5)
    assert 1 <= g < 99
    # never exceeds max_gap
    assert pick_coalesce_gap(np.array([0, 50, 100]), max_gap=8,
                             amp_cap=100.0) == 0


def test_adaptive_gap_respects_amplification_cap(wstore):
    """End to end: the adaptive engine's realized read amplification stays
    under the cap on any workload; a fixed big gap does not."""
    rng = np.random.default_rng(3)
    ids = np.unique(rng.integers(0, N_ROWS, 300))    # sparse-ish uniform
    cap = 1.5
    eng = AsyncIOEngine(wstore, coalesce_gap="adaptive", amp_cap=cap)
    eng.submit(ids).wait()
    amp = eng.stats.span_bytes / eng.stats.bytes
    assert amp <= cap + 1e-9
    eng.close()


# ---------------------------------------------------------------------------
# MutableTierTable
# ---------------------------------------------------------------------------

def test_mutable_tier_table():
    t = MutableTierTable(16)
    assert t.n_dirty == 0
    t.mark_dirty(np.array([1, 3, 3]))
    assert t.n_dirty == 2
    assert list(t.dirty_ids()) == [1, 3]
    np.testing.assert_array_equal(t.is_dirty(np.array([0, 1, 3])),
                                  [False, True, True])
    assert list(t.versions(np.array([1, 3]))) == [1, 2]   # dup counted
    t.bump_version(np.array([1]))
    assert list(t.versions(np.array([1]))) == [2]
    assert t.n_dirty == 2                                  # bump != dirty
    t.clear_dirty(np.array([1, 3]))
    assert t.n_dirty == 0
    assert list(t.versions(np.array([1, 3]))) == [2, 2]   # versions persist


# ---------------------------------------------------------------------------
# HeteroCache write path: read-your-writes, flush, flush-on-demote
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda s: AsyncIOEngine(s),
    lambda s: SyncIOEngine(s),
    lambda s: CPUManagedEngine(s),
], ids=["helios", "gids", "cpu"])
def test_write_planned_read_your_writes_all_tiers(wstore, make):
    eng = make(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        64, 128, eng)
    rng = np.random.default_rng(0)
    # one id per tier (hotness = reverse id: low ids are storage-resident)
    dev_id, host_id, sto_id = (int(np.where(cache.loc == t)[0][0])
                               for t in (0, 1, 2))
    ids = np.array([dev_id, host_id, sto_id])
    rows = _rows(rng, 3)
    res = cache.write_planned(ids, rows)
    assert (res.device_rows, res.host_rows, res.through_rows) == (1, 1, 1)
    np.testing.assert_array_equal(cache.gather(ids), rows)
    # cached writes are dirty; the write-through one is not
    assert cache.n_dirty == 2
    np.testing.assert_array_equal(wstore.read_rows(np.array([sto_id])),
                                  rows[2:])
    # storage is NOT yet current for the cached rows (write-back deferral)
    assert not np.array_equal(wstore.read_rows(ids[:2]), rows[:2])
    fr = cache.flush()
    assert fr.rows == 2 and cache.n_dirty == 0
    np.testing.assert_array_equal(wstore.read_rows(ids), rows)
    # flush with nothing dirty is a no-op
    assert cache.flush().rows == 0
    cache.close()
    eng.close()


def test_write_planned_requires_writable_store(tmp_path):
    ro = FeatureStore(str(tmp_path / "ro"), n_rows=64, row_dim=4,
                      n_shards=2, create=True)
    cache = HeteroCache(ro, np.zeros(64), 4, 8, SyncIOEngine(ro))
    assert cache.mut is None and cache.n_dirty == 0
    with pytest.raises(PermissionError):
        cache.write_planned(np.array([0]), np.zeros((1, 4), np.float32))
    cache.close()


def test_writethrough_mode_keeps_storage_current(wstore):
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        64, 128, eng, write_policy="writethrough")
    rng = np.random.default_rng(1)
    ids = np.array([int(np.where(cache.loc == t)[0][0]) for t in (0, 1, 2)])
    rows = _rows(rng, 3)
    res = cache.write_planned(ids, rows)
    assert res.through_rows == 3                  # every row hits storage
    assert cache.n_dirty == 0                     # nothing deferred
    np.testing.assert_array_equal(wstore.read_rows(ids), rows)
    np.testing.assert_array_equal(cache.gather(ids), rows)  # tiers updated too
    cache.close()


def test_invalid_write_policy_rejected(wstore):
    with pytest.raises(ValueError):
        HeteroCache(wstore, np.zeros(N_ROWS), 4, 8, SyncIOEngine(wstore),
                    write_policy="nope")


def test_refresh_flushes_dirty_demotions(wstore):
    """A dirty resident demoted to storage must write back BEFORE the tier
    copy is dropped — its value survives the demotion."""
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        32, 64, eng)
    rng = np.random.default_rng(2)
    cached = np.where(cache.loc < 2)[0]
    rows = _rows(rng, len(cached))
    cache.write_planned(cached, rows)
    assert cache.n_dirty == len(cached)
    # refresh with INVERTED hotness: every cached row demotes to storage
    res = cache.refresh(np.arange(N_ROWS, dtype=float))
    assert res.flushed == len(cached)
    assert cache.n_dirty == 0
    np.testing.assert_array_equal(wstore.read_rows(cached), rows)
    np.testing.assert_array_equal(cache.gather(cached), rows)
    # disjoint accounting: the result's virtual_s is the TOTAL operator
    # cost, but the stats split it — flush seconds in virtual_flush_s,
    # migration-only seconds in virtual_migrate_s, counted exactly once
    assert res.flush_virtual_s > 0
    st = cache.stats
    assert st.virtual_flush_s == pytest.approx(res.flush_virtual_s)
    assert st.virtual_migrate_s == pytest.approx(
        res.virtual_s - res.flush_virtual_s)
    cache.close()


def test_cache_write_stats_match_engine(wstore):
    """Cache write accounting books the ticket-resolved virtual seconds, so
    cache write+flush time == engine write time exactly."""
    eng = AsyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        64, 128, eng)
    rng = np.random.default_rng(3)
    for _ in range(3):
        ids = rng.integers(0, N_ROWS, 200)
        cache.write_planned(ids, _rows(rng, 200))
    cache.refresh(rng.standard_normal(N_ROWS))
    cache.flush()
    st = cache.stats
    assert st.virtual_write_s + st.virtual_flush_s == pytest.approx(
        eng.stats.virtual_write_s, abs=1e-12)
    assert st.written_rows > 0 and st.flushed_rows > 0
    cache.close()
    eng.close()


# ---------------------------------------------------------------------------
# split-phase prefetch (double-buffered cadence) + dirty victim flush
# ---------------------------------------------------------------------------

def test_prefetch_split_phase_and_dirty_victim_flush(wstore):
    from repro.core.hetero_cache import PendingPrefetch
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        0, 64, eng)
    rng = np.random.default_rng(4)
    # dirty the COLDEST host resident (the designated victim)
    victim = int(cache._host_ids[np.argmin(
        cache.policy.placement_scores()[cache._host_ids])])
    vrow = _rows(rng, 1)
    cache.write_planned(np.array([victim]), vrow)
    # admit a hot storage row (hotness above every resident incl. boost)
    cand = np.where(cache.loc == 2)[0][:1]
    cache.policy._scores[cand] = N_ROWS * 10.0
    pp = cache.prefetch_rows(cand, wait=False)
    assert isinstance(pp, PendingPrefetch)
    res = cache.complete_prefetch(pp)
    assert res is not None and res.rows == 1
    assert cache.loc[cand[0]] == 1                # admitted to host
    assert cache.loc[victim] == 2                 # evicted...
    np.testing.assert_array_equal(wstore.read_rows(np.array([victim])),
                                  vrow)           # ...but flushed first
    np.testing.assert_array_equal(
        cache.gather(np.array([victim])), vrow)   # read-your-writes holds
    cache.close()


def test_pending_prefetch_dropped_when_write_lands_mid_flight(wstore):
    """A write_planned that lands between prefetch issue and completion
    bumps the row's version; the stale prefetched buffer must be dropped,
    not admitted over the newer value (read-your-writes across the
    double-buffered cadence)."""
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        0, 64, eng)
    cand = np.where(cache.loc == 2)[0][:1]
    cache.policy._scores[cand] = N_ROWS * 10.0
    pp = cache.prefetch_rows(cand, wait=False)
    assert pp is not None
    # mid-flight: the row is overwritten (write-through: still storage-
    # resident, version bumped)
    new = np.full((1, ROW_DIM), 7.0, np.float32)
    cache.write_planned(cand, new)
    res = cache.complete_prefetch(pp)             # stale buffer: dropped,
    assert res is not None and res.rows == 0      # but the IO cost remains
    assert res.virtual_s > 0
    np.testing.assert_array_equal(cache.gather(cand), new)
    np.testing.assert_array_equal(wstore.read_rows(cand), new)
    cache.close()


def test_pending_prefetch_revalidates_after_refresh(wstore):
    """A refresh landing while the prefetch ticket is in flight invalidates
    stale admissions instead of corrupting the tables."""
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        0, 64, eng)
    cand = np.where(cache.loc == 2)[0][:4]
    cache.policy._scores[cand] = N_ROWS * 10.0
    pp = cache.prefetch_rows(cand, wait=False)
    assert pp is not None
    # mid-flight: a refresh admits those same rows itself
    cache.refresh(cache.policy.placement_scores())
    assert (cache.loc[cand] == 1).all()
    res = cache.complete_prefetch(pp)             # stale: must not double-admit
    assert res is not None and res.rows == 0
    # invariants: host tier membership consistent
    np.testing.assert_array_equal(np.sort(cache._host_ids),
                                  np.where(cache.loc == 1)[0])
    full = cache.gather(np.arange(N_ROWS))
    np.testing.assert_array_equal(full, wstore.read_rows(np.arange(N_ROWS)))
    cache.close()


@pytest.mark.parametrize("make", [
    lambda s: AsyncIOEngine(s),
    lambda s: AsyncIOEngine(s, striped=False),
    lambda s: SyncIOEngine(s),
    lambda s: CPUManagedEngine(s),
], ids=["helios", "helios-legacy", "gids", "cpu"])
def test_random_interleaving_never_loses_writes(wstore, make):
    """Deterministic-seed mirror of the hypothesis read-your-writes
    property (which needs the optional hypothesis dep): random
    interleavings of write/gather/refresh/flush/prefetch keep every gather
    equal to the shadow model, and the final flush makes storage alone
    reproduce it — under every engine mode."""
    eng = make(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        48, 96, eng)
    all_ids = np.arange(N_ROWS)
    shadow = wstore.read_rows(all_ids)
    rng = np.random.default_rng(0xC0FFEE)
    for step in range(40):
        op = rng.integers(0, 5)
        if op == 0:
            ids = rng.integers(0, N_ROWS, int(rng.integers(1, 64)))
            rows = _rows(rng, len(ids))
            cache.write_planned(ids, rows)
            ki, kr = keep_last_writer(ids, rows)
            shadow[ki] = kr
        elif op == 1:
            ids = rng.integers(0, N_ROWS, int(rng.integers(1, 64)))
            np.testing.assert_array_equal(cache.gather(ids), shadow[ids])
        elif op == 2:
            cache.refresh(rng.standard_normal(N_ROWS))
        elif op == 3:
            cache.flush()
            assert cache.n_dirty == 0
            np.testing.assert_array_equal(wstore.read_rows(all_ids), shadow)
        else:
            cache.prefetch_rows(rng.integers(0, N_ROWS, 16))
        np.testing.assert_array_equal(cache.gather(all_ids), shadow)
    cache.flush()
    np.testing.assert_array_equal(wstore.read_rows(all_ids), shadow)
    cache.close()
    eng.close()


# ---------------------------------------------------------------------------
# delta read-modify-write (the gradient-update primitive)
# ---------------------------------------------------------------------------

def test_apply_delta_composes_and_sums_duplicates(wstore):
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        64, 128, eng)
    ids = np.array([int(np.where(cache.loc == t)[0][0]) for t in (0, 1, 2)])
    base = cache.gather(ids).copy()
    one = np.ones((3, ROW_DIM), np.float32)
    cache.apply_delta(ids, one)
    cache.apply_delta(ids, one)                   # deltas COMPOSE
    np.testing.assert_allclose(cache.gather(ids), base + 2, rtol=1e-6)
    # duplicate ids in one batch contribute their SUMMED delta
    cache.apply_delta(np.array([ids[0], ids[0]]),
                      np.ones((2, ROW_DIM), np.float32))
    np.testing.assert_allclose(cache.gather(ids[:1]), base[:1] + 4,
                               rtol=1e-6)
    # a stale absolute write would have lost one of these; assert the
    # interleaving that bites the deep pipeline: read, then delta, then
    # write-from-read must NOT revert the delta
    stale = cache.gather(ids)                     # "batch i+1's gather"
    cache.apply_delta(ids, one)                   # "batch i's update lands"
    cache.apply_delta(ids, np.zeros_like(one))    # no-op delta, re-reads live
    np.testing.assert_allclose(cache.gather(ids)[1:], stale[1:] + 1,
                               rtol=1e-6)
    cache.flush()
    cache.close()


def test_flush_barrier_runs_even_without_dirty_rows(wstore):
    """Write-through rows land in the memmaps without an msync; the flush()
    barrier must make THEM durable too, not early-return."""
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        0, 0, eng, write_policy="writethrough")
    cache.write_planned(np.array([5]), np.full((1, ROW_DIM), 3.5, np.float32))
    assert cache.n_dirty == 0
    fr = cache.flush()
    assert fr.rows == 0
    assert cache.stats.flushes == 1               # the barrier ran
    cache.close()


# ---------------------------------------------------------------------------
# split-phase writes: tickets in flight, version-checked revalidation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda s: AsyncIOEngine(s),
    lambda s: AsyncIOEngine(s, striped=False),
    lambda s: SyncIOEngine(s),
    lambda s: CPUManagedEngine(s),
], ids=["helios", "helios-legacy", "gids", "cpu"])
def test_write_planned_split_phase_read_your_writes(wstore, make):
    """write_planned(wait=False) leaves the storage ticket in flight, yet
    a gather issued immediately after MUST observe the written values
    (per-shard FIFO ordering) — and complete_write is idempotent."""
    from repro.core.hetero_cache import PendingWrite
    eng = make(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        64, 128, eng)
    rng = np.random.default_rng(7)
    for _ in range(4):
        ids = rng.integers(0, N_ROWS, 150)
        rows = _rows(rng, 150)
        pw = cache.write_planned(ids, rows, wait=False)
        assert isinstance(pw, PendingWrite)
        ki, kr = keep_last_writer(ids, rows)
        np.testing.assert_array_equal(cache.gather(ki), kr)  # in-flight RYW
        res = cache.complete_write(pw)
        assert res.virtual_s >= 0.0
        assert cache.complete_write(pw) is res               # idempotent
    cache.flush()
    st = cache.stats
    assert st.virtual_write_s + st.virtual_flush_s == pytest.approx(
        eng.stats.virtual_write_s, abs=1e-12)
    cache.close()
    eng.close()


def test_flush_completes_inflight_writes_before_durability(wstore):
    """A flush() barrier must wait out split-phase write tickets submitted
    before it — afterwards storage alone reproduces every write."""
    eng = AsyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        0, 0, eng)                  # all writes go through
    rng = np.random.default_rng(8)
    pws, shadow = [], {}
    for _ in range(5):
        ids = rng.integers(0, N_ROWS, 100)
        rows = _rows(rng, 100)
        pws.append(cache.write_planned(ids, rows, wait=False))
        ki, kr = keep_last_writer(ids, rows)
        shadow.update(zip(ki.tolist(), kr))
    cache.flush()                                   # no explicit completes
    sids = np.array(sorted(shadow))
    np.testing.assert_array_equal(wstore.read_rows(sids),
                                  np.stack([shadow[i] for i in sids]))
    for pw in pws:
        assert pw.done                              # barrier harvested them
    cache.close()
    eng.close()


def test_split_phase_flush_version_revalidation(wstore):
    """A row re-written while its flush ticket is in flight must STAY
    dirty (version-checked clear): the newer value survives to the next
    barrier instead of being silently dropped."""
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        32, 64, eng)
    resident = int(np.where(cache.loc < 2)[0][0])
    ids = np.array([resident])
    v1, v2 = _rows(np.random.default_rng(9), 2)
    cache.write_planned(ids, v1[None])
    assert cache.n_dirty == 1
    ef = cache.flush(wait=False)                    # barrier ticket in flight
    cache.write_planned(ids, v2[None])              # mid-flight re-write
    cache.flush_complete(ef)
    assert cache.n_dirty == 1                       # v2 still pending
    np.testing.assert_array_equal(cache.gather(ids), v2[None])
    fr = cache.flush()
    assert fr.rows == 1 and cache.n_dirty == 0
    np.testing.assert_array_equal(wstore.read_rows(ids), v2[None])
    cache.close()


def test_apply_delta_split_phase(wstore):
    eng = AsyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        64, 128, eng)
    ids = np.array([int(np.where(cache.loc == t)[0][0]) for t in (0, 1, 2)])
    base = cache.gather(ids).copy()
    pw = cache.apply_delta(ids, np.ones((3, ROW_DIM), np.float32),
                           wait=False)
    np.testing.assert_allclose(cache.gather(ids), base + 1, rtol=1e-6)
    res = cache.complete_write(pw)
    assert res.rows == 3
    cache.flush()
    np.testing.assert_allclose(wstore.read_rows(ids), base + 1, rtol=1e-6)
    cache.close()
    eng.close()


# ---------------------------------------------------------------------------
# write-combining buffer: small demotion batches coalesce into one ticket
# ---------------------------------------------------------------------------

def test_write_combiner_unit():
    from repro.core.writeback import WriteCombiner
    wc = WriteCombiner(min_rows=4)
    assert len(wc) == 0 and not wc.ready and wc.lookup(np.array([1])) is None
    wc.add(np.array([3, 1]), np.array([[3.0], [1.0]], np.float32))
    wc.add(np.array([1, 5]), np.array([[10.0], [5.0]], np.float32))
    assert len(wc) == 3 and not wc.ready            # id 1 merged, last wins
    mask, rows = wc.lookup(np.array([0, 1, 5]))
    np.testing.assert_array_equal(mask, [False, True, True])
    np.testing.assert_array_equal(rows[:, 0], [10.0, 5.0])
    assert list(wc.drop(np.array([5, 7]))) == [5]
    wc.add(np.array([2, 4]), np.array([[2.0], [4.0]], np.float32))
    assert wc.ready
    ids, rows = wc.take()
    assert len(wc) == 0
    got = dict(zip(ids.tolist(), rows[:, 0].tolist()))
    assert got == {3: 3.0, 1: 10.0, 2: 2.0, 4: 4.0}


def test_write_combined_demotions_one_ticket_and_overlay(wstore):
    """Small flush-on-demote batches land in the combiner (NO storage
    ticket), gathers overlay the buffered values over stale storage, and
    the flush barrier writes everything back in one batched ticket."""
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        0, 64, eng, write_combine_rows=256)
    rng = np.random.default_rng(10)
    cached = np.where(cache.loc == 1)[0]
    rows = _rows(rng, len(cached))
    cache.write_planned(cached, rows)
    wb0 = eng.stats.write_batches
    # demote EVERY cached row (inverted hotness): small batch -> combiner
    cache.refresh(np.arange(N_ROWS, dtype=float))
    assert eng.stats.write_batches == wb0           # no ticket issued
    assert (cache.loc[cached] == 2).all()
    assert cache.n_dirty == len(cached)             # combiner = freshest
    np.testing.assert_array_equal(cache.gather(cached), rows)   # overlay
    assert not np.array_equal(wstore.read_rows(cached), rows)   # storage stale
    fr = cache.flush()
    assert fr.rows == len(cached)
    assert eng.stats.write_batches == wb0 + 1       # ONE combined ticket
    assert cache.n_dirty == 0
    np.testing.assert_array_equal(wstore.read_rows(cached), rows)
    np.testing.assert_array_equal(cache.gather(cached), rows)
    cache.close()


def test_write_combiner_threshold_triggers_combined_ticket(wstore):
    """Accumulated small demotion batches exceed write_combine_rows ->
    exactly one combined ticket goes out, covering every buffered row."""
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        0, 48, eng, write_combine_rows=40)
    rng = np.random.default_rng(11)
    shadow = {}
    wb0 = eng.stats.write_batches
    # three refreshes, each dirtying + demoting 16 rows (< threshold)
    for r in range(3):
        hot = np.where(cache.loc == 1)[0][:16]
        rows = _rows(rng, len(hot))
        cache.write_planned(hot, rows)
        shadow.update(zip(hot.tolist(), rows))
        scores = np.arange(N_ROWS, dtype=float)
        scores[hot] = -1.0                           # demote exactly these
        cache.refresh(scores)
    # 16+16+16 = 48 >= 40: the third refresh released the combined ticket
    assert eng.stats.write_batches == wb0 + 1
    cache.flush()
    sids = np.array(sorted(shadow))
    np.testing.assert_array_equal(wstore.read_rows(sids),
                                  np.stack([shadow[i] for i in sids]))
    cache.close()


def test_close_drains_write_combiner(wstore):
    """close() without a flush barrier must still release the combiner —
    it holds the ONLY copy of demoted-dirty rows, and pre-combiner
    flush-on-demote persisted those values at demotion time."""
    eng = SyncIOEngine(wstore)
    with HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                     0, 32, eng, write_combine_rows=512) as cache:
        rng = np.random.default_rng(13)
        cached = np.where(cache.loc == 1)[0]
        rows = _rows(rng, len(cached))
        cache.write_planned(cached, rows)
        cache.refresh(np.arange(N_ROWS, dtype=float))   # demote into combiner
        assert cache.n_dirty == len(cached)             # buffer = only copy
    np.testing.assert_array_equal(wstore.read_rows(cached), rows)
    eng.close()


def test_write_combined_row_promotion_stays_dirty(wstore):
    """Promoting a write-combined row back into a tier takes the BUFFERED
    value (not stale storage), keeps it dirty, and a later flush makes
    storage agree."""
    eng = SyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        0, 32, eng, write_combine_rows=128)
    rng = np.random.default_rng(12)
    victim = int(cache._host_ids[0])
    row = _rows(rng, 1)
    cache.write_planned(np.array([victim]), row)
    scores = np.arange(N_ROWS, dtype=float)
    scores[victim] = -1.0
    cache.refresh(scores)                           # demote into combiner
    assert cache.loc[victim] == 2
    scores[victim] = float(N_ROWS * 10)
    cache.refresh(scores)                           # promote straight back
    assert cache.loc[victim] == 1
    np.testing.assert_array_equal(cache.gather(np.array([victim])), row)
    assert bool(cache.mut.is_dirty(np.array([victim]))[0])
    cache.flush()
    np.testing.assert_array_equal(wstore.read_rows(np.array([victim])), row)
    cache.close()


def test_random_interleaving_with_split_phase_and_combiner(wstore):
    """The shadow-model interleaving property, now with split-phase writes
    left in flight and the write combiner enabled: no interleaving of
    write/gather/refresh/flush/prefetch ever loses a value."""
    eng = AsyncIOEngine(wstore)
    cache = HeteroCache(wstore, np.arange(N_ROWS)[::-1].astype(float),
                        48, 96, eng, write_combine_rows=64)
    all_ids = np.arange(N_ROWS)
    shadow = wstore.read_rows(all_ids)
    rng = np.random.default_rng(0xBEEF)
    pending = []
    for step in range(40):
        op = rng.integers(0, 6)
        if op == 0:
            ids = rng.integers(0, N_ROWS, int(rng.integers(1, 64)))
            rows = _rows(rng, len(ids))
            pending.append(cache.write_planned(ids, rows, wait=False))
            ki, kr = keep_last_writer(ids, rows)
            shadow[ki] = kr
        elif op == 1:
            ids = rng.integers(0, N_ROWS, int(rng.integers(1, 64)))
            np.testing.assert_array_equal(cache.gather(ids), shadow[ids])
        elif op == 2:
            cache.refresh(rng.standard_normal(N_ROWS))
        elif op == 3:
            cache.flush()
            assert cache.n_dirty == 0
            np.testing.assert_array_equal(wstore.read_rows(all_ids), shadow)
        elif op == 4:
            cache.prefetch_rows(rng.integers(0, N_ROWS, 16))
        elif pending:
            cache.complete_write(pending.pop(rng.integers(0, len(pending))))
        np.testing.assert_array_equal(cache.gather(all_ids), shadow)
    cache.flush()
    np.testing.assert_array_equal(wstore.read_rows(all_ids), shadow)
    cache.close()
    eng.close()


# ---------------------------------------------------------------------------
# trainable embeddings ride the write path end to end
# ---------------------------------------------------------------------------

def test_trainer_embedding_writeback(tmp_path):
    from repro.gnn.graph import synth_graph
    from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig
    g = synth_graph(800, 6, skew=1.0, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=800, row_dim=8,
                         n_shards=3, create=True, rng_seed=1, writable=True)
    before = store.read_rows(np.arange(800)).copy()
    cfg = TrainerConfig(prefetch_depth=1, batch_size=32, fanouts=(3, 2),
                        hidden=8, presample_batches=2, train_embeddings=True,
                        embedding_lr=0.5, embedding_flush_every=2)
    with OutOfCoreGNNTrainer(g, store, cfg) as tr:
        out = tr.train(3)
    wb = out["writeback"]
    assert wb["written_rows"] > 0
    assert wb["dirty_after_flush"] == 0           # epoch barrier drained
    after = store.read_rows(np.arange(800))
    assert (np.abs(after - before).sum(axis=1) > 0).any()  # learned rows
    # a read-only store refuses the trainable-embedding config
    ro = FeatureStore(str(tmp_path / "f"), n_rows=800, row_dim=8, n_shards=3)
    with pytest.raises(ValueError):
        OutOfCoreGNNTrainer(g, ro, cfg)


def test_trainer_adam_table_rides_flush_barriers(tmp_path):
    """embedding_adam > 0 spins up the second-moment table; it flushes at
    the same barriers as the momentum table and drains at epoch end."""
    from repro.gnn.graph import synth_graph
    from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig
    g = synth_graph(800, 6, skew=1.0, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=800, row_dim=8,
                         n_shards=3, create=True, rng_seed=1, writable=True)
    cfg = TrainerConfig(prefetch_depth=1, batch_size=32, fanouts=(3, 2),
                        hidden=8, presample_batches=2, train_embeddings=True,
                        embedding_lr=0.5, embedding_flush_every=2,
                        embedding_momentum=0.9, embedding_adam=0.99)
    with OutOfCoreGNNTrainer(g, store, cfg) as tr:
        out = tr.train(3)
    for table in ("momentum", "adam"):
        wb = out["writeback"][table]
        assert wb["written_rows"] > 0
        assert wb["flushes"] > 0
        assert wb["dirty_after_flush"] == 0
    # the second moment is nonnegative by construction and nonzero where
    # gradients landed
    v2 = FeatureStore(str(tmp_path / "f_adam"), n_rows=800, row_dim=8,
                      n_shards=3).read_rows(np.arange(800))
    assert v2.min() >= 0.0 and (v2 > 0).any()


# ---------------------------------------------------------------------------
# sharded embedding checkpoints stream through submit_write
# ---------------------------------------------------------------------------

def test_embedding_checkpoint_roundtrip_bit_exact(tmp_path, wstore):
    rng = np.random.default_rng(5)
    wstore.write_rows(np.arange(N_ROWS), _rows(rng, N_ROWS))
    orig = wstore.read_rows(np.arange(N_ROWS)).copy()
    cm = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    man = cm.save_embeddings(3, wstore, chunk_rows=300, extra={"epoch": 3})
    assert man["geometry"]["n_rows"] == N_ROWS
    assert len(man["shards"]) == N_SHARDS
    # clobber the live table, restore, compare bit-exactly
    wstore.write_rows(np.arange(N_ROWS),
                      np.zeros((N_ROWS, ROW_DIM), np.float32))
    out = cm.restore_embeddings(wstore)
    np.testing.assert_array_equal(wstore.read_rows(np.arange(N_ROWS)), orig)
    assert out["extra"] == {"epoch": 3}
    assert cm.latest_embedding_step() == 3


def test_embedding_checkpoint_gc_and_corruption(tmp_path, wstore):
    import os
    cm = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    for s in (1, 2, 3):
        cm.save_embeddings(s, wstore, chunk_rows=512)
    assert cm.all_embedding_steps() == [2, 3]     # keep-k GC
    # flip one byte in a shard: a non-fallback restore must refuse,
    # and the default restore falls back to the newest INTACT step and
    # reports what it skipped
    p = os.path.join(str(tmp_path / "ckpt"), f"emb_{3:010d}",
                     "table", "shard_0.bin")
    blob = bytearray(open(p, "rb").read())
    blob[-1] ^= 0xFF
    open(p, "wb").write(bytes(blob))
    with pytest.raises(IOError):
        cm.restore_embeddings(wstore, step=3, fallback=False)
    out = cm.restore_embeddings(wstore, step=3)
    assert out["restored_step"] == 2
    assert [s["step"] for s in out["skipped"]] == [3]
    assert "corrupt" in out["skipped"][0]["error"]


def test_embedding_checkpoint_geometry_mismatch(tmp_path, wstore):
    cm = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    cm.save_embeddings(1, wstore)
    other = FeatureStore(str(tmp_path / "other"), n_rows=N_ROWS,
                         row_dim=ROW_DIM + 1, n_shards=N_SHARDS,
                         create=True, writable=True)
    with pytest.raises(ValueError):
        cm.restore_embeddings(other, step=1)
