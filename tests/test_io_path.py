"""Striped/coalesced storage read path: per-shard SQs, range coalescing,
ticket aggregation, engine/cache stats agreement, bounded seed draws."""
import numpy as np
import pytest

from repro.core.hetero_cache import HeteroCache
from repro.core.iostack import (AsyncIOEngine, CPUManagedEngine, FeatureStore,
                                SyncIOEngine, coalesce_offsets)
from repro.gnn.sampling import draw_unique

N_ROWS, ROW_DIM, N_SHARDS = 4096, 32, 4


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    p = tmp_path_factory.mktemp("iopath_feats")
    return FeatureStore(str(p), n_rows=N_ROWS, row_dim=ROW_DIM,
                        n_shards=N_SHARDS, create=True, rng_seed=0)


# ---------------------------------------------------------------------------
# coalescing: sorted offsets merge into sequential ranges
# ---------------------------------------------------------------------------

def _ranges(offsets, gap):
    order, bounds = coalesce_offsets(np.asarray(offsets), gap)
    so = np.asarray(offsets)[order]
    return [(int(so[lo]), int(so[hi - 1]) + 1)
            for lo, hi in zip(bounds[:-1], bounds[1:])]


def test_coalesce_empty_and_single():
    order, bounds = coalesce_offsets(np.empty(0, np.int64), 8)
    assert len(order) == 0 and list(bounds) == [0]
    assert _ranges([7], 0) == [(7, 8)]              # single row: one range


def test_coalesce_gap_semantics():
    # adjacent rows always merge; gap counts UNREQUESTED rows in between
    assert _ranges([0, 1, 2], 0) == [(0, 3)]
    assert _ranges([0, 2, 4], 0) == [(0, 1), (2, 3), (4, 5)]
    assert _ranges([0, 2, 4], 1) == [(0, 5)]        # 1 waste row per join
    assert _ranges([0, 2, 4], 2) == [(0, 5)]
    assert _ranges([0, 10], 8) == [(0, 1), (10, 11)]
    assert _ranges([0, 10], 9) == [(0, 11)]
    # duplicates share a range, unsorted input is sorted first
    assert _ranges([5, 5, 5], 0) == [(5, 6)]
    assert _ranges([9, 0, 1], 0) == [(0, 2), (9, 10)]


def test_coalesce_whole_shard_run(store):
    """A request covering one full shard coalesces to exactly ONE range."""
    eng = AsyncIOEngine(store, coalesce_gap=0)
    shard0 = np.arange(0, N_ROWS, N_SHARDS)         # every row of shard 0
    r0 = eng.stats.ranges
    data, _ = eng.submit(shard0).wait()
    assert eng.stats.ranges - r0 == 1
    assert eng.stats.span_bytes == len(shard0) * store.row_bytes
    np.testing.assert_array_equal(data, store.read_rows(shard0))
    eng.close()


def test_submit_splits_by_shard_and_skips_empty_shards(store):
    """One SQE batch per shard HIT; shards with no rows get none."""
    eng = AsyncIOEngine(store)
    tk = eng.submit(np.array([0, 4, 8]))            # all on shard 0
    tk.wait()
    assert tk.shards == 1
    tk = eng.submit(np.array([0, 1, 2, 3, 4]))      # shards 0-3
    tk.wait()
    assert tk.shards == N_SHARDS
    tk = eng.submit(np.array([], np.int64))         # empty: resolves at once
    data, virt = tk.wait()
    assert tk.shards == 0 and len(data) == 0 and virt == 0.0
    eng.close()


# ---------------------------------------------------------------------------
# correctness: striped+coalesced gathers match FeatureStore.read_rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gap", [0, 3, 64])
def test_striped_gather_matches_read_rows(store, gap):
    rng = np.random.default_rng(1)
    eng = AsyncIOEngine(store, coalesce_gap=gap)
    for ids in (np.arange(N_ROWS),                  # every row
                rng.integers(0, N_ROWS, 999),       # duplicates included
                np.array([N_ROWS - 1]),
                rng.permutation(N_ROWS)[:317]):
        data, _ = eng.submit(ids).wait()
        np.testing.assert_array_equal(data, store.read_rows(ids))
        # scatter form: caller-provided buffer and destination rows
        out = np.zeros((len(ids) + 5, ROW_DIM), store.dtype)
        eng.submit(ids, out, np.arange(len(ids)) + 5).wait()
        np.testing.assert_array_equal(out[5:], store.read_rows(ids))
    eng.close()


def _shard_ids(shard, offsets):
    return np.asarray(offsets, np.int64) * N_SHARDS + shard


_GATHER_CASES = {
    # duplicates inside one shard and across shards
    "duplicates": np.concatenate([_shard_ids(0, [5, 5, 9, 5, 300]),
                                  _shard_ids(2, [7, 7, 7, 1])]),
    # adjacent runs, runs split by gaps of 1..12 rows, shuffled
    "runs": np.random.default_rng(3).permutation(np.concatenate(
        [_shard_ids(1, np.r_[0:40, 41:60, 70:80, 92:93, 200:230]),
         _shard_ids(3, np.r_[10:20, 29:31, 500:520])])),
    # one row on shard 0, one on shard 3, shards 1 and 2 empty
    "one_row_shards": np.concatenate([_shard_ids(0, [17]),
                                      _shard_ids(3, [N_ROWS // N_SHARDS - 1])]),
}


@pytest.mark.parametrize("gap", [0, 8, "adaptive"])
@pytest.mark.parametrize("case", sorted(_GATHER_CASES))
def test_striped_gather_rows_and_accounting_exact(store, case, gap):
    """The per-shard gather returns exactly ``read_rows``' bytes, and the
    modelled device sees exactly the ranges ``coalesce_offsets`` forms:
    ``IOStats.ranges``, ``span_bytes`` and the ticket's virtual seconds are
    reckoned here, independently, from the same offsets."""
    from repro.core.iostack import pick_coalesce_gap
    from repro.core.simulator import SSDModel
    ids = _GATHER_CASES[case]
    eng = AsyncIOEngine(store, coalesce_gap=gap, chaos=None)
    ssd = SSDModel(eng.env)
    qd = int(256 * min(1.0, eng.worker_budget / 0.3))
    sid, off = store.locate(ids)
    want_ranges = want_span = 0
    want_virt = 0.0
    for s in np.unique(sid):
        offs = off[sid == s]
        g = (pick_coalesce_gap(offs, eng.max_coalesce_gap, eng.amp_cap)
             if gap == "adaptive" else gap)
        rs = _ranges(offs, g)
        span = sum(e - b for b, e in rs) * store.row_bytes
        want_ranges += len(rs)
        want_span += span
        want_virt = max(want_virt, ssd.range_io_time(len(rs), span, qd))
    want_virt = max(want_virt, want_span / eng.env.pcie_bw)
    want_rows = store.read_rows(ids)

    data, virt = eng.submit(ids).wait()
    np.testing.assert_array_equal(data, want_rows)
    assert virt == want_virt
    # scatter form: caller-provided buffer, shuffled destination rows
    out = np.full((len(ids) + 3, ROW_DIM), -1, store.dtype)
    dest = np.random.default_rng(4).permutation(len(ids)) + 3
    _, virt2 = eng.submit(ids, out, dest).wait()
    np.testing.assert_array_equal(out[dest], want_rows)
    assert (out[:3] == -1).all()                    # untouched rows stay
    assert virt2 == want_virt
    st = eng.stats
    assert st.ranges == 2 * want_ranges
    assert st.span_bytes == 2 * want_span
    assert st.virtual_io_s == pytest.approx(2 * want_virt, rel=1e-12)
    eng.close()


@pytest.mark.parametrize("chaos", [None, "transient"])
def test_striped_read_gathers_once_per_shard_batch(store, monkeypatch, chaos):
    """Regression guard: the host copy is one gather per shard batch, never
    one memmap slice per coalesced range — with or without retried
    transient faults, and still bit-exact."""
    from repro.ft.chaos import ChaosSchedule
    ids = np.random.default_rng(5).choice(N_ROWS, 3000, replace=False)
    want = store.read_rows(ids)
    ch = (ChaosSchedule(seed=11, read_error_rate=0.3)
          if chaos == "transient" else None)
    eng = AsyncIOEngine(store, coalesce_gap=0, chaos=ch)
    calls = []
    getitem = np.memmap.__getitem__

    def counting(self, index):
        calls.append(1)
        return getitem(self, index)

    monkeypatch.setattr(np.memmap, "__getitem__", counting)
    reads = 4 if chaos else 1
    for _ in range(reads):
        data, _ = eng.submit(ids).wait()
        np.testing.assert_array_equal(data, want)
    monkeypatch.undo()
    st = eng.stats
    assert st.ranges > 10 * st.shard_batches       # scattered: many ranges
    assert len(calls) <= st.shard_batches
    if chaos:
        assert st.retries > 0 and st.transient_errors > 0
    eng.close()


def test_striped_coalesced_beats_legacy_2x_on_skew(store):
    """Acceptance: >=2x effective storage bandwidth (virtual time) over the
    PR-2 single-queue path on a skewed workload."""
    rng = np.random.default_rng(0)
    p = 1.0 / (np.arange(N_ROWS) + 1.0) ** 1.1
    p /= p.sum()
    batches = [np.unique(rng.choice(N_ROWS, size=4 * N_ROWS, p=p))
               for _ in range(2)]
    bw = {}
    for label, kw in (("legacy", dict(striped=False)),
                      ("coalesced", dict(striped=True, coalesce_gap=8))):
        eng = AsyncIOEngine(store, **kw)
        for b in batches:
            eng.submit(b).wait()
        bw[label] = eng.stats.bw()
        eng.close()
    assert bw["coalesced"] >= 2.0 * bw["legacy"]


def test_ticket_virtual_time_is_max_over_parallel_shards(store):
    """Shards progress in parallel: a batch striped over all shards costs
    ~the slowest shard, not the sum — 4 shards' worth of rows on one shard
    must cost MORE than the same rows striped over all four."""
    eng = AsyncIOEngine(store, coalesce_gap=0)
    rows_per = 256
    one_shard = np.arange(0, rows_per * N_SHARDS * N_SHARDS, N_SHARDS)
    striped = np.arange(rows_per * N_SHARDS)        # round-robin: all shards
    _, virt_one = eng.submit(one_shard).wait()
    _, virt_striped = eng.submit(striped).wait()
    eng.close()
    # same row count; the single-shard batch coalesces to one bigger range
    # but still serializes on one SSD, so it cannot beat 4-way parallelism
    assert virt_striped < virt_one


# ---------------------------------------------------------------------------
# satellite: cache stats agree with engine stats in every mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [
    lambda s: AsyncIOEngine(s),
    lambda s: AsyncIOEngine(s, striped=False),
    lambda s: SyncIOEngine(s),
    lambda s: CPUManagedEngine(s),
], ids=["async", "async-legacy", "gids", "cpu"])
def test_cache_storage_virtual_matches_engine(store, make):
    """complete_planned accounts the virtual seconds the ticket actually
    resolved with, so cache storage time == engine IO time exactly —
    including the CPU engine's staging overhead and the async engine's
    coalesced time (previously recomputed at full queue depth)."""
    eng = make(store)
    cache = HeteroCache(store, np.arange(N_ROWS)[::-1], 128, 256, eng)
    v0 = eng.stats.virtual_io_s
    for ids in (np.arange(0, N_ROWS, 3), np.arange(512),   # hits only
                np.arange(N_ROWS - 64, N_ROWS)):
        cache.gather(ids)
    assert cache.stats.virtual_storage_s == pytest.approx(
        eng.stats.virtual_io_s - v0, abs=1e-12)
    assert cache.stats.storage_misses > 0
    cache.close()
    eng.close()


def test_pending_gather_exposes_ticket_virt(store):
    eng = AsyncIOEngine(store)
    cache = HeteroCache(store, np.arange(N_ROWS)[::-1], 64, 64, eng)
    pg = cache.submit_planned(np.arange(N_ROWS - 256, N_ROWS))  # all misses
    cache.complete_planned(pg)
    assert pg.storage_virt > 0
    pg_hit = cache.submit_planned(np.array([0, 1]))             # all hits
    cache.complete_planned(pg_hit)
    assert pg_hit.storage_virt == 0.0
    cache.close()
    eng.close()


# ---------------------------------------------------------------------------
# satellite: bounded-cost unique seed draw
# ---------------------------------------------------------------------------

def test_draw_unique_contract():
    rng = np.random.default_rng(0)
    for n, k in ((10, 10), (10, 0), (100, 7), (1 << 20, 1024)):
        ids = draw_unique(rng, n, k)
        assert len(ids) == k
        assert len(np.unique(ids)) == k
        if k:
            assert ids.min() >= 0 and ids.max() < n
    with pytest.raises(ValueError):
        draw_unique(rng, 4, 5)


def test_draw_unique_is_uniform_enough():
    """Every id is reachable and the draw is not grossly biased: over many
    sparse draws each id's hit count stays within a loose band of uniform."""
    rng = np.random.default_rng(2)
    n, k, reps = 64, 4, 4000
    counts = np.bincount(
        np.concatenate([draw_unique(rng, n, k) for _ in range(reps)]),
        minlength=n)
    expect = reps * k / n
    assert counts.min() > 0.6 * expect
    assert counts.max() < 1.4 * expect


def test_trainer_draws_bounded_unique_seeds(tmp_path):
    from repro.gnn.graph import synth_graph
    from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig
    g = synth_graph(3000, 8, skew=1.0, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=3000, row_dim=16,
                         n_shards=4, create=True, rng_seed=1)
    cfg = TrainerConfig(mode="helios", batch_size=64, fanouts=(4, 3),
                        hidden=16, presample_batches=2)
    with OutOfCoreGNNTrainer(g, store, cfg) as tr:
        seen = []
        orig = tr.sampler.sample

        def spy(seeds):
            seen.append(np.asarray(seeds))
            return orig(seeds)

        tr.sampler.sample = spy
        tr.train(3)
    assert len(seen) == 3
    for seeds in seen:
        assert len(seeds) == 64
        assert len(np.unique(seeds)) == 64          # sampler contract holds
        assert seeds.max() < 3000
