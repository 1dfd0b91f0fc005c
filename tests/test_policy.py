"""Cache-policy layer: online/oracle placement, tier migration invariants,
and the split-phase gather path shared by trainer and server."""
import numpy as np
import pytest

from repro.core.hetero_cache import HeteroCache
from repro.core.iostack import AsyncIOEngine, FeatureStore, SyncIOEngine
from repro.core.policy import (OnlineDecayPolicy, OracleOfflinePolicy,
                               StaticPresamplePolicy, make_policy, placement)

N_ROWS = 1024


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    p = tmp_path_factory.mktemp("policy_feats")
    return FeatureStore(str(p), n_rows=N_ROWS, row_dim=16, n_shards=4,
                        create=True, rng_seed=0)


def _cache(store, policy=None, dev=64, host=128, hot=None):
    return HeteroCache(store, hot, dev, host, io_engine=SyncIOEngine(store),
                       policy=policy)


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------

def test_placement_reexported_from_hotness():
    """Back-compat: ``hotness.placement`` is the policy-layer placement."""
    from repro.core import hotness
    assert hotness.placement is placement
    loc, slot = placement(np.array([5, 1, 9, 7, 3, 0, 2, 8]), 2, 3)
    assert loc[2] == 0 and loc[7] == 0
    assert set(np.where(loc == 1)[0]) == {0, 3, 4}


def test_static_policy_never_refreshes(store):
    cache = _cache(store, StaticPresamplePolicy(np.arange(N_ROWS)[::-1]))
    for _ in range(5):
        cache.gather(np.arange(100))
        assert cache.maybe_refresh() is None
    assert cache.stats.refreshes == 0


def test_online_policy_decay_and_cadence():
    pol = OnlineDecayPolicy(8, half_life=1.0, refresh_every=2)
    pol.record(np.array([0, 1]))
    assert not pol.refresh_due()                # cadence: not yet
    pol.record(np.array([0]))
    assert pol.refresh_due()
    s = pol.placement_scores()
    assert s[0] > s[1] > s[2] == 0.0            # 0 hit twice, 1 decayed once
    pol.refreshed()
    assert not pol.refresh_due()


def test_online_policy_hysteresis_boosts_residents():
    pol = OnlineDecayPolicy(4, refresh_every=1, hysteresis=0.5)
    pol.record(np.array([0, 1]))                # rows 0 and 1 tie
    loc = np.array([0, 2, 2, 2], np.int8)       # row 0 is the resident
    s = pol.placement_scores(loc)
    assert s[0] > s[1]                          # challenger must beat margin


def test_oracle_policy_places_by_upcoming_window():
    trace = [np.array([0, 1]), np.array([2, 3]),
             np.array([4, 5]), np.array([6, 7])]
    pol = OracleOfflinePolicy(8, trace, window=2)
    init = pol.initial_scores()
    assert init[[0, 1, 2, 3]].sum() == 4 and init[[4, 5, 6, 7]].sum() == 0
    pol.record(trace[0])
    assert not pol.refresh_due()
    pol.record(trace[1])
    assert pol.refresh_due()                    # window boundary
    nxt = pol.placement_scores()
    assert nxt[[4, 5, 6, 7]].sum() == 4 and nxt[[0, 1, 2, 3]].sum() == 0
    pol.record(trace[2])
    pol.record(trace[3])
    assert not pol.refresh_due()                # trace exhausted: no change


def test_make_policy_factory():
    assert make_policy("static", 8).name == "static"
    assert make_policy("online", 8).name == "online"
    assert make_policy("oracle", 8, trace=[np.array([0])]).name == "oracle"
    with pytest.raises(ValueError):
        make_policy("oracle", 8)                # oracle needs the trace
    with pytest.raises(ValueError):
        make_policy("belady", 8)


# ---------------------------------------------------------------------------
# tier migration
# ---------------------------------------------------------------------------

def _check_invariants(cache, store, dev, host):
    loc, slot = cache.loc, cache.slot
    # every row maps to exactly one tier, partitions exactly sized
    assert (loc == 0).sum() == dev and (loc == 1).sum() == host
    assert ((loc >= 0) & (loc <= 2)).all()
    # slot tables dense and consistent per tier
    for tier, rows in ((0, dev), (1, host)):
        s = np.sort(slot[loc == tier])
        np.testing.assert_array_equal(s, np.arange(rows))
    np.testing.assert_array_equal(np.sort(cache._dev_ids),
                                  np.where(loc == 0)[0])
    np.testing.assert_array_equal(np.sort(cache._host_ids),
                                  np.where(loc == 1)[0])
    # tier contents match the backing store row-for-row
    if dev:
        ids = np.where(loc == 0)[0]
        np.testing.assert_allclose(
            np.asarray(cache.device_tier)[slot[ids]], store.read_rows(ids),
            rtol=1e-6)
    if host:
        ids = np.where(loc == 1)[0]
        np.testing.assert_allclose(cache.host_tier[slot[ids]],
                                   store.read_rows(ids), rtol=1e-6)


@pytest.mark.parametrize("dev,host", [(64, 128), (0, 128), (64, 0)])
def test_refresh_sequences_preserve_invariants(store, dev, host):
    """After any sequence of refresh() calls every node id maps to exactly
    one tier, slot tables stay dense/consistent, and a full gather still
    returns rows identical to FeatureStore.read_rows."""
    rng = np.random.default_rng(7)
    cache = _cache(store, dev=dev, host=host, hot=rng.random(N_ROWS))
    all_ids = np.arange(N_ROWS)
    ref = store.read_rows(all_ids)
    for _ in range(5):
        res = cache.refresh(rng.random(N_ROWS))
        _check_invariants(cache, store, dev, host)
        np.testing.assert_allclose(cache.gather(all_ids), ref, rtol=1e-6)
        assert res.promotions >= 0 and res.demotions >= 0
    assert cache.stats.refreshes == 5
    cache.close()


def test_refresh_same_scores_moves_nothing(store):
    scores = np.random.default_rng(1).random(N_ROWS)
    cache = _cache(store, hot=scores)
    res = cache.refresh(scores)
    assert res.promotions == 0 and res.demotions == 0
    assert res.moved_bytes == 0 and res.virtual_s == 0.0


def test_refresh_migrates_through_io_tickets(store):
    """Storage-tier admissions ride the async engine (tagged tickets), and
    demoted rows leave the fast tiers."""
    eng = AsyncIOEngine(store, worker_budget=0.3)
    cache = HeteroCache(store, np.arange(N_ROWS)[::-1], 64, 128,
                        io_engine=eng)
    reqs_before = eng.stats.requests
    res = cache.refresh(np.arange(N_ROWS, dtype=float))   # reverse hotness
    assert eng.stats.requests > reqs_before               # rows pulled via IO
    assert res.promotions > 0 and res.demotions > 0
    assert res.virtual_s > 0
    _check_invariants(cache, store, 64, 128)
    eng.close()


def test_online_cache_tracks_hot_set_drift(store):
    pol = OnlineDecayPolicy(N_ROWS, half_life=2.0, refresh_every=2,
                            hysteresis=0.05)
    cache = _cache(store, pol)
    hot_a = np.arange(64)
    hot_b = np.arange(500, 564)
    for _ in range(4):
        cache.gather(hot_a)
        cache.maybe_refresh()
    assert (cache.loc[hot_a] == 0).mean() > 0.9           # A promoted to HBM
    for _ in range(6):
        cache.gather(hot_b)
        cache.maybe_refresh()
    assert (cache.loc[hot_b] == 0).mean() > 0.9           # B took over
    assert cache.stats.promotions > 0 and cache.stats.demotions > 0
    np.testing.assert_allclose(cache.gather(hot_b),
                               store.read_rows(hot_b), rtol=1e-6)


# ---------------------------------------------------------------------------
# split-phase gather (the one code path)
# ---------------------------------------------------------------------------

def test_split_phase_matches_store_and_accounts_once(store):
    cache = _cache(store, hot=np.arange(N_ROWS)[::-1])
    ids = np.array([0, 100, 300, 700, 1000, 7])
    pending = cache.submit_planned(ids)
    assert pending.n_device + pending.n_host + pending.n_storage == len(ids)
    cache.lookup_planned(pending)
    cache.lookup_planned(pending)                         # idempotent
    out = cache.complete_planned(pending)
    np.testing.assert_allclose(out, store.read_rows(ids), rtol=1e-6)
    st = cache.stats
    assert st.batches == 1                                # one accounting site
    assert st.device_hits + st.host_hits + st.storage_misses == len(ids)
    assert cache.complete_planned(pending) is out         # no double count
    assert st.batches == 1


def test_split_phase_padded_buffer_for_trainer(store):
    cache = _cache(store)
    ids = np.array([3, 9, 27])
    pending = cache.submit_planned(ids, n_rows=8)
    out = cache.complete_planned(pending)
    assert out.shape == (8, store.row_dim)
    np.testing.assert_allclose(out[:3], store.read_rows(ids), rtol=1e-6)
    assert (out[3:] == 0).all()                           # padding stays zero


def test_refresh_between_submit_and_complete_is_consistent(store):
    """A refresh landing mid-gather must not tear the in-flight request:
    the pending gather pinned its table/tier snapshot."""
    cache = _cache(store, hot=np.arange(N_ROWS)[::-1])
    ids = np.arange(0, N_ROWS, 3)
    pending = cache.submit_planned(ids)
    cache.refresh(np.arange(N_ROWS, dtype=float))         # full upheaval
    out = cache.complete_planned(pending)
    np.testing.assert_allclose(out, store.read_rows(ids), rtol=1e-6)


# ---------------------------------------------------------------------------
# policy-driven prefetch (hide the first miss)
# ---------------------------------------------------------------------------

def test_online_prefetch_candidates_rank_rising_storage_rows():
    pol = OnlineDecayPolicy(16, refresh_every=10**6)
    loc = np.full(16, 2, np.int8)
    loc[0] = 0                                      # row 0 already cached
    pol.record(np.array([0, 3, 5, 5]))              # 5 rises fastest
    cand = pol.prefetch_candidates(loc, k=8)
    assert list(cand) == [5, 3]                     # resident 0 excluded
    # the trend reference resets: no new accesses -> nothing rises
    assert len(pol.prefetch_candidates(loc, k=8)) == 0
    pol.record(np.array([7]))
    assert list(pol.prefetch_candidates(loc, k=8)) == [7]


def test_static_policy_never_prefetches():
    pol = StaticPresamplePolicy(np.arange(8)[::-1])
    assert len(pol.prefetch_candidates(np.full(8, 2, np.int8), 4)) == 0


def test_oracle_prefetch_candidates_from_upcoming_window():
    trace = [np.array([4, 4, 6]), np.array([6, 6])]
    pol = OracleOfflinePolicy(8, trace, window=2)
    loc = np.full(8, 2, np.int8)
    assert list(pol.prefetch_candidates(loc, 8)) == [6, 4]  # 6 hotter ahead
    loc[6] = 1                                      # already resident
    assert list(pol.prefetch_candidates(loc, 8)) == [4]


def test_prefetch_hides_first_miss(store):
    """Predicted-hot rows stop counting as storage misses: after one cold
    batch establishes the trend, maybe_prefetch() pulls those rows into the
    host tier and subsequent gathers hit without waiting for a refresh."""
    pol = OnlineDecayPolicy(N_ROWS, refresh_every=10**6)
    cache = _cache(store, pol, dev=0, host=128)
    hot = np.arange(500, 532)
    cache.gather(hot)                               # cold: all misses
    m0 = cache.stats.storage_misses
    assert m0 == len(hot)
    res = cache.maybe_prefetch(64)
    assert res is not None and res.rows == len(hot) and res.tier == "host"
    assert (cache.loc[hot] == 1).all()
    out = cache.gather(hot)                         # now served from DRAM
    assert cache.stats.storage_misses == m0         # no NEW misses
    assert cache.stats.refreshes == 0               # refresh played no part
    assert cache.stats.prefetches == 1
    np.testing.assert_allclose(out, store.read_rows(hot), rtol=1e-6)
    cache.close()


def test_prefetch_targets_device_tier_when_no_host(store):
    """GIDS-style device-only cache: prefetch admits into HBM instead."""
    pol = OnlineDecayPolicy(N_ROWS, refresh_every=10**6)
    cache = _cache(store, pol, dev=64, host=0)
    hot = np.arange(100, 116)
    cache.gather(hot)
    res = cache.maybe_prefetch(32)
    assert res is not None and res.tier == "device"
    assert (cache.loc[hot] == 0).all()
    np.testing.assert_allclose(cache.gather(hot), store.read_rows(hot),
                               rtol=1e-6)
    cache.close()


def test_prefetch_preserves_tier_invariants(store):
    pol = OnlineDecayPolicy(N_ROWS, refresh_every=10**6)
    cache = _cache(store, pol, dev=64, host=128)
    rng = np.random.default_rng(3)
    for _ in range(4):
        cache.gather(rng.integers(0, N_ROWS, 200))
        cache.maybe_prefetch(32)
    _check_invariants(cache, store, 64, 128)
    np.testing.assert_allclose(cache.gather(np.arange(N_ROWS)),
                               store.read_rows(np.arange(N_ROWS)), rtol=1e-6)
    cache.close()


def test_trainer_prefetch_operator_reduces_misses(tmp_path):
    """The prefetch operator wires through the trainer pipeline: prefetches
    happen and the same workload sees fewer storage misses than without
    the operator."""
    from repro.gnn.graph import synth_graph
    from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig
    g = synth_graph(4000, 8, skew=1.2, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=4000, row_dim=16,
                         n_shards=4, create=True, rng_seed=2)
    misses = {}
    # one batch at a time: with two in flight a prefetch races wall-clock
    # against the next batch's tier plan, so miss counts are
    # scheduler-dependent — depth 1 keeps the same operator wiring
    # deterministic
    for pf in (0, 64):
        cfg = TrainerConfig(prefetch_depth=1, batch_size=64,
                            fanouts=(4, 3), hidden=16, presample_batches=2,
                            cache_policy="online", refresh_every=10**6,
                            prefetch_rows=pf)
        with OutOfCoreGNNTrainer(g, store, cfg) as tr:
            out = tr.train(8)
        misses[pf] = out["cache"]["storage_misses"]
        if pf:
            assert out["cache"]["prefetches"] > 0
            assert out["cache"]["prefetched_rows"] > 0
    assert misses[64] < misses[0]


# ---------------------------------------------------------------------------
# Belady per-access oracle
# ---------------------------------------------------------------------------

def test_belady_scores_rank_by_next_use():
    from repro.core.policy import BeladyOraclePolicy
    trace = [np.array([3, 5]), np.array([5]), np.array([1])]
    pol = BeladyOraclePolicy(8, trace)
    s = pol.initial_scores()
    # next use: row 3 & 5 at batch 0 (score 1), row 1 at batch 2, rest never
    assert s[3] == s[5] == 1.0
    assert 0 < s[1] < 1.0
    assert s[0] == s[2] == 0.0
    pol.record(trace[0])                      # cursor -> 1
    s = pol.placement_scores()
    assert s[5] == 1.0                        # row 5 used again at batch 1
    assert s[3] == 0.0                        # row 3 never used again
    loc = np.full(8, 2, np.int8)
    assert list(pol.prefetch_candidates(loc, 8)) == [5, 1]  # soonest first
    pol.record(trace[1])
    pol.record(trace[2])
    assert not pol.refresh_due()              # trace exhausted


def test_belady_requires_trace():
    with pytest.raises(ValueError):
        make_policy("belady", 8)


def test_belady_empty_trace_scores_zero():
    pol = make_policy("belady", 8, trace=[])
    np.testing.assert_array_equal(pol.initial_scores(), np.zeros(8))
    np.testing.assert_array_equal(pol.placement_scores(), np.zeros(8))
    assert not pol.refresh_due()
    assert len(pol.prefetch_candidates(np.full(8, 2, np.int8), 4)) == 0


def test_belady_upper_bounds_windowed_oracle(store):
    """Acceptance: the per-access Belady oracle's hit rate upper-bounds the
    windowed OracleOfflinePolicy on the same drifting trace — the windowed
    cadence can only lose information."""
    rng = np.random.default_rng(1)
    base = rng.permutation(N_ROWS)
    p = 1.0 / (np.arange(N_ROWS) + 1.0) ** 1.2
    p /= p.sum()
    trace = [np.roll(base, (t // 6) * 400)[
        rng.choice(N_ROWS, size=256, p=p)] for t in range(24)]
    hit = {}
    for kind in ("oracle", "belady"):
        policy = make_policy(kind, N_ROWS, trace=trace, refresh_every=6)
        cache = _cache(store, policy, dev=50, host=100)
        for ids in trace:
            cache.complete_planned(cache.submit_planned(ids))
            cache.maybe_refresh()
        hit[kind] = cache.stats.hit_rate
        cache.close()
    assert hit["belady"] >= hit["oracle"]


# ---------------------------------------------------------------------------
# dirty-aware demotion scores
# ---------------------------------------------------------------------------

def test_online_write_bias_boosts_dirty_residents():
    pol = OnlineDecayPolicy(4, refresh_every=1, hysteresis=0.0,
                            write_bias=0.5)
    pol.record(np.array([0, 1, 2, 3]))
    loc = np.array([1, 1, 2, 2], np.int8)
    dirty = np.array([True, False, False, False])
    s = pol.placement_scores(loc, dirty=dirty)
    # equal access counts: the dirty resident outranks the clean one by
    # exactly the write bias (its demotion costs a flush write)
    assert s[0] == pytest.approx(1.5 * s[1])
    assert s[1] == s[2] == s[3]
    # without the bitmap behavior is unchanged
    s = pol.placement_scores(loc)
    assert s[0] == s[1]


def test_dirty_rows_survive_refresh_pressure(tmp_path):
    """End to end: with write_bias, a dirty resident row under mild score
    pressure stays cached (no flush), while with bias 0 it demotes."""
    wstore = FeatureStore(str(tmp_path / "wb"), n_rows=64, row_dim=4,
                          n_shards=2, create=True, rng_seed=0, writable=True)
    kept = {}
    for bias in (0.0, 10.0):
        pol = OnlineDecayPolicy(64, refresh_every=1, hysteresis=0.0,
                                write_bias=bias)
        cache = HeteroCache(wstore, None, 0, 8,
                            io_engine=SyncIOEngine(wstore), policy=pol)
        # establish residents 0..7, then dirty them
        for _ in range(4):
            cache.gather(np.arange(8))
            cache.maybe_refresh()
        assert (cache.loc[np.arange(8)] == 1).all()
        cache.write_planned(np.arange(8),
                            np.ones((8, 4), np.float32))
        # challengers 8..15 get marginally hotter access counts
        for _ in range(6):
            cache.gather(np.arange(8, 16))
            cache.maybe_refresh()
        kept[bias] = int((cache.loc[np.arange(8)] == 1).sum())
        cache.flush()
        cache.close()
    assert kept[10.0] > kept[0.0]             # bias kept dirty rows resident


# ---------------------------------------------------------------------------
# end-to-end: drifting hot set (benchmark acceptance, scaled down)
# ---------------------------------------------------------------------------

def test_drift_hit_rates_static_below_online_below_oracle(store):
    """Acceptance: under a drifting hot set the online policy strictly
    beats the static presample placement, and both are bounded above by
    the offline oracle."""
    rng = np.random.default_rng(0)
    base = rng.permutation(N_ROWS)
    p = 1.0 / (np.arange(N_ROWS) + 1.0) ** 1.2
    p /= p.sum()
    trace = [np.roll(base, (t // 6) * 400)[
        rng.choice(N_ROWS, size=256, p=p)] for t in range(24)]
    pres = np.zeros(N_ROWS)
    for b in trace[:3]:
        np.add.at(pres, b, 1.0)

    hit = {}
    for kind in ("static", "online", "oracle"):
        policy = make_policy(kind, N_ROWS, presample=pres, trace=trace,
                             refresh_every=3, half_life=4, hysteresis=0.05)
        cache = _cache(store, policy, dev=50, host=100)
        for ids in trace:
            cache.complete_planned(cache.submit_planned(ids))
            cache.maybe_refresh()
        hit[kind] = cache.stats.hit_rate
        cache.close()
    assert hit["online"] > hit["static"]
    assert hit["oracle"] >= hit["online"]


def test_trainer_online_policy_end_to_end(tmp_path):
    from repro.gnn.graph import synth_graph
    from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig
    g = synth_graph(3000, 8, skew=1.2, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=3000, row_dim=16,
                         n_shards=4, create=True, rng_seed=2)
    cfg = TrainerConfig(mode="helios", batch_size=64, fanouts=(4, 3),
                        hidden=16, presample_batches=2,
                        cache_policy="online", refresh_every=2)
    with OutOfCoreGNNTrainer(g, store, cfg) as tr:
        out = tr.train(8)
    assert out["cache"]["policy"] == "online"
    assert out["cache"]["refreshes"] > 0
    assert out["cache"]["hit_rate"] > 0
    assert np.isfinite(out["loss_last"])


def test_server_online_policy_refreshes_from_request_stream(tmp_path):
    from repro.gnn.graph import synth_graph
    from repro.serving import BULK, GNNInferenceServer, ServerConfig
    g = synth_graph(4000, 8, skew=1.2, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=4000, row_dim=16,
                         n_shards=4, create=True, rng_seed=1)
    rng = np.random.default_rng(5)
    hot_a, hot_b = np.arange(200), np.arange(2000, 2200)
    reqs = ([rng.choice(hot_a, 8, replace=False) for _ in range(12)]
            + [rng.choice(hot_b, 8, replace=False) for _ in range(12)])

    hit = {}
    for pol in ("static", "online"):
        cfg = ServerConfig(request_batch_size=8, fanouts=(4, 3), hidden=16,
                           device_cache_frac=0.05, host_cache_frac=0.10,
                           presample_batches=2, max_batch_requests=2,
                           cache_policy=pol, refresh_every=2, seed=0)
        with GNNInferenceServer(g, store, cfg) as srv:
            futs = [srv.submit(s, BULK, float(i)) for i, s in enumerate(reqs)]
            srv.flush()
            assert all(f.result() is not None for f in futs)
            hit[pol] = srv.cache.stats.hit_rate
            if pol == "online":
                assert srv.cache.stats.refreshes > 0
    assert hit["online"] > hit["static"]      # adapted to the drifted stream


def test_server_prefetch_reduces_storage_misses(tmp_path):
    """The server's ``prefetch_rows`` pulls rising storage rows into the
    tiers before requests ask for them: a Zipf request stream sees fewer
    storage misses with it than without."""
    from repro.gnn.graph import synth_graph
    from repro.serving import GNNInferenceServer, ServerConfig, zipf_workload
    g = synth_graph(4000, 8, skew=1.2, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=4000, row_dim=16,
                         n_shards=4, create=True, rng_seed=2)
    wl = zipf_workload(g.n_vertices, 24, 16, rate_rps=6e4,
                       degrees=g.degrees(), seed=1)
    misses = {}
    for pf in (0, 128):
        cfg = ServerConfig(request_batch_size=16, fanouts=(4, 3), hidden=16,
                           device_cache_frac=0.01, host_cache_frac=0.04,
                           presample_batches=2, max_batch_requests=4,
                           cache_policy="online", refresh_every=10**6,
                           prefetch_rows=pf, seed=0)
        with GNNInferenceServer(g, store, cfg) as srv:
            for seeds, arrival, klass in wl:
                srv.submit(seeds, klass, arrival)
            srv.flush()
            misses[pf] = srv.cache.stats.storage_misses
            if pf:
                assert srv.cache.stats.prefetched_rows > 0
    assert misses[128] < misses[0]
