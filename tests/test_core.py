"""Helios core: IO stack, heterogeneous cache, pipeline."""
import time

import numpy as np
import pytest

from repro.core.hetero_cache import HeteroCache
from repro.core.hotness import placement
from repro.core.iostack import (AsyncIOEngine, CPUManagedEngine, FeatureStore,
                                SyncIOEngine)
from repro.core.pipeline import Operator, PipelineExecutor
from repro.core.simulator import ArrayModel


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    p = tmp_path_factory.mktemp("feats")
    return FeatureStore(str(p), n_rows=4096, row_dim=32, n_shards=4,
                        create=True, rng_seed=0)


def test_feature_store_roundtrip(store):
    ids = np.array([0, 1, 5, 4095, 1024, 1024])
    rows = store.read_rows(ids)
    assert rows.shape == (6, 32)
    assert np.allclose(rows[4], rows[5])           # same id same row
    assert not np.allclose(rows[0], rows[1])


def test_async_engine_decoupled_submission(store):
    """Helios property: submit returns before completion (decoupled SQ/CQ)."""
    eng = AsyncIOEngine(store, worker_budget=0.3)
    ids = np.arange(2048)
    t0 = time.perf_counter()
    ticket = eng.submit(ids)
    submit_time = time.perf_counter() - t0
    data, virt = ticket.wait()
    assert submit_time < 0.05                      # non-blocking submit
    assert data.shape == (2048, 32)
    assert np.allclose(data, store.read_rows(ids))
    assert eng.stats.requests == 2048
    eng.close()


def test_async_beats_sync_virtual_throughput(store):
    """Decoupled async IO reaches higher modeled throughput than the
    BaM/GIDS-style coupled engine (paper Fig. 7)."""
    a = AsyncIOEngine(store, worker_budget=0.3)
    s = SyncIOEngine(store)
    ids = np.arange(4096)
    a.submit(ids).wait()
    s.submit(ids)
    assert a.stats.virtual_io_s < s.stats.virtual_io_s
    a.close()


def test_cpu_managed_slowest(store):
    c = CPUManagedEngine(store)
    s = SyncIOEngine(store)
    ids = np.arange(1024)
    c.submit(ids)
    s.submit(ids)
    assert c.stats.virtual_io_s > s.stats.virtual_io_s


def test_placement_hottest_on_device():
    hot = np.array([5, 1, 9, 7, 3, 0, 2, 8])
    loc, slot = placement(hot, device_rows=2, host_rows=3)
    assert loc[2] == 0 and loc[7] == 0             # hotness 9, 8 -> device
    assert set(np.where(loc == 1)[0]) == {0, 3, 4}  # 5, 7, 3 -> host
    assert loc[1] == 2 and loc[5] == 2


def test_hetero_cache_gather_correct(store):
    hot = np.arange(store.n_rows)[::-1].astype(np.int64)   # row 0 hottest
    cache = HeteroCache(store, hot, device_rows=256, host_rows=512)
    ids = np.array([0, 100, 300, 2000, 4000, 7])
    got = cache.gather(ids)
    ref = store.read_rows(ids)
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    assert cache.stats.device_hits > 0
    assert cache.stats.host_hits > 0
    assert cache.stats.storage_misses > 0


def test_cache_skew_hit_rate(store):
    """Skewed access + hotness placement -> high hit rate (paper: 10% cache
    removes ~70% of traffic on CL)."""
    rng = np.random.default_rng(0)
    # Zipfian accesses
    access = (rng.zipf(1.5, 20000) - 1) % store.n_rows
    hot = np.bincount(access, minlength=store.n_rows)
    cache = HeteroCache(store, hot, device_rows=205, host_rows=205)  # 10%
    ids = access[:4096]
    cache.gather(np.unique(ids))
    assert cache.stats.hit_rate > 0.5


def _sleeping_ops(secs):
    """A three-stage chain, one stage per resource, each sleeping ``secs``."""
    def nap(ctx):
        time.sleep(secs)
    return [Operator("a", nap, "host"),
            Operator("b", nap, "io", ("a",)),
            Operator("c", nap, "device", ("b",))]


def test_pipeline_overlap_beats_serial():
    """Batches in flight together overlap their stages on the host's wall
    clock (paper Fig. 11): depth 3 against one batch at a time."""
    walls = {}
    for depth in (1, 3):
        pipe = PipelineExecutor(_sleeping_ops(0.015), prefetch_depth=depth)
        walls[depth] = pipe.run(lambda i: {}, 10)["wall_s"]
        pipe.close()
    # one at a time: 10 x 45 ms; depth 3 fills the stages: ~10 x 15 + 30 ms
    assert walls[1] >= 10 * 0.045
    assert walls[3] < 0.75 * walls[1]


def test_pipeline_depth_one_runs_one_batch_at_a_time():
    """prefetch_depth=1: batch i+1's first operator starts after batch i's
    last one ended, while a batch's independent operators still run."""
    from repro.obs import trace as obs_trace

    def nap(ctx):
        time.sleep(0.002)
    ops = [Operator("s", nap, "host"),
           Operator("x", nap, "io", ("s",)),
           Operator("y", nap, "host", ("s",)),
           Operator("t", nap, "device", ("x", "y"))]
    prev = obs_trace.TRACER
    tr = obs_trace.install()
    try:
        pipe = PipelineExecutor(ops, prefetch_depth=1)
        out = pipe.run(lambda i: {}, 6)
        pipe.close()
    finally:
        obs_trace.TRACER = prev
    assert {k: v["calls"] for k, v in out["stages"].items()} == dict.fromkeys(
        "sxyt", 6)
    spans = [s for s in tr.spans if s.cat == "pipe"]
    by_batch = {}
    for s in spans:
        by_batch.setdefault(s.args["batch"], []).append(s)
    assert sorted(by_batch) == list(range(6))
    for i in range(5):
        assert (min(s.t0 for s in by_batch[i + 1])
                >= max(s.t1 for s in by_batch[i]))


def test_pipeline_rejects_depth_below_one():
    with pytest.raises(ValueError):
        PipelineExecutor(_sleeping_ops(0.0), prefetch_depth=0)


def test_pipeline_dependency_order():
    seen = []
    ops = [
        Operator("x", lambda ctx: seen.append("x"), "host", ()),
        Operator("y", lambda ctx: seen.append("y"), "io", ("x",)),
        Operator("z", lambda ctx: seen.append("z"), "device", ("y",)),
    ]
    pipe = PipelineExecutor(ops, prefetch_depth=1)
    pipe.run(lambda i: {}, 1)
    pipe.close()
    assert seen == ["x", "y", "z"]


def test_array_model_saturates_with_ssds():
    one = ArrayModel(1)
    twelve = ArrayModel(12)
    t1 = one.read_time(10000, 4096, 1024)
    t12 = twelve.read_time(10000, 4096, 1024)
    assert t12 < t1
    assert twelve.peak_bw(4096) >= 6 * one.peak_bw(4096)


def test_feature_store_round_robin_striping(store):
    """Striping is true round-robin: row i -> shard i % n_shards, so hot
    (low-id) prefixes spread evenly instead of saturating shard 0."""
    hot_ids = np.arange(1024)                       # a hot low-id prefix
    sid, off = store.locate(hot_ids)
    counts = np.bincount(sid, minlength=store.n_shards)
    assert counts.max() - counts.min() <= 1         # balanced to within 1
    np.testing.assert_array_equal(sid, hot_ids % store.n_shards)
    np.testing.assert_array_equal(off, hot_ids // store.n_shards)
    # shard files hold exactly the round-robin row counts
    for s, shard in enumerate(store.shards):
        assert shard.shape[0] == len(range(s, store.n_rows, store.n_shards))


def test_async_engine_close_joins_workers(store):
    eng = AsyncIOEngine(store, worker_budget=0.3)
    threads = list(eng._threads)
    assert threads and all(t.is_alive() for t in threads)
    eng.submit(np.arange(64)).wait()
    eng.close()
    assert not any(t.is_alive() for t in threads)
    eng.close()                                     # idempotent


def test_engines_are_context_managers(store):
    with AsyncIOEngine(store, worker_budget=0.3) as eng:
        data, _ = eng.submit(np.arange(32)).wait()
        assert data.shape == (32, store.row_dim)
    assert not eng._threads
    with SyncIOEngine(store) as eng:
        eng.submit(np.arange(8))


def test_hetero_cache_close_owns_engine(store):
    hot = np.arange(store.n_rows)[::-1].astype(np.int64)
    cache = HeteroCache(store, hot, device_rows=64, host_rows=64)
    owned = cache.io
    threads = list(owned._threads)
    cache.close()                                   # owns -> joins workers
    assert not any(t.is_alive() for t in threads)

    shared = AsyncIOEngine(store, worker_budget=0.3)
    cache = HeteroCache(store, hot, device_rows=64, host_rows=64,
                        io_engine=shared)
    cache.close()                                   # shared -> left running
    assert any(t.is_alive() for t in shared._threads)
    shared.close()


def test_presample_draws_unique_seeds():
    class SpySampler:
        def __init__(self):
            self.seen = []

        def sample(self, seeds):
            self.seen.append(seeds)
            from repro.gnn.sampling import MiniBatch
            return MiniBatch(seeds, np.ones(len(seeds), bool), [], seeds,
                             np.zeros(len(seeds), np.int64))

    from repro.core.hotness import presample_gnn
    spy = SpySampler()
    presample_gnn(spy, seeds_per_batch=64, n_batches=4, n_rows=100)
    assert len(spy.seen) == 4
    for seeds in spy.seen:
        assert len(np.unique(seeds)) == len(seeds)  # without replacement
        assert len(seeds) == 64


def test_cache_stats_zero_batch_hit_rate():
    from repro.core.hetero_cache import CacheStats
    st = CacheStats()
    assert st.hit_rate == 0.0                       # no division by zero
    assert st.virtual_batch_time(pipelined=True) == 0.0


def test_feature_store_rejects_unmarked_legacy_layout(tmp_path):
    """Reopening a store directory without the round-robin layout marker
    (i.e. written under the old contiguous partitioning) fails loudly
    instead of silently permuting rows."""
    import os
    p = str(tmp_path / "legacy")
    FeatureStore(p, n_rows=256, row_dim=8, n_shards=4, create=True,
                 rng_seed=0)
    # reopening a marked store is fine
    FeatureStore(p, n_rows=256, row_dim=8, n_shards=4, create=False)
    # reopening with different geometry (shard count) must also fail:
    # same scheme, different striping -> silently permuted rows otherwise
    with pytest.raises(ValueError, match="layout"):
        FeatureStore(p, n_rows=256, row_dim=8, n_shards=8, create=False)
    os.remove(os.path.join(p, "LAYOUT"))
    with pytest.raises(ValueError, match="layout"):
        FeatureStore(p, n_rows=256, row_dim=8, n_shards=4, create=False)


def test_presample_stream_decorrelated_from_trainer_batches():
    """Presample must NOT draw the same seed batches the trainer will
    train on (oracle placement would inflate measured hit rates)."""
    train_rng = np.random.default_rng(0)              # trainer's make_ctx
    train_batch = train_rng.choice(100, size=16, replace=False)

    class SpySampler:
        def __init__(self):
            self.seen = []

        def sample(self, seeds):
            self.seen.append(seeds)
            from repro.gnn.sampling import MiniBatch
            return MiniBatch(seeds, np.ones(len(seeds), bool), [], seeds,
                             np.zeros(len(seeds), np.int64))

    from repro.core.hotness import presample_gnn
    spy = SpySampler()
    presample_gnn(spy, seeds_per_batch=16, n_batches=1, n_rows=100, seed=0)
    assert not np.array_equal(spy.seen[0], train_batch)


def test_drain_waits_for_inflight_completion(store):
    """drain() uses join()/task_done() semantics: it must not return while
    a worker is still mid-read on the last popped item, so every ticket
    submitted before the drain has resolved when it returns."""
    eng = AsyncIOEngine(store, worker_budget=1.0)
    tickets = [eng.submit(np.arange(2048)) for _ in range(12)]
    eng.drain()
    assert all(tk.future.done() for tk in tickets)
    eng.close()


def test_async_engine_close_resolves_queued_tickets(store):
    """close() drains before stopping: every ticket submitted before the
    close resolves instead of stranding its waiter."""
    eng = AsyncIOEngine(store, worker_budget=0.3)
    tickets = [eng.submit(np.arange(256)) for _ in range(16)]
    eng.close()                                     # no waits in between
    for tk in tickets:
        data, _ = tk.wait()                         # must not deadlock
        assert data.shape == (256, store.row_dim)
