"""Fused cache-lookup kernel (PR 7): interpret-mode bit-identity against
the host ``plan()`` path across engine modes, duplicate-heavy batches,
empty tiers, padded trainer batches, and the miss-partition property.
"""
import numpy as np
import pytest

from repro.core.hetero_cache import HeteroCache
from repro.core.iostack import (AsyncIOEngine, FeatureStore, SyncIOEngine)
from repro.distributed.partition import (PartitionedFeatureStore,
                                         make_partition)
from repro.distributed.remote_engine import RemoteIOEngine

N_ROWS, ROW_DIM = 1024, 16


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    p = tmp_path_factory.mktemp("fused_feats")
    return FeatureStore(str(p), n_rows=N_ROWS, row_dim=ROW_DIM,
                        n_shards=2, create=True, rng_seed=3)


def _batches(seed=0, n=4, dup=True):
    rng = np.random.default_rng(seed)
    out = [rng.integers(0, N_ROWS, 300) for _ in range(n)]
    if dup:
        # extreme duplication: 20 unique ids x 15 occurrences
        out.append(np.repeat(out[0][:20], 15))
    out.append(np.empty(0, np.int64))
    return out


# ---------------------------------------------------------------------------
# kernel <-> oracle equality (interpret mode; what CI exercises)
# ---------------------------------------------------------------------------

def _tables(rng, n, frac_dev=0.2, frac_host=0.3, remote=False):
    # the uncached rest splits 3:2 between storage and remote
    rest = 1 - frac_dev - frac_host
    loc = rng.choice([0, 1, 2, 3] if remote else [0, 1, 2], n,
                     p=[frac_dev, frac_host, 0.6 * rest, 0.4 * rest] if remote
                     else [frac_dev, frac_host, rest])
    loc = loc.astype(np.int32)
    slot = np.zeros(n, np.int64)
    for tier in (0, 1):
        m = loc == tier
        slot[m] = np.arange(m.sum())
    return loc, slot


@pytest.mark.parametrize("B,n,remote", [(1, 64, False), (57, 200, True),
                                        (256, 128, False), (97, 500, True)])
def test_kernel_matches_oracle(B, n, remote):
    from repro.kernels.cache_lookup.ops import fused_cache_lookup
    rng = np.random.default_rng(B + n)
    loc, slot = _tables(rng, n, remote=remote)
    dev = rng.normal(size=((loc == 0).sum(), ROW_DIM)).astype(np.float32)
    host = rng.normal(size=((loc == 1).sum(), ROW_DIM)).astype(np.float32)
    ids = rng.integers(0, n, B)
    ref = fused_cache_lookup(ids, loc, slot, dev, host, use_pallas=False)
    ker = fused_cache_lookup(ids, loc, slot, dev, host, use_pallas=True,
                             interpret=True)
    for name, a, b in zip(("out", "first_idx", "miss_ids", "miss_dest",
                           "rem_ids", "rem_dest", "counts"), ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    # first_idx against numpy's unique
    _, first, inv = np.unique(ids, return_index=True, return_inverse=True)
    np.testing.assert_array_equal(np.asarray(ref[1]), first[inv])


def test_kernel_empty_tiers():
    """Every id on storage: both cache tiers are empty (padded to one zero
    row inside ops) and the miss list covers the whole deduped batch."""
    from repro.kernels.cache_lookup.ops import fused_cache_lookup
    ids = np.array([5, 3, 5, 5, 9])
    loc = np.full(16, 2, np.int32)
    slot = np.zeros(16, np.int64)
    empty = np.zeros((0, ROW_DIM), np.float32)
    for use_pallas in (False, True):
        out, fi, mid, mdst, rid, rdst, cnt = fused_cache_lookup(
            ids, loc, slot, empty, empty, use_pallas=use_pallas,
            interpret=True)
        assert np.asarray(out).sum() == 0
        assert int(np.asarray(cnt)[0]) == 3 and int(np.asarray(cnt)[1]) == 0
        np.testing.assert_array_equal(np.asarray(mid)[:3], [5, 3, 9])
        np.testing.assert_array_equal(np.asarray(mdst)[:3], [0, 1, 4])


# ---------------------------------------------------------------------------
# cache-level bit-identity: fused (host + pallas-interpret) vs plan() path
# ---------------------------------------------------------------------------

def _run(cache, batches, n_rows=None):
    outs = [cache.complete_planned(
        cache.submit_planned(b, n_rows=n_rows)).copy() for b in batches]
    st = cache.stats
    occ = (st.device_hits, st.host_hits, st.storage_misses, st.remote_hits)
    return outs, occ


@pytest.mark.parametrize("engine", ["sync", "striped", "legacy"])
def test_fused_bit_identical_engine_modes(store, engine):
    def make():
        if engine == "sync":
            return SyncIOEngine(store)
        return AsyncIOEngine(store, striped=engine == "striped")

    batches = _batches()
    ref = [store.read_rows(np.asarray(b)) for b in batches]
    got = {}
    for mode, kw in [("plan", dict(fused=False)),
                     ("host", dict(fused=True, fused_backend="host")),
                     ("pallas", dict(fused=True,
                                     fused_backend="pallas-interpret"))]:
        eng = make()
        cache = HeteroCache(store, None, 100, 200, eng, **kw)
        got[mode] = _run(cache, batches)
        for o, r in zip(got[mode][0], ref):
            np.testing.assert_array_equal(o, r, err_msg=f"{engine}/{mode}")
        if hasattr(eng, "close"):
            eng.close()
    # occurrence-based tier stats agree exactly across all three paths
    assert got["plan"][1] == got["host"][1] == got["pallas"][1]


def test_fused_bit_identical_remote_mode(tmp_path):
    """Four-tier lookup (device/host/storage/remote) under RemoteIOEngine:
    the fused miss lists split identically and gathers stay bit-exact."""
    pstore = PartitionedFeatureStore(
        str(tmp_path / "p"), N_ROWS, ROW_DIM,
        make_partition("hash", N_ROWS, 4), n_shards=2, create=True,
        rng_seed=7)
    batches = _batches(seed=5)
    ref = [pstore.read_rows(np.asarray(b)) for b in batches]
    occs = {}
    for mode, kw in [("plan", dict(fused=False)),
                     ("host", dict()),
                     ("pallas", dict(fused_backend="pallas-interpret"))]:
        with RemoteIOEngine(pstore, me=0) as eng:
            cache = HeteroCache(pstore, None, 64, 128, eng, **kw)
            outs, occ = _run(cache, batches)
            occs[mode] = occ
            assert occ[3] > 0           # remote tier actually exercised
            for o, r in zip(outs, ref):
                np.testing.assert_array_equal(o, r, err_msg=mode)
    assert occs["plan"] == occs["host"] == occs["pallas"]


def test_fused_padded_trainer_batches(store):
    """n_rows > len(ids): the trainer pads minibatch buffers; rows past the
    batch stay zero and the gathered prefix is exact."""
    ids = np.repeat(np.arange(40), 3)
    for kw in (dict(fused=False), dict(), dict(fused_backend="pallas-interpret")):
        eng = AsyncIOEngine(store)
        cache = HeteroCache(store, None, 100, 200, eng, **kw)
        out = cache.complete_planned(cache.submit_planned(ids, n_rows=160))
        np.testing.assert_array_equal(out[:120], store.read_rows(ids))
        assert np.all(out[120:] == 0)
        eng.close()


def test_fused_dedup_shrinks_io(store):
    """The fused path's whole point: duplicate-heavy batches submit each
    missed row ONCE.  Engine request counts must drop by the dup factor
    while occurrence-based cache stats stay unchanged."""
    ids = np.repeat(np.arange(300, 500), 4)        # cold rows x4
    reqs = {}
    for mode, kw in [("plan", dict(fused=False)), ("host", dict())]:
        eng = AsyncIOEngine(store, striped=False)
        cache = HeteroCache(store, None, 100, 200, eng, **kw)
        cache.gather(ids)
        reqs[mode] = (eng.stats.requests, cache.stats.storage_misses)
        eng.close()
    assert reqs["plan"][1] == reqs["host"][1]      # occurrence stats equal
    assert reqs["host"][0] * 4 <= reqs["plan"][0]  # IO requests deduped


@pytest.mark.parametrize("n_rows,batch", [(1024, 43_009), (126_977, 8)])
def test_pallas_backend_rejects_past_smem(tmp_path, n_rows, batch):
    """The kernel's scalar memory cannot hold the tables and the batch:
    the cache refuses with a ValueError before any compile or IO."""
    big = FeatureStore(str(tmp_path / "big"), n_rows=n_rows, row_dim=4,
                       n_shards=2, create=True)
    eng = SyncIOEngine(big)
    cache = HeteroCache(big, None, 16, 16, eng,
                        fused_backend="pallas-interpret")
    with pytest.raises(ValueError, match="scalar memory"):
        cache.submit_planned(np.arange(batch) % n_rows)


# ---------------------------------------------------------------------------
# hypothesis property: hits + miss list partition the input batch
# ---------------------------------------------------------------------------

try:
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
    from hypothesis import given, settings

    @given(ids=hnp.arrays(np.int64, st.integers(1, 300),
                          elements=st.integers(0, 255)),
           fracs=st.tuples(st.floats(0, 0.45), st.floats(0, 0.45)))
    @settings(max_examples=25, deadline=None)
    def test_miss_list_partitions_batch(ids, fracs):
        """miss-list ids ∪ hit ids == input ids, with no overlap: every
        input id is EITHER gathered from a cache tier (device/host) or
        appears in exactly one of the deduplicated miss legs."""
        from repro.kernels.cache_lookup.ops import fused_cache_lookup
        rng = np.random.default_rng(int(ids.sum()) % 2**31)
        n = 256
        loc, slot = _tables(rng, n, fracs[0], fracs[1], remote=True)
        dev = rng.normal(size=(max((loc == 0).sum(), 0), 4)) \
            .astype(np.float32)
        host = rng.normal(size=(max((loc == 1).sum(), 0), 4)) \
            .astype(np.float32)
        out, fi, mid, mdst, rid, rdst, cnt = (
            np.asarray(x) for x in fused_cache_lookup(
                ids, loc, slot, dev, host, use_pallas=True, interpret=True))
        nm, nr = int(cnt[0]), int(cnt[1])
        miss = set(mid[:nm]) | set(rid[:nr])
        hits = {int(i) for i in ids if loc[i] <= 1}
        assert not miss & hits                       # no overlap
        assert miss | hits == set(int(i) for i in ids)   # full cover
        assert len(set(mid[:nm]) & set(rid[:nr])) == 0   # legs disjoint
        # dests point at FIRST occurrences of their ids
        for v, d in list(zip(mid[:nm], mdst[:nm])) + \
                list(zip(rid[:nr], rdst[:nr])):
            assert ids[d] == v and fi[d] == d
except ImportError:                                  # pragma: no cover
    pass
