"""Quickstart: the Helios components in ~70 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

import numpy as np

from repro import compile_cache
from repro.core.hetero_cache import HeteroCache
from repro.core.iostack import AsyncIOEngine, FeatureStore
from repro.core.policy import OnlineDecayPolicy

compile_cache.enable()
root = tempfile.mkdtemp(prefix="helios_quickstart_")

# 1. a "terabyte-scale" feature table striped over 12 storage shards (SSDs)
store = FeatureStore(f"{root}/features", n_rows=50_000, row_dim=256,
                     n_shards=12, create=True, rng_seed=0)
print(f"storage tier: {store.n_rows} rows x {store.row_dim} "
      f"({store.n_rows * store.row_bytes / 1e6:.0f} MB over {store.n_shards} shards)")

# 2. the async IO stack: decoupled submission / completion
io = AsyncIOEngine(store, worker_budget=0.3)     # "30% of cores"
ticket = io.submit(np.arange(10_000))            # returns immediately
print(f"submitted 10k reads (non-blocking); doing other work ...")
data, virtual_s = ticket.wait()
print(f"IO complete: {data.shape}, modeled time {virtual_s * 1e3:.2f} ms "
      f"({data.nbytes / virtual_s / 1e9:.1f} GB/s under the 12-SSD envelope)")

# 3. the heterogeneous cache: policy-placed HBM / host / storage tiers
rng = np.random.default_rng(0)
access = (rng.zipf(1.4, 200_000) - 1) % store.n_rows    # skewed accesses
hot = np.bincount(access, minlength=store.n_rows)
cache = HeteroCache(store, hot, device_rows=2_500, host_rows=5_000, io_engine=io)
batch = np.unique(access[:30_000])
feats = cache.gather(batch)
st = cache.stats
print(f"gathered {len(batch)} rows: {st.device_hits} device / {st.host_hits} "
      f"host / {st.storage_misses} storage (hit rate {st.hit_rate:.0%})")
print(f"tier times: device {st.virtual_device_s*1e3:.2f} ms, host "
      f"{st.virtual_host_s*1e3:.2f} ms, storage {st.virtual_storage_s*1e3:.2f} ms "
      f"-> pipelined batch time {st.virtual_batch_time(True)*1e3:.2f} ms")

# 4. online policy + tier migration: when the hot set drifts, the cache
# re-derives placement from the live access stream and migrates rows
policy = OnlineDecayPolicy(store.n_rows, init_scores=hot, half_life=4,
                           refresh_every=4, hysteresis=0.05)
cache = HeteroCache(store, None, device_rows=2_500, host_rows=5_000,
                    io_engine=io, policy=policy)
drifted = (access + 25_000) % store.n_rows               # hot set moved
for i in range(0, 120_000, 10_000):
    cache.gather(np.unique(drifted[i:i + 10_000])[:4_000])
    cache.maybe_refresh()
st = cache.stats
print(f"after drift: hit rate {st.hit_rate:.0%} with {st.refreshes} "
      f"refreshes, {st.promotions} promotions / {st.demotions} demotions "
      f"({st.migrated_bytes / 1e6:.0f} MB migrated asynchronously)")
io.close()
