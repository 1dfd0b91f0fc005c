"""Hypothesis property tests on system invariants."""
import pytest

pytest.importorskip("hypothesis")   # optional dep: skip, don't abort collection

import hypothesis.extra.numpy as hnp
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core.hotness import placement
from repro.launch.hlo_cost import _parse_op_line, _shape_bytes, _parse_shapes
from repro.models.moe import MoEConfig, router_weights
from repro.models.steps import fused_xent

SET = dict(max_examples=25, deadline=None)


@given(hot=hnp.arrays(np.int64, st.integers(4, 60),
                      elements=st.integers(0, 1000)),
       frac=st.tuples(st.floats(0, 0.5), st.floats(0, 0.5)))
@settings(**SET)
def test_placement_partition(hot, frac):
    n = len(hot)
    d, h = int(n * frac[0]), int(n * frac[1])
    loc, slot = placement(hot, d, h)
    # partition sizes exact
    assert (loc == 0).sum() == d and (loc == 1).sum() == h
    # every device row is at least as hot as every storage row
    if d and (loc == 2).any():
        assert hot[loc == 0].min() >= hot[loc == 2].max() - 0  # ties allowed
    # slots within tiers are unique
    for tier in (0, 1):
        s = slot[loc == tier]
        assert len(np.unique(s)) == len(s)


@given(logits=hnp.arrays(np.float32, st.tuples(st.integers(1, 4),
                                               st.integers(2, 30)),
                         elements=st.floats(-5, 5, width=32)))
@settings(**SET)
def test_fused_xent_matches_naive(logits):
    labels = np.arange(logits.shape[0]) % logits.shape[1]
    nll, _ = fused_xent(jnp.asarray(logits)[None], jnp.asarray(labels)[None])
    # naive
    lse = jax.nn.logsumexp(jnp.asarray(logits), axis=-1)
    gold = jnp.take_along_axis(jnp.asarray(logits),
                               jnp.asarray(labels)[:, None], axis=1)[:, 0]
    naive = jnp.mean(lse - gold)
    assert abs(float(nll) - float(naive)) < 1e-4


_router = jax.jit(router_weights, static_argnums=(1, 2))


@given(bs=st.integers(1, 3), sl=st.integers(1, 8), e=st.integers(4, 16),
       k=st.integers(1, 4), seed=st.integers(0, 99))
@settings(**SET)
def test_router_weights_invariants(bs, sl, e, k, seed):
    k = min(k, e)
    # one draw at the largest shape, sliced: one compile for every example
    logits = np.asarray(jax.random.normal(jax.random.key(seed),
                                          (3, 8, 16)))[:bs, :sl, :e]
    mcfg = MoEConfig(n_experts=e, top_k=k, d_expert=8)
    topw, topi, aux, z = (np.asarray(a) for a in _router(logits, mcfg, e))
    assert topw.shape == (bs, sl, k)
    # normalized non-negative weights
    assert float(np.min(topw)) >= 0
    np.testing.assert_allclose(topw.sum(-1), 1.0, rtol=1e-5)
    # indices valid + unique per token
    assert int(topi.max()) < e
    for b in range(bs):
        for s in range(sl):
            assert len(np.unique(topi[b, s])) == k
    # the load-balance loss is aux = E * sum_e me_e * ce_e, me_e the mean
    # router probability of expert e and ce_e the share of tokens whose
    # top-1 choice is e.  A token's top-1 probability is at least 1/E, so
    # me_e >= ce_e / E and aux >= sum_e ce_e^2 >= 1/E (sum_e ce_e = 1);
    # me_e <= 1 gives aux <= E.  (aux >= 1 holds only when the mean
    # probabilities are uniform, or at one token.)
    ce = np.bincount(topi[..., 0].ravel(), minlength=e) / (bs * sl)
    assert float(aux) >= float(np.sum(ce ** 2)) * (1 - 1e-5)
    assert float(np.sum(ce ** 2)) >= 1.0 / e - 1e-9
    assert float(aux) <= e * (1 + 1e-5)
    if bs * sl == 1:
        assert float(aux) >= 1 - 1e-5


@given(st.integers(1, 6), st.integers(1, 6), st.integers(1, 4))
@settings(**SET)
def test_hlo_shape_bytes(a, b, c):
    assert _shape_bytes(_parse_shapes(f"bf16[{a},{b},{c}]")) == 2 * a * b * c
    assert _shape_bytes(_parse_shapes(f"f32[{a},{b}]")) == 4 * a * b
    assert _shape_bytes(_parse_shapes("pred[]")) == 1


def test_hlo_op_line_tuple_type():
    line = ('  %while.1 = (s32[], bf16[2,3]{1,0}, /*index=2*/f32[4]) '
            'while(%tuple.1), condition=%c, body=%b, '
            'backend_config={"known_trip_count":{"n":"28"}}')
    name, type_str, kind, rest = _parse_op_line(line)
    assert name == "%while.1" and kind == "while"
    assert _shape_bytes(_parse_shapes(type_str)) == 4 + 12 + 16


_PROP_STORE = None


def _prop_store():
    """Tiny feature store shared across hypothesis examples (built once)."""
    global _PROP_STORE
    if _PROP_STORE is None:
        import tempfile
        from repro.core.iostack import FeatureStore
        _PROP_STORE = FeatureStore(tempfile.mkdtemp(prefix="prop_cache_"),
                                   n_rows=96, row_dim=4, n_shards=3,
                                   create=True, rng_seed=1)
    return _PROP_STORE


@given(seqs=st.lists(hnp.arrays(np.float64, st.just(96),
                                elements=st.floats(0, 100, width=64)),
                     min_size=1, max_size=4),
       tiers=st.tuples(st.integers(0, 40), st.integers(0, 40)))
@settings(**SET)
def test_cache_refresh_invariants(seqs, tiers):
    """After ANY sequence of refresh() calls: every node id maps to exactly
    one tier, slot tables stay dense/consistent, and a full gather still
    matches FeatureStore.read_rows."""
    from repro.core.hetero_cache import HeteroCache
    from repro.core.iostack import SyncIOEngine
    store = _prop_store()
    dev, host = tiers
    cache = HeteroCache(store, np.zeros(96), dev, host,
                        io_engine=SyncIOEngine(store))
    all_ids = np.arange(96)
    ref = store.read_rows(all_ids)
    for scores in seqs:
        cache.refresh(scores)
        loc, slot = cache.loc, cache.slot
        assert (loc == 0).sum() == dev and (loc == 1).sum() == host
        for tier, rows in ((0, dev), (1, host)):
            np.testing.assert_array_equal(np.sort(slot[loc == tier]),
                                          np.arange(rows))
        np.testing.assert_array_equal(np.sort(cache._dev_ids),
                                      np.where(loc == 0)[0])
        np.testing.assert_array_equal(np.sort(cache._host_ids),
                                      np.where(loc == 1)[0])
        np.testing.assert_allclose(cache.gather(all_ids), ref, rtol=1e-6)
    cache.close()


_PROP_ENGINES = {}


def _prop_engine(gap):
    """Striped engines over the shared store, one per coalesce gap (reused
    across hypothesis examples; threads are joined at process exit)."""
    if gap not in _PROP_ENGINES:
        from repro.core.iostack import AsyncIOEngine
        _PROP_ENGINES[gap] = AsyncIOEngine(_prop_store(), coalesce_gap=gap)
    return _PROP_ENGINES[gap]


@given(ids=hnp.arrays(np.int64, st.integers(0, 300),
                      elements=st.integers(0, 95)),
       gap=st.sampled_from([0, 1, 7, 200, "adaptive"]))
@settings(**SET)
def test_striped_coalesced_gather_matches_read_rows(ids, gap):
    """The striped + range-coalesced read path is byte-identical to the
    plain FeatureStore gather for ANY id multiset and ANY coalesce gap —
    splitting by shard, sorting, and reading whole ranges must never
    permute, drop, or duplicate a row."""
    store = _prop_store()
    eng = _prop_engine(gap)
    data, virt = eng.submit(ids).wait()
    np.testing.assert_array_equal(data, store.read_rows(ids))
    assert virt >= 0.0
    # scatter form into a caller buffer at shifted destinations
    out = np.zeros((len(ids) + 2, store.row_dim), store.dtype)
    eng.submit(ids, out, np.arange(len(ids)) + 2).wait()
    np.testing.assert_array_equal(out[2:], store.read_rows(ids))


_WB_STORE = None
_WB_ENGINES = {}


def _wb_store():
    """Tiny WRITABLE feature store shared across hypothesis examples."""
    global _WB_STORE
    if _WB_STORE is None:
        import tempfile
        from repro.core.iostack import FeatureStore
        _WB_STORE = FeatureStore(tempfile.mkdtemp(prefix="prop_wb_"),
                                 n_rows=96, row_dim=4, n_shards=3,
                                 create=True, rng_seed=7, writable=True)
    return _WB_STORE


_WB_PSTORE = None


def _wb_pstore():
    """Writable 3-worker partitioned fleet sharing the single-store
    geometry (96 x 4), for the remote-tier properties."""
    global _WB_PSTORE
    if _WB_PSTORE is None:
        import tempfile
        from repro.distributed.partition import (PartitionedFeatureStore,
                                                 make_partition)
        _WB_PSTORE = PartitionedFeatureStore(
            tempfile.mkdtemp(prefix="prop_wb_remote_"), 96, 4,
            make_partition("hash", 96, 3), n_shards=2, create=True,
            rng_seed=7, writable=True)
    return _WB_PSTORE


def _wb_engine(mode):
    if mode not in _WB_ENGINES:
        from repro.core.iostack import (AsyncIOEngine, CPUManagedEngine,
                                        SyncIOEngine)
        if mode == "remote":
            from repro.distributed.remote_engine import RemoteIOEngine
            _WB_ENGINES[mode] = RemoteIOEngine(_wb_pstore(), me=0)
        else:
            _WB_ENGINES[mode] = {
                "helios": AsyncIOEngine, "gids": SyncIOEngine,
                "cpu": CPUManagedEngine}[mode](_wb_store())
    return _WB_ENGINES[mode]


def _wb_setup(mode):
    """(store, engine) pair for a mode — the remote mode swaps in the
    partitioned fleet store so rows not owned by worker 0 become the
    cache's fourth (remote) tier."""
    eng = _wb_engine(mode)
    return (_wb_pstore() if mode == "remote" else _wb_store()), eng


@pytest.mark.parametrize("mode", ["helios", "gids", "cpu", "remote"])
@given(ops=st.lists(
    st.tuples(st.sampled_from(["write", "gather", "refresh", "flush",
                               "prefetch"]),
              st.integers(0, 2**31 - 1)),
    min_size=1, max_size=8),
    tiers=st.tuples(st.integers(0, 30), st.integers(0, 30)))
@settings(**SET)
def test_writeback_read_your_writes(mode, ops, tiers):
    """ANY interleaving of write_planned / refresh / flush / prefetch /
    gather never loses a written value: every gather sees exactly the
    shadow model (read-your-writes across tier migration), and after the
    final flush barrier STORAGE alone reproduces it — under all three
    single-node engine modes AND the peer-striped remote engine (where
    rows owned by other workers form the cache's fourth tier and writes
    land at their owner, owner-writes)."""
    from repro.core.hetero_cache import HeteroCache
    store, eng = _wb_setup(mode)
    n = store.n_rows
    all_ids = np.arange(n)
    cache = HeteroCache(store, np.zeros(n), tiers[0], tiers[1],
                        io_engine=eng)
    shadow = store.read_rows(all_ids)             # current durable truth
    for op, seed in ops:
        rng = np.random.default_rng(seed)
        if op == "write":
            ids = rng.integers(0, n, rng.integers(1, 24))
            rows = rng.standard_normal((len(ids), store.row_dim)) \
                .astype(np.float32)
            cache.write_planned(ids, rows)
            from repro.core.iostack import keep_last_writer
            ki, kr = keep_last_writer(ids, rows)
            shadow[ki] = kr
        elif op == "gather":
            ids = rng.integers(0, n, rng.integers(1, 24))
            np.testing.assert_array_equal(cache.gather(ids), shadow[ids])
        elif op == "refresh":
            cache.refresh(rng.standard_normal(n))
        elif op == "flush":
            cache.flush()
            assert cache.n_dirty == 0
            np.testing.assert_array_equal(store.read_rows(all_ids), shadow)
        elif op == "prefetch":
            cand = rng.integers(0, n, 8)
            cache.prefetch_rows(cand)
        # the full gather ALWAYS matches, whatever just happened
        np.testing.assert_array_equal(cache.gather(all_ids), shadow)
    cache.flush()
    np.testing.assert_array_equal(store.read_rows(all_ids), shadow)
    cache.close()


@pytest.mark.parametrize("mode", ["helios", "gids", "cpu", "remote"])
@given(batches=st.lists(hnp.arrays(np.int64, st.integers(0, 120),
                                   elements=st.integers(0, 95)),
                        min_size=1, max_size=8),
       order_seed=st.integers(0, 2**31 - 1))
@settings(**SET)
def test_ooo_harvest_matches_fifo_property(mode, batches, order_seed):
    """Ticket results are IDENTICAL whether the caller drains them FIFO
    via wait() or harvests them in an arbitrary out-of-order interleaving
    (CompletionQueue + random try_complete polling) — under all three
    single-node engine modes plus the peer-striped RemoteIOEngine, for
    ANY batch multiset.  Completion order must never leak into payloads."""
    from repro.core.iostack import (CompletionQueue, CPUManagedEngine,
                                    SyncIOEngine)
    store = _prop_store()
    if mode == "helios":
        eng = _prop_engine(0)           # shared striped AsyncIOEngine
    elif mode == "remote":
        eng = _wb_engine("remote")      # shared peer-striped engine
    else:
        eng = (SyncIOEngine if mode == "gids" else CPUManagedEngine)(store)
    fifo = [eng.submit(b).wait()[0] for b in batches]
    cq = CompletionQueue()
    tickets = [eng.submit(b, cq=cq) for b in batches]
    got = {}
    rng = np.random.default_rng(order_seed)
    while len(got) < len(tickets):
        if rng.integers(0, 2) and cq.pending:
            tk = cq.pop()
            got[id(tk)] = tk.wait()[0]
        else:                            # poll a random ticket directly
            tk = tickets[int(rng.integers(0, len(tickets)))]
            out = tk.try_complete()
            if out is not None and id(tk) not in got:
                got[id(tk)] = out[0]
    for tk, ref in zip(tickets, fifo):
        np.testing.assert_array_equal(got[id(tk)], ref)
    cq.drain()


@given(n_rows=st.integers(8, 64), row_dim=st.integers(1, 5),
       n_shards=st.integers(1, 4), seed=st.integers(0, 99))
@settings(max_examples=10, deadline=None)
def test_embedding_checkpoint_roundtrip_property(n_rows, row_dim, n_shards,
                                                 seed):
    """save_embeddings -> restore_embeddings is bit-exact for ANY store
    geometry (rows/dims/shards) and content."""
    import tempfile
    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.core.iostack import FeatureStore
    root = tempfile.mkdtemp(prefix="prop_ckpt_")
    store = FeatureStore(f"{root}/t", n_rows=n_rows, row_dim=row_dim,
                         n_shards=n_shards, create=True, rng_seed=seed,
                         writable=True)
    orig = store.read_rows(np.arange(n_rows)).copy()
    cm = CheckpointManager(f"{root}/ckpt")
    cm.save_embeddings(0, store, chunk_rows=7)
    store.write_rows(np.arange(n_rows),
                     np.zeros((n_rows, row_dim), np.float32))
    cm.restore_embeddings(store)
    np.testing.assert_array_equal(store.read_rows(np.arange(n_rows)), orig)


@given(hnp.arrays(np.float32, st.integers(2, 200),
                  elements=st.floats(-1, 1, width=32)))
@settings(**SET)
def test_compression_bounded_error(g):
    from repro.distributed.compression import compress_decompress
    out = compress_decompress(jnp.asarray(g))
    blocks = np.abs(g).max() if len(g) else 0.0
    assert float(jnp.max(jnp.abs(out - jnp.asarray(g)))) <= blocks / 127 + 1e-7


@given(st.integers(2, 5), st.integers(5, 30), st.integers(0, 1000))
@settings(**SET)
def test_attention_causality(heads, seq, seed):
    """Changing a future token never affects past outputs."""
    from repro.models.attention import attend
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(k1, (1, seq, heads, 8))
    k = jax.random.normal(k2, (1, seq, 1, 8))
    v = jax.random.normal(k3, (1, seq, 1, 8))
    o1 = attend(q, k, v, causal=True, q_chunk=8)
    k2_ = k.at[:, -1].set(9.0)
    v2_ = v.at[:, -1].set(-9.0)
    o2 = attend(q, k2_, v2_, causal=True, q_chunk=8)
    np.testing.assert_allclose(np.asarray(o1[:, :-1]), np.asarray(o2[:, :-1]),
                               atol=1e-5)
