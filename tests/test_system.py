"""End-to-end behaviour tests for the whole system."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.iostack import FeatureStore
from repro.data.tokens import OutOfCoreTokenIterator, TokenStore
from repro.gnn.graph import synth_graph
from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig
from repro.models import lm, steps
from repro.train.optim import adamw


def test_gnn_out_of_core_end_to_end(tmp_path):
    """The paper's workload: out-of-core GNN training on a skewed graph with
    all three Helios components engaged; loss improves and the cache
    absorbs most traffic."""
    g = synth_graph(8000, 8, skew=1.2, seed=0)
    store = FeatureStore(str(tmp_path / "f"), n_rows=8000, row_dim=64,
                         n_shards=4, create=True, rng_seed=1)
    tr = OutOfCoreGNNTrainer(g, store, TrainerConfig(
        batch_size=128, fanouts=(5, 4), hidden=64,
        device_cache_frac=0.1, host_cache_frac=0.2, presample_batches=3))
    with tr:
        out = tr.train(10)
    assert out["loss_last"] < out["loss_first"]
    assert out["cache"]["hit_rate"] > 0.3


def test_lm_train_with_out_of_core_data(tmp_path):
    """LM training fed by the out-of-core token pipeline + checkpoint/resume."""
    cfg = get_config("llama3.2-3b").reduced()
    store = TokenStore(str(tmp_path / "tok"), n_sequences=64, seq_len=16,
                       vocab=cfg.vocab, n_shards=2, create=True)
    it = OutOfCoreTokenIterator(store, batch_size=8, n_microbatches=2)
    params = lm.init_params(jax.random.key(0), cfg)
    opt = adamw(1e-3)
    state = {"params": params, "opt": opt.init(params)}
    ts = jax.jit(steps.make_train_step(cfg, opt, q_chunk=16))
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_write=False)
    losses = []
    for step in range(6):
        state, m = ts(state, next(it))
        losses.append(float(m["loss"]))
    mgr.save(6, state, extra={"data_iter": it.checkpoint_state()})
    assert losses[-1] < losses[0]
    assert all(np.isfinite(x) for x in losses)
    # resume
    restored, extra = mgr.restore()
    assert extra["step"] == 6
    assert extra["data_iter"]["cursor"] == it.checkpoint_state()["cursor"]
    state2 = jax.tree.map(jnp.asarray, restored)
    _, m = ts(state2, next(it))
    assert np.isfinite(float(m["loss"]))


def test_moe_expert_hotness_tiering(tmp_path):
    """Helios applied to MoE: expert weights tiered by routing hotness."""
    from repro.core.hetero_cache import HeteroCache
    from repro.core.hotness import expert_hotness
    n_experts, d = 64, 128
    store = FeatureStore(str(tmp_path / "experts"), n_rows=n_experts,
                         row_dim=d, n_shards=2, create=True, rng_seed=2)
    routing = np.random.default_rng(0).zipf(1.5, 100000) % n_experts
    hot = expert_hotness(np.bincount(routing, minlength=n_experts))
    cache = HeteroCache(store, hot, device_rows=8, host_rows=16)
    used = np.unique(routing[:500])
    rows = cache.gather(used)
    np.testing.assert_allclose(rows, store.read_rows(used), rtol=1e-6)
    assert cache.stats.hit_rate > 0.3
