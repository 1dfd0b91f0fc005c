"""The training batch's feature array is sized to a row bucket: the batch's
real rows, then zeros up to a rung of a fixed ladder, never the sampler's
worst case.  The padding is dead work, so the step on the bucket matches
the step on the fully padded batch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.iostack import FeatureStore
from repro.gnn.graph import synth_graph
from repro.gnn.models import init_gnn_params, make_gnn_train_step
from repro.gnn.sampling import NeighborSampler, draw_unique
from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig, row_bucket
from repro.train.optim import adamw

ROW_DIM = 32
N_VERTICES = 4000
# float32 rounding of sums over a few thousand rows, with margin: the
# padded rows add exact zeros, but the sums may be taken in another order
LOSS_RTOL = 1e-6
GRAD_RTOL = 1e-5
# Adam steps a weight by about lr in its gradient's sign, so one whose
# gradient lies within rounding of zero may step otherwise
UPDATE_RTOL = 1e-4


@pytest.fixture(scope="module")
def graph():
    return synth_graph(N_VERTICES, 8, skew=1.0, seed=0)


def _trainer(graph, tmp_path, **kw):
    st = FeatureStore(str(tmp_path / "f"), n_rows=N_VERTICES, row_dim=ROW_DIM,
                      n_shards=4, create=True, rng_seed=3,
                      writable=kw.get("train_embeddings", False))
    cfg = dict(mode="helios-nopipe", batch_size=64, fanouts=(4, 3), hidden=32,
               presample_batches=2)
    cfg.update(kw)
    return OutOfCoreGNNTrainer(graph, st, TrainerConfig(**cfg)), st


def _record(trn):
    """Keep each batch the sampler draws and what the step is fed, in
    order (the serial pipeline samples batch i before it trains it)."""
    mbs, fed = [], []
    sample, step = trn.sampler.sample, trn.step_fn

    def rec_sample(seeds):
        mbs.append(sample(seeds))
        return mbs[-1]

    def rec_step(state, feats, *rest):
        out = step(state, feats, *rest)
        fed.append((np.asarray(feats), out))
        return out
    trn.sampler.sample, trn.step_fn = rec_sample, rec_step
    return mbs, fed


@pytest.mark.parametrize("model", ["sage", "gcn", "gat"])
def test_step_on_bucket_matches_full_padded_batch(graph, model):
    mb = NeighborSampler(graph, (4, 3), seed=5).sample(
        draw_unique(np.random.default_rng(6), N_VERTICES, 64))
    n = mb.n_real
    full = np.zeros((len(mb.nodes), ROW_DIM), np.float32)
    full[:n] = np.random.default_rng(7).standard_normal((n, ROW_DIM))
    bucket = full[:row_bucket(n)]
    assert n <= len(bucket) < len(full)
    params = init_gnn_params(jax.random.key(0), model, ROW_DIM, 32,
                             graph.n_classes)
    opt = adamw(1e-3)
    step = make_gnn_train_step(model, opt, 64)
    tensors = (tuple(jnp.asarray(b.src_pos) for b in mb.blocks),
               tuple(jnp.asarray(b.dst_pos) for b in mb.blocks),
               tuple(jnp.asarray(b.edge_mask) for b in mb.blocks),
               jnp.asarray(mb.labels))

    def run(feats):
        return step({"params": params, "opt": opt.init(params)},
                    jnp.asarray(feats), *tensors)

    (s_full, m_full), (s_bkt, m_bkt) = run(full), run(bucket)
    np.testing.assert_allclose(float(m_bkt["loss"]), float(m_full["loss"]),
                               rtol=LOSS_RTOL)
    def gap(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)

    def change(s):
        return jax.tree.map(lambda p, p0: np.asarray(p, np.float64)
                            - np.asarray(p0, np.float64), s["params"], params)
    # the clipped gradient (Adam's first moment) and the parameters' change
    for a, b in zip(jax.tree.leaves(s_bkt["opt"]["m"]),
                    jax.tree.leaves(s_full["opt"]["m"])):
        assert gap(a, b) <= GRAD_RTOL
    for a, b in zip(jax.tree.leaves(change(s_bkt)),
                    jax.tree.leaves(change(s_full))):
        assert gap(a, b) <= UPDATE_RTOL


def test_step_is_fed_the_real_rows_then_zeros(graph, tmp_path):
    trn, st = _trainer(graph, tmp_path)
    with trn:
        mbs, fed = _record(trn)
        trn.train(3)
    assert len(mbs) == len(fed) == 3
    for mb, (feats, _) in zip(mbs, fed):
        n = mb.n_real
        assert feats.shape == (row_bucket(len(feats)), ROW_DIM)
        assert n <= len(feats) < len(mb.nodes)
        np.testing.assert_array_equal(feats[:n], st.read_rows(mb.nodes[:n]))
        assert not feats[n:].any()


def test_bucket_never_shrinks_and_compiles_once_a_rung(graph, tmp_path):
    trn, _ = _trainer(graph, tmp_path, mode="helios")
    with trn:
        jitted = trn.step_fn
        mbs, fed = _record(trn)
        seeded = trn._rows
        out = trn.train(12)
    rows = [len(feats) for feats, _ in fed]
    assert len(rows) == 12
    assert out["stages"]["batch_build"]["bucket_rises"] <= 1
    assert out["stages"]["batch_build"]["feature_rows"] == sum(rows)
    assert all(seeded <= r <= trn._rows for r in rows)
    assert max(mb.n_real for mb in mbs) <= trn._rows
    # one compiled step for each rung reached
    assert len(set(rows)) <= 2 and jitted._cache_size() <= 2


def test_embedding_writeback_applies_exactly_the_real_rows(graph, tmp_path):
    trn, _ = _trainer(graph, tmp_path, train_embeddings=True,
                      embedding_lr=0.5)
    with trn:
        mbs, fed = _record(trn)
        applied = []
        apply = trn.embeddings.apply_grads

        def rec_apply(ids, grads, wait=True):
            applied.append((np.array(ids), np.array(grads)))
            return apply(ids, grads, wait=wait)
        trn.embeddings.apply_grads = rec_apply
        trn.train(3)
    assert len(applied) == len(mbs) == len(fed) == 3
    for mb, (feats, out), (ids, grads) in zip(mbs, fed, applied):
        fgrad = np.asarray(out[2])
        n = mb.n_real
        assert fgrad.shape == feats.shape
        np.testing.assert_array_equal(ids, mb.nodes[:n])
        np.testing.assert_array_equal(grads, fgrad[:n])
        # rows past the real ones reach no loss: their gradient is zero
        assert not fgrad[n:].any()
