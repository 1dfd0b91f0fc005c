"""GAT through the program's normal path, against the plain reference in
``tests/gat_ref.py`` (a loop over destination rows and explicit edge
lists), at a small size on the CPU with seeded random weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gat_ref
from repro.core.iostack import FeatureStore
from repro.gnn.graph import synth_graph
from repro.gnn.models import (_agg_gat, gnn_forward, gnn_loss,
                              init_gnn_params, make_gnn_train_step)
from repro.gnn.sampling import NeighborSampler, draw_unique
from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig, row_bucket
from repro.serving import GNNInferenceServer
from repro.serving.service import ServerConfig
from repro.train.optim import adamw

N_VERTICES, ROW_DIM, HIDDEN, HEADS = 3000, 64, 32, 4     # 4 heads x 8
FANOUTS, BATCH = (5, 3), 32
# both sides compute in float32 at highest matmul precision and differ
# only in the order of their sums (segment sums over a padded edge list
# against a loop over each row's edges): a few ulps of each output, so
# 1e-5 of the logits' RMS and of the loss leaves room above rounding
LOGIT_TOL = 1e-5
LOSS_RTOL = 1e-5
# a gradient leaf sums such terms over every edge and row of the batch
# (1e-7 to 2.5e-7 of its norm, read on the CPU): 1e-5 of its norm
GRAD_RTOL = 1e-5


@pytest.fixture(scope="module")
def graph():
    return synth_graph(N_VERTICES, 8, skew=1.0, seed=0)


@pytest.fixture(scope="module")
def batch(graph):
    """One sampled batch on a row bucket: its real rows' features, then
    zeros, and its device tensors."""
    mb = NeighborSampler(graph, FANOUTS, seed=5).sample(
        draw_unique(np.random.default_rng(6), N_VERTICES, BATCH))
    n = mb.n_real
    feats = np.zeros((row_bucket(n), ROW_DIM), np.float32)
    feats[:n] = np.random.default_rng(7).standard_normal((n, ROW_DIM))
    return mb, feats


@pytest.fixture(scope="module")
def params(graph):
    return init_gnn_params(jax.random.key(3), "gat", ROW_DIM, HIDDEN,
                           graph.n_classes)


def _blocks(mb):
    return [(jnp.asarray(b.src_pos), jnp.asarray(b.dst_pos),
             jnp.asarray(b.edge_mask)) for b in mb.blocks]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def test_params_layout(params):
    for i, lp in enumerate(params["layers"]):
        assert lp["w"].shape == ((ROW_DIM if i == 0 else HIDDEN), HIDDEN)
        assert lp["a_src"].shape == lp["a_dst"].shape == (HEADS, 8)
        assert lp["b"].shape == (HIDDEN,)
    assert params["head"]["w"].shape[0] == HIDDEN


def test_forward_loss_and_grads_match_reference(batch, params):
    mb, feats = batch
    blocks = _blocks(mb)
    labels = jnp.asarray(mb.labels)
    with jax.default_matmul_precision("highest"):
        h = jax.jit(gnn_forward, static_argnums=3)(params, feats, blocks,
                                                   "gat")
        got = h[:BATCH] @ params["head"]["w"] + params["head"]["b"]
        (loss, _), grads = jax.jit(jax.value_and_grad(
            lambda p: gnn_loss(p, feats, blocks, labels, BATCH, "gat"),
            has_aux=True))(params)
    ref_blocks = [(b.src_pos, b.dst_pos, b.edge_mask) for b in mb.blocks]
    want = gat_ref.logits(params, feats, ref_blocks, BATCH)
    ref_loss, ref_grads = jax.value_and_grad(gat_ref.loss)(
        params, feats, ref_blocks, mb.labels, BATCH)
    rms = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert float(jnp.max(jnp.abs(got - want))) <= LOGIT_TOL * rms
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=LOSS_RTOL)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert _rel(g, r) <= GRAD_RTOL, jax.tree_util.keystr(path)
    # the attention vectors are trained: their gradients are not zero
    for lp in ref_grads["layers"]:
        assert float(jnp.linalg.norm(lp["a_src"])) > 0.0
        assert float(jnp.linalg.norm(lp["a_dst"])) > 0.0


def test_forward_reads_heads_from_the_parameters(batch, params):
    """The same weights read as 2 heads of 16: the forward takes the head
    count from ``a_src``'s shape, as the benchmark's loaded weights need."""
    mb, feats = batch
    two = jax.tree.map(lambda x: x, params)
    for lp in two["layers"]:
        lp["a_src"] = lp["a_src"].reshape(2, HIDDEN // 2)
        lp["a_dst"] = lp["a_dst"].reshape(2, HIDDEN // 2)
    with jax.default_matmul_precision("highest"):
        h = jax.jit(gnn_forward, static_argnums=3)(two, feats, _blocks(mb),
                                                   "gat")
        got = h[:BATCH] @ two["head"]["w"] + two["head"]["b"]
    want = gat_ref.logits(two, feats, [(b.src_pos, b.dst_pos, b.edge_mask)
                                       for b in mb.blocks], BATCH)
    four = gat_ref.logits(params, feats, [(b.src_pos, b.dst_pos,
                                           b.edge_mask) for b in mb.blocks],
                          BATCH)
    rms = float(jnp.sqrt(jnp.mean(want ** 2)))
    # the reference's tolerance above; two heads are another model
    assert float(jnp.max(jnp.abs(got - want))) <= LOGIT_TOL * rms
    assert float(jnp.max(jnp.abs(want - four))) > 100 * LOGIT_TOL * rms


def test_attention_weights_sum_to_one_self_edge_included(batch):
    mb, feats = batch
    blk = mb.blocks[1]                  # layer 1's block: the hop-2 edges
    n = feats.shape[0]
    z = np.random.default_rng(8).standard_normal((n, HEADS, 8))
    z[..., -1] = 1.0                    # a constant feature reads sum(alpha)
    a = np.random.default_rng(9).standard_normal((2, HEADS, 8))
    out = np.asarray(_agg_gat(jnp.asarray(z, jnp.float32), *a, blk.src_pos,
                              blk.dst_pos, blk.edge_mask))
    np.testing.assert_allclose(out[..., -1], 1.0, rtol=1e-6)
    # a row with no in-edges attends over its self edge alone
    lonely = np.setdiff1d(np.arange(n), blk.dst_pos[blk.edge_mask])
    assert len(lonely) and (lonely < BATCH).any()
    np.testing.assert_allclose(out[lonely], z[lonely], rtol=1e-6)


def test_masked_edges_and_padding_rows_change_nothing(batch, params):
    """The same loss and update on two bucket sizes, and with the masked
    edge slots pointed at other real rows."""
    mb, feats = batch
    n = mb.n_real
    wide = np.zeros((len(mb.nodes), ROW_DIM), np.float32)
    wide[:len(feats)] = feats
    assert len(feats) < len(wide)
    rng = np.random.default_rng(10)
    moved = []
    for b in mb.blocks:
        src, dst = b.src_pos.copy(), b.dst_pos.copy()
        off = ~b.edge_mask
        src[off] = rng.integers(0, n, off.sum())
        dst[off] = rng.integers(0, n, off.sum())
        moved.append((src, dst, b.edge_mask))
    assert any((~b.edge_mask).any() for b in mb.blocks)
    opt = adamw(1e-3)
    step = make_gnn_train_step("gat", opt, BATCH)
    labels = jnp.asarray(mb.labels)

    def run(f, blocks):
        src, dst, em = (tuple(jnp.asarray(b[i]) for b in blocks)
                        for i in range(3))
        return step({"params": params, "opt": opt.init(params)},
                    jnp.asarray(f), src, dst, em, labels)

    plain = [(b.src_pos, b.dst_pos, b.edge_mask) for b in mb.blocks]
    s0, m0 = run(feats, plain)
    for f, blocks in ((wide, plain), (feats, moved)):
        s1, m1 = run(f, blocks)
        # float32 sums over another number of rows or slots, in another
        # order: the same rounding bounds as test_row_bucket.py
        np.testing.assert_allclose(float(m1["loss"]), float(m0["loss"]),
                                   rtol=1e-6)
        for a, b in zip(jax.tree.leaves(s1["opt"]["m"]),
                        jax.tree.leaves(s0["opt"]["m"])):
            assert _rel(a, b) <= 1e-5


def _store(tmp_path):
    return FeatureStore(str(tmp_path / "f"), n_rows=N_VERTICES,
                        row_dim=ROW_DIM, n_shards=4, create=True, rng_seed=3)


def test_trainer_runs_gat_and_counts_its_edges(graph, tmp_path):
    cfg = TrainerConfig(model="gat", hidden=HIDDEN, batch_size=BATCH,
                        fanouts=FANOUTS, prefetch_depth=1,
                        presample_batches=2)
    trn = OutOfCoreGNNTrainer(graph, _store(tmp_path), cfg)
    with trn:
        drawn, fed = [], []
        sample, step = trn.sampler.sample, trn.step_fn

        def rec_sample(seeds):
            drawn.append(sample(seeds))
            return drawn[-1]

        def rec_step(state, feats, *rest):
            fed.append(feats.shape[0])
            return step(state, feats, *rest)
        trn.sampler.sample, trn.step_fn = rec_sample, rec_step
        out = trn.train(3)
    assert jax.tree.structure(trn.state["params"]) == jax.tree.structure(
        init_gnn_params(jax.random.key(0), "gat", ROW_DIM, HIDDEN,
                        graph.n_classes))
    assert all(np.isfinite(m["loss"]) for m in trn.metrics_log)
    bb = out["stages"]["batch_build"]
    slots = sum(len(b.edge_mask) for mb in drawn for b in mb.blocks)
    real = sum(int(b.edge_mask.sum()) for mb in drawn for b in mb.blocks)
    # one self edge per row and layer: the bucket's rows, n_real of them real
    assert bb["edge_slots"] == slots + 2 * sum(fed)
    assert bb["real_edges"] == real + 2 * sum(mb.n_real for mb in drawn)


def test_sage_edge_counters_are_the_blocks(graph, tmp_path):
    cfg = TrainerConfig(model="sage", hidden=HIDDEN, batch_size=BATCH,
                        fanouts=FANOUTS, prefetch_depth=1,
                        presample_batches=2)
    with OutOfCoreGNNTrainer(graph, _store(tmp_path), cfg) as trn:
        drawn = []
        sample = trn.sampler.sample
        trn.sampler.sample = lambda s: drawn.append(sample(s)) or drawn[-1]
        bb = trn.train(2)["stages"]["batch_build"]
    assert bb["edge_slots"] == sum(len(b.edge_mask) for mb in drawn
                                   for b in mb.blocks)
    assert bb["real_edges"] == sum(int(b.edge_mask.sum()) for mb in drawn
                                   for b in mb.blocks)


def test_server_logits_equal_trainer_forward(graph, tmp_path, params):
    cfg = ServerConfig(model="gat", hidden=HIDDEN, request_batch_size=16,
                       fanouts=FANOUTS, chaos=None)
    with GNNInferenceServer(graph, _store(tmp_path), cfg,
                            params=params) as srv:
        seen = []
        step = srv.infer_step

        def rec_step(p, feats, src, dst, em):
            seen.append((feats, list(zip(src, dst, em))))
            return step(p, feats, src, dst, em)
        srv.infer_step = rec_step
        seeds = draw_unique(np.random.default_rng(11), N_VERTICES, 16)
        fut = srv.submit(seeds)
        srv.flush()
    served = fut.result()["logits"]
    assert len(seen) == 1 and served.shape == (16, graph.n_classes)
    feats, blocks = seen[0]
    h = gnn_forward(params, feats, blocks, "gat")
    fwd = h[:16] @ params["head"]["w"] + params["head"]["b"]
    np.testing.assert_allclose(served, np.asarray(fwd), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("model", ["gin", "GAT", ""])
def test_unknown_model_raises(params, model):
    with pytest.raises(ValueError, match="unknown GNN model"):
        init_gnn_params(jax.random.key(0), model, ROW_DIM, HIDDEN, 7)
    with pytest.raises(ValueError, match="unknown GNN model"):
        gnn_forward(params, jnp.zeros((4, ROW_DIM)), [], model)


def test_heads_must_divide_hidden():
    with pytest.raises(ValueError, match="multiple of heads"):
        init_gnn_params(jax.random.key(0), "gat", ROW_DIM, 30, 7)
