"""Each fault a cell can have, planted in the timed path underneath an
otherwise whole CPU run, turns ``correct`` false."""
from __future__ import annotations

import numpy as np


def test_train_state_unchanged(run_tiny, monkeypatch):
    import repro.gnn.train as train_mod

    real = train_mod.make_gnn_train_step

    def broken(*a, **k):
        step = real(*a, **k)

        def same_state(state, *args):
            _, m = step(state, *args)
            return state, m
        return same_state
    monkeypatch.setattr(train_mod, "make_gnn_train_step", broken)
    res, _ = run_tiny("sage-cl.train", seed=2**31 + 11)
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] > 0.9


def test_train_half_batch(run_tiny, monkeypatch):
    import jax
    import jax.numpy as jnp

    import repro.gnn.models as models

    def half_loss(params, feats, blocks, labels, batch_size, model):
        n = batch_size // 2
        h = models.gnn_forward(params, feats, blocks, model)
        logits = h[:n] @ params["head"]["w"] + params["head"]["b"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:n, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold), jnp.float32(0)
    monkeypatch.setattr(models, "gnn_loss", half_loss)
    res, _ = run_tiny("gcn-ig.train", seed=2**31 + 12)
    assert not res["correct"]


def _alter_rows(monkeypatch):
    """Every fiftieth gathered row comes back off by 0.5."""
    from repro.core.hetero_cache import HeteroCache

    real = HeteroCache.complete_planned

    def altered(self, pg):
        out = real(self, pg)
        out[::50] += np.float32(0.5)
        return out
    monkeypatch.setattr(HeteroCache, "complete_planned", altered)


def test_train_row_altered(run_tiny, monkeypatch):
    _alter_rows(monkeypatch)
    res, _ = run_tiny("sage-cl.train", seed=2**31 + 13)
    assert not res["correct"]
    assert res["checks"]["bad_rows"]["value"] > 0


def test_serve_row_altered(run_tiny, monkeypatch):
    _alter_rows(monkeypatch)
    res, _ = run_tiny("sage-cl.serve", seed=2**31 + 14)
    assert not res["correct"]


def test_serve_logit_altered(run_tiny, monkeypatch):
    import repro.serving.service as service

    real = service.make_gnn_infer_step

    def broken(*a, **k):
        step = real(*a, **k)

        def altered(*args):
            return step(*args).at[0, 0].add(0.5)
        return altered
    monkeypatch.setattr(service, "make_gnn_infer_step", broken)
    res, _ = run_tiny("sage-cl.serve", seed=2**31 + 15)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > 1e-2
