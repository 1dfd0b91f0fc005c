"""Tracing of the training pipeline's device stream: the per-batch input
wait of each operator, the traced feature upload, the batch counters and
the XLA compiles placed under the span that caused them."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.iostack import FeatureStore
from repro.gnn.graph import synth_graph
from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig, row_bucket
from repro.obs import trace as obs_trace

ROW_DIM = 32
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    """A small trainer whose sampler keeps every MiniBatch it draws."""
    g = synth_graph(5000, 8, skew=1.0, seed=0)
    st = FeatureStore(str(tmp_path_factory.mktemp("trace_pipe") / "f"),
                      n_rows=5000, row_dim=ROW_DIM, n_shards=4, create=True,
                      rng_seed=3)
    with OutOfCoreGNNTrainer(g, st, TrainerConfig(
            mode="helios", batch_size=64, fanouts=(4, 3), hidden=32,
            presample_batches=2)) as trn:
        real = trn.sampler.sample
        trn.drawn = []

        def sample(seeds):
            mb = real(seeds)
            trn.drawn.append(mb)
            return mb
        trn.sampler.sample = sample
        yield trn


@pytest.fixture(scope="module")
def traced(trainer):
    prev = obs_trace.TRACER
    tr = obs_trace.install()
    trainer.drawn.clear()
    try:
        out = trainer.train(4)
    finally:
        obs_trace.TRACER = prev
    return tr, out, list(trainer.drawn)


def _named(tr, name):
    return sorted((s for s in tr.spans if s.name == name),
                  key=lambda s: s.args["batch"])


def test_one_wait_and_one_upload_span_per_batch(traced):
    tr, _, drawn = traced
    waits = _named(tr, "pipe.wait.batch_build")
    uploads = _named(tr, "pipe.train.upload")
    assert [s.args["batch"] for s in waits] == [0, 1, 2, 3]
    assert [s.args["batch"] for s in uploads] == [0, 1, 2, 3]
    # only the device stream's waits on the io/host pools are recorded
    assert {s.name for s in tr.spans if s.cat == "wait"} == {
        "pipe.wait.batch_build"}
    assert {s.args["on"] for s in waits} <= {"cache_lookup", "io_complete"}
    builds = {s.args["batch"]: s for s in tr.spans
              if s.name == "pipe.batch_build"}
    for w in waits:
        assert w.t1 > w.t0 and w.track == "device"
        # the wait ends where the operator starts
        assert w.t1 == builds[w.args["batch"]].t0
    # each upload is a rung of the row ladder, not the sampler's worst case
    for u in uploads:
        rows, rest = divmod(u.args["bytes"], ROW_DIM * 4)
        assert rest == 0 and rows == row_bucket(rows) < len(drawn[0].nodes)


def test_operator_spans_carry_the_batch_only(traced):
    tr, _, _ = traced
    ops = [s for s in tr.spans if s.cat == "pipe"]
    assert ops and all(set(s.args) == {"batch"} for s in ops)


def _tensor_bytes(mb):
    """Device bytes of a batch's index, mask and label tensors."""
    def dev_bytes(a):
        return a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
    tensors = ([b.src_pos for b in mb.blocks] + [b.dst_pos for b in mb.blocks]
               + [b.edge_mask for b in mb.blocks] + [mb.labels])
    return sum(dev_bytes(a) for a in tensors)


def _counters(out):
    bb = out["stages"]["batch_build"]
    return {k: bb[k] for k in ("calls", "feature_rows", "real_rows",
                               "h2d_bytes", "bucket_rises")}


def test_batch_counters_match_the_sampled_batches(traced):
    tr, out, drawn = traced
    assert len(drawn) == 4
    c = _counters(out)
    real = sorted(int(np.count_nonzero(mb.node_mask)) for mb in drawn)
    rows = sorted(u.args["bytes"] // (ROW_DIM * 4)
                  for u in _named(tr, "pipe.train.upload"))
    assert c["calls"] == 4 and c["real_rows"] == sum(real)
    # each batch's feature rows are a rung of the ladder holding its real
    # rows, and at most the sampler's padded node count
    assert c["feature_rows"] == sum(rows)
    assert all(r == row_bucket(r) for r in rows)
    assert all(r >= n for r, n in zip(rows, real))
    assert (c["real_rows"] <= c["feature_rows"]
            <= sum(len(mb.nodes) for mb in drawn))
    assert c["h2d_bytes"] == (c["feature_rows"] * ROW_DIM * 4
                              + sum(_tensor_bytes(mb) for mb in drawn))
    assert c["bucket_rises"] == 0     # seeded by the presample epoch


def test_stage_report_holds_waits_and_sums(traced):
    _, out, _ = traced
    st = out["stages"]
    assert st["batch_build"]["wait_s"] > 0.0
    # waits are timed only where the device stream takes its inputs from
    # the io/host pools: not on those pools, nor behind batch_build
    assert "wait_s" not in st["io_complete"]
    assert "wait_s" not in st["train"]
    assert st["train"]["upload_s"] > 0.0


def test_tracer_off_records_nothing_and_still_counts(trainer):
    prev = obs_trace.TRACER
    try:
        obs_trace.TRACER = None
        trainer.drawn.clear()
        out = trainer.train(2)
        assert _counters(out)["calls"] == 2
        assert _counters(out)["real_rows"] == sum(mb.n_real
                                                  for mb in trainer.drawn)
        assert "upload_s" not in out["stages"]["train"]
        assert "obs" not in out
        tr = obs_trace.install()
        tr.enabled = False
        out = trainer.train(2)
        assert tr.spans == [] and _counters(out)["calls"] == 2
    finally:
        obs_trace.TRACER = prev


def test_compile_span_parented_to_the_open_span():
    prev = obs_trace.TRACER
    try:
        obs_trace.install()
        tr = obs_trace.install()          # the listener is registered once

        def triple_plus_one(x):
            return x * 3 + 1
        with tr.span("outer") as outer:
            jax.jit(triple_plus_one)(jnp.ones((7, 13))).block_until_ready()
        comp = [s for s in tr.spans if s.name == "jax.compile"
                and "triple_plus_one" in (s.args or {}).get("fn", "")]
        assert len(comp) == 1
        assert comp[0].parent == outer.sid
        assert outer.t0 <= comp[0].t0 <= comp[0].t1 <= outer.t1
    finally:
        obs_trace.TRACER = prev


def test_env_tracer_does_not_import_jax(tmp_path):
    code = ("import sys, repro.obs.trace as t; "
            "print('jax' in sys.modules, t.TRACER is not None)")
    env = dict(os.environ, PYTHONPATH=SRC, HELIOS_TRACE="1")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=tmp_path, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["False", "True"]
