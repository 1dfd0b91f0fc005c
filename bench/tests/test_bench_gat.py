"""The ``gat-pa.train`` cell on the CPU: a tiny run is correct against
``bench/refs/gat.py``, the reference's weights have the program's
layout, the required operations match a hand count, and the edge
padding is read from the stage report."""
from __future__ import annotations

import harness
import refcore
from flops import train_flops_per_seed
from test_bench_control import dot_bf16

PA = dict(feature_dim=128, hidden=1024, heads=4, n_classes=172,
          fanouts=[25, 10])


def test_gat_train_agrees_with_reference(run_tiny):
    res, read = run_tiny("gat-pa.train", seed=2**31 + 15)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["loss_gap"]["value"] < 1e-5
    assert read(refcore.dot_highest)["grad_gap"] < 1e-5


def test_gat_control_and_half_batch_fail(run_tiny):
    """One bfloat16 pass and the half-batch fault, in the program's place,
    come out not correct under the cell's limits."""
    res, read = run_tiny("gat-pa.train", seed=2**31 + 16)
    assert res["correct"], res["checks"]
    for nums in (read(dot_bf16), read(refcore.dot_highest, True)):
        assert not harness.within({k: harness.check(nums[k], c["limit"])
                                   for k, c in res["checks"].items()
                                   if k in nums})


def test_gat_traced_run_reads_edge_padding(run_tiny):
    res, _ = run_tiny("gat-pa.train", seed=2**31 + 17, trace=True)
    assert res["correct"], res["checks"]
    pad = res["metrics"]["edge_pad_share.train"]
    assert pad["unit"] == "%" and 0.0 < pad["value"] < 100.0
    # a CPU run names no device metric
    assert "device_idle_share.train" not in res["metrics"]


def test_ref_params_are_the_program_layout():
    import jax

    from repro.gnn.models import init_gnn_params

    cfg = dict(harness.load_config(harness.load_benchmark(), "gat-pa"))
    ref = jax.eval_shape(lambda k: harness.load_ref("gat").init_params(
        k, cfg), jax.random.key(0))
    prog = jax.eval_shape(lambda k: init_gnn_params(
        k, "gat", cfg["feature_dim"], cfg["hidden"], cfg["n_classes"],
        cfg["n_layers"]), jax.random.key(0))
    assert jax.tree.structure(ref) == jax.tree.structure(prog)
    assert ([(a.shape, a.dtype) for a in jax.tree.leaves(ref)]
            == [(a.shape, a.dtype) for a in jax.tree.leaves(prog)])


def test_gat_training_count():
    m = harness.load_ref("gat").matmuls(PA)
    # layer 1: 26 destination rows x the 128x1024 projection, forward and
    # weight gradient; scores h.(W a) over 4 heads for 26 x (1 + 10)
    # source rows and 26 destination rows; layer 2: the seed's 1024x1024
    # projection and 26 + 1 score rows, and the 1024x172 head, each
    # forward, weight and input gradient
    hand = (26 * 2 * 128 * 1024 * 2 + 286 * 2 * 128 * 4 * 2
            + 26 * 2 * 128 * 4 * 2 + 2 * 1024 * 1024 * 3
            + 26 * 2 * 1024 * 4 * 3 + 2 * 1024 * 4 * 3 + 2 * 1024 * 172 * 3)
    assert train_flops_per_seed(m) == hand == 22_282_240


def test_edge_pad_share_reads_the_stage_report():
    reader = harness.load_metric("edge_pad_share.train")
    ctx = {"stages": {"batch_build": {"calls": 2, "edge_slots": 800,
                                      "real_edges": 200}}}
    assert reader.read(ctx) == 75.0
    # a program without the counters gives nothing
    assert reader.read({"stages": {"batch_build": {"calls": 2}}}) is None
    assert reader.read({}) is None
