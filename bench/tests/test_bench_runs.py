"""Whole runs on the CPU at a tiny size: the reference agrees with the
trainer's steps and the server's logits, and the command refuses to run
without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_train_agrees_with_reference(run_tiny):
    res, _ = run_tiny("sage-cl.train")
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_seeds_per_s", "setup_s"}
    assert res["checks"]["loss_gap"]["value"] < 1e-5


def test_gcn_train_agrees_with_reference(run_tiny):
    import refcore

    res, read = run_tiny("gcn-ig.train")
    assert res["correct"], res["checks"]
    assert read(refcore.dot_highest)["grad_gap"] < 1e-5


def test_serve_agrees_with_reference(run_tiny):
    res, _ = run_tiny("sage-cl.serve", trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 8
    assert res["checks"]["logit_gap"]["value"] < 1e-5
    # a CPU run names no device metric
    assert "device_idle_share.serve" not in res["metrics"]
    assert "forward_ms.serve" in res["metrics"]


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sage-cl.train",
         "--seed", "2147483650", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"correct": true' not in p.stdout
    assert "TPU" in p.stderr
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_dataset_made_once(tiny, tmp_path, monkeypatch):
    """A configuration's graph, labels and feature table are made by the
    first run in a checkout and read back, unchanged, by the next."""
    import numpy as np

    import harness
    import system

    monkeypatch.setattr(harness, "DATA_DIR", tmp_path / "data")
    cfg = tiny("gcn-ig")
    a = system.make_data(cfg)
    made = sorted(p.name for p in (tmp_path / "data").iterdir())
    assert not any(n.endswith(".partial") for n in made) and len(made) == 2
    b = system.make_data(cfg)
    for x, y in ((a.rowptr, b.rowptr), (a.col, b.col), (a.labels, b.labels)):
        assert np.array_equal(x, y)
    ids = np.arange(0, cfg["n_vertices"], 97)
    assert np.array_equal(b.store.read_rows(ids), harness.feature_rows(
        cfg["feature_seed"], cfg["feature_dim"], ids))
    assert b.timings["features_s"] < a.timings["features_s"]
