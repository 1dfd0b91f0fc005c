"""The readers of the pipeline's input wait, the traced upload, the padded
share of the batch and the bytes a step uploads: nothing to read gives
``None``, a hand-built context its number, and a traced run at a tiny
size reports all four."""
from __future__ import annotations

import pytest

import harness

STAGES = {"stages": {
    "batch_build": {"wall_s": 0.5, "virtual_s": 0.0, "calls": 4,
                    "wait_s": 0.2, "feature_rows": 1000, "real_rows": 150,
                    "h2d_bytes": 4096},
    "train": {"wall_s": 2.0, "virtual_s": 0.0, "calls": 4,
              "upload_s": 1.2}}}
# stages as a program without these counters reports them
BARE = {"stages": {k: {"wall_s": 1.0, "virtual_s": 0.0, "calls": 4}
                   for k in ("batch_build", "train")}}


@pytest.mark.parametrize("name, want", [
    ("input_wait_ms.train", 50.0),
    ("upload_ms.train", 300.0),
    ("padded_row_share.train", 85.0),
    ("h2d_mb_per_step.train", 0.001024)])
def test_reader(name, want):
    reader = harness.load_metric(name)
    assert reader.read({}) is None
    assert reader.read(BARE) is None
    assert reader.read(STAGES) == pytest.approx(want)


def test_traced_tiny_run_reports_them(run_tiny):
    res, _ = run_tiny("sage-cl.train", trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["input_wait_ms.train"]["value"] > 0.0
    assert m["upload_ms.train"]["value"] > 0.0
    assert 0.0 < m["padded_row_share.train"]["value"] < 100.0
    assert m["h2d_mb_per_step.train"]["value"] > 0.0
