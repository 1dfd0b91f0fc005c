"""The control: the reference at a lower precision than the
configuration's, put in the program's place, comes out not correct under
the cell's own limits, at the cells' widths (1024-wide rows, hidden 256,
the configuration's classes) on a small graph and batch, while the
program passes the same limits; so does the planted half-batch fault.

On the chip the control is JAX's ``Precision.HIGH`` (three bfloat16
passes, 1.3e-5 relative error a product on a v5e).  A CPU computes every
float32 product in full, and the three passes written out there err by
4.4e-6 a product, too little to cross the chip's limits at a test's
size; the CPU test therefore reads them (they must be seen) and puts one
bfloat16 pass, the precision below them, in the program's place."""
from __future__ import annotations

import time

import pytest

import harness
import refcore
import study
from conftest import bench_with_serve


def dot_bf16(a, b):
    """One bfloat16 pass: both operands rounded, float32 accumulation."""
    import jax.numpy as jnp

    return refcore.dot_highest(a.astype(jnp.bfloat16).astype(jnp.float32),
                               b.astype(jnp.bfloat16).astype(jnp.float32))


@pytest.fixture
def small(monkeypatch, tmp_path):
    import jax

    monkeypatch.setattr(harness, "DATA_DIR", tmp_path / "data")

    def go(cell: str):
        bench = bench_with_serve()
        c = harness.find(bench["workloads"], cell, "workload")
        cfg = harness.load_config(bench, c["config"])
        cfg.update(n_vertices=20_000, batch_size=64, n_shards=4)
        return bench, c, cfg
    yield go
    jax.config.update("jax_default_matmul_precision", None)


def judged(out: dict, nums: dict) -> bool:
    """``correct`` with ``nums`` in place of the program's numbers."""
    return harness.within({k: harness.check(v, c["limit"])
                           for k, c in out["checks"].items()
                           for v in [nums.get(k, c["value"])]})


@pytest.mark.parametrize("cell", ["sage-cl.train", "gcn-ig.train"])
def test_training_control_fails(small, cell):
    bench, c, cfg = small(cell)
    out, read = harness.execute(bench, c, cfg, 2**31 + 21, 4.0, False,
                                harness.device_info(1, allow_cpu=True),
                                time.perf_counter())
    assert out["correct"], out["checks"]
    assert judged(out, read(dot_bf16)) is False
    assert judged(out, read(refcore.dot_highest, True)) is False
    high = read(refcore.dot_high)
    assert high["grad_gap"] > 5 * read(refcore.dot_highest)["grad_gap"]


def test_serving_control_fails(small):
    bench, c, cfg = small("sage-cl.serve")
    row = study.readings(bench, c, cfg, 2**31 + 21, 4.0,
                         harness.device_info(1, allow_cpu=True),
                         time.perf_counter())
    assert row["correct"], row["checks"]
    assert row["control_correct"] is False, row["control"]
