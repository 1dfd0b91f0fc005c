"""Shared set-up of the benchmark's CPU tests: ``bench/`` and ``src/`` on
the path, tiny configurations of the cells, and the matmul precision a
run sets put back afterwards.

``BENCHMARK.json`` holds no serving cell yet; ``SERVE`` is the entries a
serving cell would add (the ``serve`` mix on ``sage-cl``, its end-to-end
and per-layer metrics), so the tests drive the serving kind as such a
cell would."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SERVE_CELL = {"name": "sage-cl.serve", "config": "sage-cl",
              "traffic": "serve", "chips": 1}
SERVE = {
    "end_to_end": [{"name": n, "unit": u, "workloads": ["sage-cl.serve"]}
                   for n, u in (("serve_p50_ms", "ms"), ("serve_p95_ms", "ms"),
                                ("serve_completed_rps", "requests/s"))],
    "per_layer": [{"name": n, "unit": u, "layer": layer,
                   "moves": "serve_p95_ms", "workloads": ["sage-cl.serve"]}
                  for n, u, layer in (
                      ("gather_ms.serve", "ms", "cache"),
                      ("forward_ms.serve", "ms", "device step"),
                      ("cache_hit_rate.serve", "%", "cache"),
                      ("compiles.serve", "count", "device step"),
                      ("device_idle_share.serve", "%", "device"),
                      ("gen_late_p95_ms.serve", "ms", "load generator"))]}


def bench_with_serve() -> dict:
    """``BENCHMARK.json`` with the serving cell's entries added."""
    import harness

    bench = harness.load_benchmark()
    bench["workloads"] = bench["workloads"] + [SERVE_CELL]
    for group, items in SERVE.items():
        bench[group] = bench[group] + items
    return bench


TINY = dict(n_vertices=3000, avg_degree=8, feature_dim=64, hidden=32,
            batch_size=32, fanouts=[5, 3], n_shards=4, n_classes=7)


@pytest.fixture
def tiny():
    """``tiny(config_name)``: the configuration cut to a test's size."""
    import harness

    def make(name: str) -> dict:
        cfg = harness.load_config(harness.load_benchmark(), name)
        cfg.update(TINY)
        return cfg
    return make


@pytest.fixture
def run_tiny(tiny, tmp_path, monkeypatch):
    """``run_tiny(cell, seed=..., trace=...)``: one CPU run of a cell at the
    tiny size, the rest of the run as on the chip (its data in a temporary
    directory)."""
    import jax

    import harness

    monkeypatch.setattr(harness, "DATA_DIR", tmp_path / "data")

    def go(cell: str, seed: int = 2**31 + 5, trace: bool = False,
           seconds: float = 2.0):
        """The result line's fields and ``readings``."""
        bench = bench_with_serve()
        c = harness.find(bench["workloads"], cell, "workload")
        return harness.execute(bench, c, tiny(c["config"]), seed, seconds,
                               trace, harness.device_info(1, allow_cpu=True),
                               time.perf_counter())
    yield go
    jax.config.update("jax_default_matmul_precision", None)
