#!/usr/bin/env python3
"""Find a serving mix's knee once, on the chip: one server, the mix's
request shape on a configuration, open-loop load at each rate in turn.

    python bench/sweep.py --config sage-cl --traffic serve --rates 2 4 6 8 --seconds 30

For each rate prints the completed requests per second, the latency
median and 95th percentile, and the growth of latency from the first to
the last third of the requests (a growing backlog).  The knee is the
highest rate completed without a growing backlog; a cell's mix file
holds 0.8 of it as a number.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2**31 + 101)
    args = ap.parse_args(argv)

    import jax
    import loadgen
    import system
    from repro.serving import GNNInferenceServer
    from repro.serving.scheduler import PriorityClass

    bench = harness.load_benchmark()
    cfg = harness.load_config(bench, args.config)
    mix = harness.load_traffic(args.traffic)
    harness.device_info(1)
    harness.enable_compile_cache()
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    kind = harness.load_kind(mix["kind"])
    klass = PriorityClass("bench", 0, budget_v=1e9)
    data = system.make_data(cfg)
    arch = harness.load_ref(cfg["arch"])
    params = system.init_params(arch, cfg, args.seed)
    pop = loadgen.popularity(data.col, cfg["n_vertices"])
    with GNNInferenceServer(data.graph, data.store,
                            kind.server_config(cfg, mix, args.seed),
                            params=params) as srv:
        for s in loadgen.request_seeds(pop, mix["seeds_per_request"], 8,
                                       np.random.default_rng(1)):
            fut = srv.submit(s, klass)
            srv.flush()
            fut.result()
        for i, rate in enumerate(args.rates):
            due = loadgen.due_times(rate, args.seconds, args.seed + i)
            reqs = loadgen.request_seeds(
                pop, mix["seeds_per_request"], len(due),
                np.random.default_rng([args.seed, i]))
            t0 = time.perf_counter()
            done, late, _ = kind.serve_open_loop(srv, klass, reqs, due, t0)
            lat = 1000.0 * (done - (t0 + due))
            third = max(1, len(lat) // 3)
            print(json.dumps({
                "rate_rps": rate, "requests": len(due),
                "completed_rps": float(np.sum(done <= t0 + args.seconds)
                                       / args.seconds),
                "p50_ms": harness.percentile(lat, 50),
                "p95_ms": harness.percentile(lat, 95),
                "first_third_median_ms": float(np.median(lat[:third])),
                "last_third_median_ms": float(np.median(lat[-third:])),
                "gen_late_p95_ms": 1000 * harness.percentile(late, 95)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
