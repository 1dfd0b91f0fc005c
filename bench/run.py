#!/usr/bin/env python3
"""On-chip benchmark of the out-of-core GNN system, one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout's root names the cells; everything that
belongs to one configuration, traffic mix or per-layer metric is a file
found by its name (``bench/configs/``, ``bench/traffic/``,
``bench/metrics/``), and the traffic mix's ``kind`` picks the module that
runs it (``bench/kinds/<kind>.py``).  A run builds the cell's data from
the seed, warms up, measures for ``--seconds`` and checks what the timed
path produced against the plain reference (``bench/refs/``).  The last line of
stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, ``breakdown`` (traced runs) and ``checks``, each compared
number beside its limit.  Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """One run of one cell on the chip; returns the result line's fields."""
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], workload, "workload")
    cfg = harness.load_config(bench, cell["config"])
    device = harness.device_info(cell["chips"])
    harness.enable_compile_cache()
    return harness.execute(bench, cell, cfg, seed, seconds, trace, device,
                           T_START)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_result(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
