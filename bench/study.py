#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers on
many seeds, the control's (the reference at the precision below the
configuration's, in the program's place) and the planted half-batch
fault's, all in one process on the chip.

    python bench/study.py --workload sage-cl.train --seeds 1 2 3 ...

Each seed is a whole run of the cell with a short window (``--seconds``).
One JSON line per seed on stdout: the run's checks, every number read
for the program, the control and the fault (compared or not), and
whether the control and the fault come out correct under the cell's
limits; then the largest program reading and the smallest control and
fault reading of each number.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402
import refcore  # noqa: E402


def readings(bench: dict, cell: dict, cfg: dict, seed: int, seconds: float,
             device: dict, t_start: float) -> dict:
    """One run of the cell, then the control's and the half-batch fault's
    readings of the same steps or requests, each judged by the cell's
    limits as the run's own numbers are."""
    out, read = harness.execute(bench, cell, cfg, seed, seconds, False,
                                device, t_start)
    row = {"seed": seed, "correct": out["correct"], "checks": out["checks"],
           "timings": out["timings"]}
    lim = {k: c["limit"] for k, c in out["checks"].items()}
    for part, args in (("program", (refcore.dot_highest,)),
                       ("control", (refcore.dot_high,)),
                       ("half_batch", (refcore.dot_highest, True))):
        nums = read(*args)
        row[part] = nums
        row[f"{part}_correct"] = harness.within(
            {k: harness.check(v, lim[k]) for k, v in nums.items()
             if k in lim})
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    cfg = harness.load_config(bench, cell["config"])
    device = harness.device_info(cell["chips"])
    harness.enable_compile_cache()
    rows, t0 = [], T_START
    for seed in args.seeds:
        row = readings(bench, cell, cfg, seed, args.seconds, device, t0)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
        t0 = time.perf_counter()
    summary = {}
    for part, pick in (("program", max), ("control", min),
                       ("half_batch", min)):
        for k in (k for k, v in rows[0][part].items()
                  if isinstance(v, float)):
            summary[f"{part}.{k}"] = pick(r[part][k] for r in rows)
        summary[f"{part}_correct_any"] = any(r[f"{part}_correct"]
                                             for r in rows)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
