"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Run all:

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --only fig7,fig11
    PYTHONPATH=src python -m benchmarks.run --only io_path --smoke --json out.json
"""
import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated figure-name prefixes, e.g. "
                         "fig7,serve")
    ap.add_argument("--list", action="store_true",
                    help="list available figures and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the expensive sweeps (CI per-PR budget); "
                         "every code path and acceptance ratio still runs")
    ap.add_argument("--json", default="",
                    help="also dump the emitted rows to this JSON file "
                         "(CI uploads it as the perf-regression artifact)")
    ap.add_argument("--trace", metavar="OUT.json", default="",
                    help="trace every benchmark workload into one Chrome/"
                         "Perfetto JSON (sets HELIOS_TRACE before figs "
                         "import; CI uploads it as the trace artifact)")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    if args.smoke:
        # figs reads the env var at import time, so set it before importing
        os.environ["HELIOS_BENCH_SMOKE"] = "1"
    if args.trace:
        # same import-order contract as --smoke: the tracer installs at
        # repro.obs.trace import, which figs triggers transitively
        os.environ["HELIOS_TRACE"] = args.trace
    from benchmarks import figs
    if args.list:
        for fn in figs.ALL:
            doc = (fn.__doc__ or "").strip().splitlines()[0]
            print(f"{fn.__name__}: {doc}")
        return
    sel = [s.strip() for s in args.only.split(",") if s.strip()]
    print("name,us_per_call,derived")
    t0 = time.time()
    for fn in figs.ALL:
        if sel and not any(fn.__name__.startswith(s) for s in sel):
            continue
        fn()
    wall = time.time() - t0
    print(f"# total wall {wall:.1f}s", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"smoke": args.smoke, "wall_s": wall,
                       "rows": [{"name": n, "us_per_call": u, "derived": d}
                                for n, u, d in figs.ROWS]}, fh, indent=1)
        print(f"# wrote {len(figs.ROWS)} rows to {args.json}",
              file=sys.stderr)


if __name__ == '__main__':
    main()
