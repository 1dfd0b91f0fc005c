"""Deep GNN-aware pipeline (paper §3.3, TPU-adapted).

The trainer decomposes a mini-batch into operators — ``sample`` ->
``io_submit`` -> {``cache_lookup``, ``io_complete``} -> ``batch_build`` ->
``train``, plus ``cache_refresh`` (and, when enabled, ``prefetch`` and
``embedding_writeback``) on the io resource; the authoritative plan is
``gnn.train.OutOfCoreGNNTrainer._operators`` — and this executor runs
them on a two-level pipeline:

  * intra-mini-batch: each operator is submitted to its resource's pool
    and starts once its dependencies in the same batch have finished, so
    operators with no mutual dependency run concurrently;
  * inter-mini-batch: up to ``prefetch_depth`` mini-batches are in flight,
    so IO and host work for batch i+1 runs while the device trains batch
    i.  ``prefetch_depth=1`` runs one batch at a time.

Each resource class has a bounded pool: "io" and "host" a few threads
each, "device" one thread, the one device stream.  The executor times
every operator on the host's wall clock and sums the numbers an operator
returns; for a device-stream operator fed from another pool it also
times the wait for its inputs after its turn on the device pool came
(the stream starved of its batch).  With a tracer installed each run is
a ``pipe.<op>`` span and each such wait a ``pipe.wait.<op>`` span.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable

from repro.obs import trace as _trace


@dataclass
class Operator:
    """One GPU-initiated operator in the execution plan."""
    name: str
    fn: Callable[..., Any]             # (ctx) -> None | {name: number}, summed
    resource: str                      # "io" | "host" | "device"
    deps: tuple = ()                   # names of ops in the same batch


@dataclass
class StageTiming:
    wall_s: float = 0.0
    calls: int = 0
    # device-stream operators fed from the io/host pools only: host wall
    # from the operator's turn on the one-thread device pool to its last
    # input's completion, the device stream ready but starved of its batch
    wait_s: float = 0.0
    # per-call numbers an operator reports by returning a dict
    # (batch_build's row and byte counts, the traced upload time)
    sums: dict = field(default_factory=dict)


class PipelineExecutor:
    """Two-level operator pipeline with bounded per-resource executors."""

    def __init__(self, plan: list[Operator], prefetch_depth: int = 2,
                 io_workers: int = 2, host_workers: int = 2):
        if prefetch_depth < 1:
            raise ValueError(f"prefetch_depth must be >= 1, got "
                             f"{prefetch_depth}")
        self.plan = plan
        self.prefetch_depth = prefetch_depth
        self.pools = {
            "io": ThreadPoolExecutor(io_workers, "pipe-io"),
            "host": ThreadPoolExecutor(host_workers, "pipe-host"),
            "device": ThreadPoolExecutor(1, "pipe-dev"),   # one device stream
        }
        self.timings: dict[str, StageTiming] = {op.name: StageTiming()
                                                for op in plan}
        # device-stream operators whose inputs come from another pool: the
        # ones whose dependency wait is timed (and traced as pipe.wait.<op>)
        where = {op.name: op.resource for op in plan}
        self.starvable = {op.name for op in plan if op.resource == "device"
                          and any(where[d] != "device" for d in op.deps)}
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def _run_op(self, op: Operator, ctx: dict, batch_idx: int,
                waited_from: float | None = None):
        """Run one operator; ``waited_from`` is when its runner began
        waiting on the operator's dependencies (``None``: not timed)."""
        t0 = time.perf_counter()
        out = op.fn(ctx)
        t1 = time.perf_counter()
        wait = t0 - waited_from if waited_from is not None else 0.0
        with self._lock:
            st = self.timings[op.name]
            st.wall_s += t1 - t0
            st.wait_s += wait
            st.calls += 1
            if out:
                for k, v in out.items():
                    st.sums[k] = st.sums.get(k, 0) + v
        tr = _trace.TRACER
        if tr is not None and tr.enabled:
            if wait > 0.0:
                # the dependency that finished last is the one waited for
                on = max(op.deps, key=lambda d: ctx.get(f"__wall_end_{d}", 0.0))
                tr.record(f"pipe.wait.{op.name}", waited_from, t0,
                          track=op.resource, cat="wait",
                          args={"batch": batch_idx, "on": on})
            tr.record(f"pipe.{op.name}", t0, t1, track=op.resource, cat="pipe",
                      args={"batch": batch_idx})
            ctx[f"__wall_end_{op.name}"] = t1
        return out

    def _run_batch(self, batch_idx: int, ctx: dict) -> None:
        """Execute one mini-batch's operator DAG."""
        done: dict[str, Future] = {}

        def runner(op: Operator):
            waited_from = (time.perf_counter() if op.name in self.starvable
                           else None)
            for d in op.deps:
                done[d].result()
            return self._run_op(op, ctx, batch_idx, waited_from)

        for op in self.plan:
            done[op.name] = self.pools[op.resource].submit(runner, op)
        for f in done.values():
            f.result()

    # ------------------------------------------------------------------
    def run(self, make_ctx: Callable[[int], dict], n_batches: int) -> dict:
        """Drive ``n_batches`` through the pipeline; returns the wall time
        and the per-stage report."""
        t0 = time.perf_counter()
        inflight: list[Future] = []
        with ThreadPoolExecutor(self.prefetch_depth, "pipe-batch") as pool:
            for i in range(n_batches):
                inflight.append(pool.submit(
                    lambda i=i: self._run_batch(i, make_ctx(i))))
                while len(inflight) >= self.prefetch_depth:
                    inflight.pop(0).result()
            for f in inflight:
                f.result()
        return {
            "n_batches": n_batches,
            "wall_s": time.perf_counter() - t0,
            "stages": {k: {"wall_s": v.wall_s, "calls": v.calls, **v.sums,
                           **({"wait_s": v.wait_s} if k in self.starvable
                              else {})}
                       for k, v in self.timings.items()},
        }

    def close(self):
        for p in self.pools.values():
            p.shutdown(wait=False)
