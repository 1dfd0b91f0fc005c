"""Serving cells: open-loop online inference through
``GNNInferenceServer.submit``/``flush``.

A generator thread hands each request to the server loop at its due time
(``loadgen``); the server loop, the only caller of the server, submits
what has arrived and flushes.  A request's latency runs from its due time
to its logits on the host.  Requests due in the window are waited for up
to a minute past its close; one that never completes counts as missing.
"""
from __future__ import annotations

import math
import queue
import threading
import time

import jax
import numpy as np

import harness
import loadgen
import refcore
import system

LATE_WAIT_S = 60.0


class SampleLog:
    """Keeps every batch the server's sampler draws, by its seeds."""

    def __init__(self, sampler):
        self.sampler, self._sample = sampler, sampler.sample
        self.by_seeds = {}
        sampler.sample = self.sample

    def sample(self, seeds):
        mb = self._sample(seeds)
        self.by_seeds.setdefault(np.asarray(seeds).tobytes(), mb)
        return mb

    def detach(self):
        del self.sampler.sample


def server_config(cfg: dict, mix: dict, seed: int):
    from repro.serving import ServerConfig

    return ServerConfig(request_batch_size=mix["seeds_per_request"],
                        max_batch_requests=mix["max_batch_requests"],
                        seed=seed, **system.system_kwargs(cfg))


def serve_open_loop(srv, klass, reqs, due, t0):
    """Drive the server from this thread; returns per-request completion
    times (``nan`` where missing) and how late the generator handed each
    request over."""
    n = len(reqs)
    done = np.full(n, np.nan)
    late = np.zeros(n)
    results = [None] * n
    q = queue.Queue()

    def generate():
        for i in range(n):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - (t0 + due[i])
            q.put(i)
        q.put(None)

    def finished(i):
        def cb(fut):
            done[i] = time.perf_counter()
            results[i] = fut.result()
        return cb

    gen = threading.Thread(target=generate, name="bench-loadgen")
    gen.start()
    deadline = t0 + due[-1] + LATE_WAIT_S
    ended = False
    try:
        while not ended:
            batch = [q.get(timeout=max(0.0, deadline - time.perf_counter()))]
            while True:
                try:
                    batch.append(q.get_nowait())
                except queue.Empty:
                    break
            ended = None in batch
            for i in (j for j in batch if j is not None):
                srv.submit(reqs[i], klass).add_done_callback(finished(i))
            srv.flush()
    except queue.Empty:
        pass
    finally:
        gen.join()
    return done, late, results


def run(r: harness.Spec) -> dict:
    from repro.serving import GNNInferenceServer
    from repro.serving.scheduler import PriorityClass

    cfg, mix = r.cfg, r.traffic
    arch = harness.load_ref(cfg["arch"])
    res = {"timings": {}}
    # one class whose budget admission never sheds
    klass = PriorityClass("bench", 0, budget_v=1e9)
    with harness.CompileCounter() as cc:
        data = system.make_data(cfg)
        res["timings"].update(data.timings)
        t0 = time.perf_counter()
        params0 = system.init_params(arch, cfg, r.seed)
        pop = loadgen.popularity(data.col, cfg["n_vertices"])
        k = mix["seeds_per_request"]
        warm = loadgen.request_seeds(pop, k, mix["warmup_requests"],
                                     np.random.default_rng([r.seed, 7]))
        due = loadgen.due_times(mix["rate_rps"], r.seconds, r.seed)
        reqs = loadgen.request_seeds(pop, k, len(due),
                                     np.random.default_rng([r.seed, 8]))
        srv = GNNInferenceServer(data.graph, data.store,
                                 server_config(cfg, mix, r.seed),
                                 params=params0)
        res["timings"]["server_init_s"] = time.perf_counter() - t0
        try:
            t0 = time.perf_counter()
            for s in warm:
                fut = srv.submit(s, klass)
                srv.flush()
                fut.result()
            res["timings"]["warmup_s"] = time.perf_counter() - t0
            log = SampleLog(srv.sampler)
            results = window(r, srv, klass, reqs, due, cc, res)
            log.detach()
        finally:
            srv.close()
            del srv
    res["checks"], res["readings"] = check(r, arch, data, params0, reqs,
                                           results, log)
    res["correct"] = harness.within(res["checks"])
    return res


def window(r, srv, klass, reqs, due, cc, res) -> list:
    """Serve the mix for the window; fills ``res`` with the end-to-end
    metrics and the per-layer context, returns each request's result."""
    from repro.obs import trace as obs_trace

    import tracing

    c0 = cc.snapshot()[0]
    cache0 = srv.cache.stats()
    prof = tracer = None
    if r.trace:
        tracer = obs_trace.install()
        prof = tracing.Window(harness.tmp_root())
        prof.__enter__()
    t0 = time.perf_counter()
    done, late, results = serve_open_loop(srv, klass, reqs, due, t0)
    if prof is not None:
        prof.__exit__(None, None, None)
        obs_trace.uninstall()
    res["memory_peak_bytes"] = harness.memory_peak_bytes()
    lat = done - (t0 + due)
    res["attempted"] = len(due)
    res["failed"] = int(np.count_nonzero(np.isnan(lat)))
    lat_ms = [1000.0 * x if math.isfinite(x) else math.inf for x in lat]
    res["end_to_end"] = {
        "serve_p50_ms": harness.percentile(lat_ms, 50),
        "serve_p95_ms": harness.percentile(lat_ms, 95),
        "serve_completed_rps": float(np.count_nonzero(
            done <= t0 + r.seconds)) / r.seconds,
        "setup_s": t0 - r.t_start}
    ctx = {"cache": srv.cache.stats.delta(cache0)._values(),
           "compiles": cc.snapshot()[0] - c0, "gen_late_s": list(late)}
    if prof is not None:
        import shutil

        planes = prof.planes()
        ctx["trace"] = tracing.reduce(
            planes, tracing.tracer_spans(tracer, prof, planes))
        spans = {}
        for s in tracer.spans:
            if s.name.startswith("serve."):
                spans.setdefault(s.name, []).append(s.t1 - s.t0)
        ctx["spans"] = spans
        shutil.rmtree(prof.log_dir, ignore_errors=True)
    res["ctx"] = ctx
    return results


def check(r, arch, data, params0, reqs, results, log):
    """Compare a sample of the served requests, drawn from the seed, with
    the plain reference; returns the checks and ``readings(dot)``, the
    logit gap with the reference's products taken by ``dot``."""
    cfg, mix, lim = r.cfg, r.traffic, r.limits()
    fanouts = tuple(cfg["fanouts"])
    k = mix["seeds_per_request"]
    fmax = k * fanouts[0]
    fn = harness.feature_fn(cfg["feature_dim"])
    key = harness.feature_key(cfg["feature_seed"])

    def rows(ids):
        return fn(key, jax.numpy.asarray(np.asarray(ids, np.int32)))

    served = [i for i, x in enumerate(results) if x is not None]
    missing = len(results) - len(served)
    rng = np.random.default_rng([r.seed, 9])
    pick = sorted(rng.choice(served, min(len(served), mix["check_requests"]),
                             replace=False)) if served else []
    faults = bad_edges = 0
    trees, got = [], []
    for i in pick:
        mb = log.by_seeds.get(np.asarray(reqs[i], np.int64).tobytes())
        logits = np.asarray(results[i]["logits"])
        if mb is None or logits.shape != (k, cfg["n_classes"]):
            faults += 1
            continue
        t, f = refcore.tree_from_blocks(
            mb.nodes, mb.node_mask,
            [(x.src_pos, x.dst_pos, x.edge_mask) for x in mb.blocks],
            mb.labels, fanouts)
        faults += f + int(not np.array_equal(t.seeds, reqs[i]))
        bad_edges += refcore.tree_bad_edges(data.rowptr, data.col, t)
        trees.append(t)
        got.append(logits)

    def readings(dot, half=False):
        if half or not trees:
            return {"logit_gap": math.nan}
        ref = refcore.serve_reference(arch, params0, trees, rows, dot, fmax)
        return {"logit_gap": refcore.logit_gap(np.concatenate(got),
                                               np.concatenate(ref))}

    nums = readings(refcore.dot_highest)
    checks = {"logit_gap": harness.check(nums["logit_gap"], lim["logit_gap"]),
              "missing": harness.check(missing, 0),
              "bad_edges": harness.check(bad_edges, 0),
              "faults": harness.check(faults, 0)}
    return checks, readings
