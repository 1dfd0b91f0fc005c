#!/usr/bin/env python3
"""Smoke run of the out-of-core GNN system's main path on one TPU chip.

    python chip_smoke.py                                # on a TPU v5e host
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny sizes, any backend

Phases, each through the system's own entry points:

  train   ``OutOfCoreGNNTrainer``: GraphSAGE, hidden 256, batch 1024,
          fanouts (25, 10), 5 % device and 10 % host tiers, on the
          paper's CL graph (Table 1: row width 1024, average degree 42,
          skew 1.2, 12 shards) cut to 10^6 vertices, 4.1 GB of features.
          Checks: the loss is finite and falls on step 1's batch; step
          1's loss matches the same jitted step on the CPU backend; one
          batch's gathered rows are bit-identical to
          ``FeatureStore.read_rows``.
  serve   ``GNNInferenceServer`` with the trained parameters answers 16
          requests of 64 seeds.  Checks: every future resolves with
          finite logits of the expected shape.
  kernel  ``HeteroCache(fused_backend="pallas")``, the fused lookup
          kernel, on the largest table and batch its scalar memory takes.
          Checks: rows, miss lists and tier counts are bit-identical to
          the host backend on the same batch, and rows to the store.

Timings are host-clock wall times.  A full run exits non-zero, printing no
result, unless JAX's first device is a TPU.  ``--rehearse`` runs every
phase at tiny sizes on whatever backend is present, with the persistent
compilation cache off, and never reports ``"ok": true``.  The last line of
stdout is one JSON object; a failed phase exits 1 without it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

FANOUTS = (25, 10)
HIDDEN = 256
SIZES = {
    # the CL graph cut to 10^6 vertices; the kernel's table and batch are
    # the largest the TPU v5e scalar memory takes (N = 65,536 -> B <= 21,504)
    "full": dict(scale=1e-3, batch=1024, warmup=2, steps=8, requests=16,
                 request_seeds=64, kernel_rows=65_536, kernel_batch=21_504),
    "rehearse": dict(scale=5e-6, batch=32, warmup=2, steps=3, requests=4,
                     request_seeds=16, kernel_rows=2_048, kernel_batch=256),
}
# step 1 on the chip against the CPU backend: matmuls at the TPU's default
# precision take bf16 passes over f32 inputs
LOSS_RTOL = 1e-2
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def say(phase: str, key: str, value) -> None:
    print(f"{phase}: {key}: {value}", flush=True)


class CompileCounter:
    """Counts programs XLA built or loaded from the persistent cache, the
    seconds that took, and persistent-cache hits (``jax.monitoring``)."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def _on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1
                self.compile_s += secs

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False

    def snapshot(self) -> tuple:
        with self._lock:
            return self.compiles, self.compile_s, self.cache_hits


class StepRecorder:
    """Stands in for the trainer's jitted step: runs it, waits for its
    outputs, stamps the host clock, and keeps step 1's inputs for the CPU
    reference."""

    def __init__(self, step, counter: CompileCounter):
        self.step = step
        self.counter = counter
        self.first_args = None
        self.walls = []             # call to outputs ready, per step
        self.ends = []              # host clock after each step's outputs
        self.compiles = []          # compile count at each step's end

    def __call__(self, *args):
        import jax
        if self.first_args is None:
            self.first_args = args
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.step(*args))
        self.ends.append(time.perf_counter())
        self.walls.append(self.ends[-1] - t0)
        self.compiles.append(self.counter.snapshot()[0])
        return out


def make_data(sz: dict, root: str, seed: int):
    from repro.gnn.graph import make_dataset
    t0 = time.perf_counter()
    g, store, spec = make_dataset("CL", root, scale=sz["scale"], seed=seed)
    say("data", "cut", f"CL vertices {spec.n_vertices:,} -> {g.n_vertices:,} "
        f"(scale {sz['scale']:g}); row width {store.row_dim}, average "
        f"degree {g.n_edges // g.n_vertices}, skew {spec.skew}, "
        f"{store.n_shards} shards unchanged")
    say("data", "features on storage (GB)",
        f"{store.n_rows * store.row_bytes / 1e9:.3f}")
    say("data", "set-up wall s", f"{time.perf_counter() - t0:.1f}")
    return g, store


def phase_train(sz, g, store, counter, seed):
    import jax
    from repro.gnn.sampling import draw_unique
    from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig

    cfg = TrainerConfig(model="sage", hidden=HIDDEN, batch_size=sz["batch"],
                        fanouts=FANOUTS, mode="helios", seed=seed)
    n_steps = sz["warmup"] + sz["steps"]
    c0 = counter.snapshot()
    with OutOfCoreGNNTrainer(g, store, cfg) as tr:
        rec = StepRecorder(tr.step_fn, counter)
        tr.step_fn = rec
        t0 = time.perf_counter()
        out = tr.train(n_steps)
        wall = time.perf_counter() - t0
        losses = [m["loss"] for m in tr.metrics_log]
        w = sz["warmup"] - 1
        timed = rec.ends[-1] - rec.ends[w]
        say("train", "padded batch rows", f"{len(rec.first_args[1]):,} "
            f"({rec.first_args[1].nbytes / 1e9:.2f} GB of features/step)")
        c1 = counter.snapshot()
        say("train", "compilations / compile s, all steps",
            f"{c1[0] - c0[0]} / {c1[1] - c0[1]:.1f}")
        say("train", "step wall time after warm-up (host clock, "
            "block_until_ready) s", f"{timed / sz['steps']:.4f}")
        say("train", "jitted step wall after warm-up, median (host clock, "
            "call to block_until_ready) s",
            f"{float(np.median(rec.walls[w + 1:])):.4f}")
        say("train", "compilations during the timed steps",
            rec.compiles[-1] - rec.compiles[w])
        say("train", "total wall s", f"{wall:.1f}")
        say("train", "cache hit rate", f"{out['cache']['hit_rate']:.4f}")
        say("train", "losses", [round(x, 5) for x in losses])
        check(len(losses) == n_steps, f"{len(losses)} losses for {n_steps} steps")
        check(bool(np.all(np.isfinite(losses))), "non-finite loss")
        # the loss on step 1's batch, before and after training: one fixed
        # batch, so batch-to-batch noise cannot hide or fake the fall
        _, m_end = rec.step(tr.state, *rec.first_args[1:])
        end = float(m_end["loss"])
        say("train", "step 1 batch loss before / after training",
            f"{losses[0]:.5f} / {end:.5f}")
        check(np.isfinite(end) and end < losses[0], "loss did not fall")
        say("train", "check loss finite and falling", "passed")

        cpu = jax.devices("cpu")[0]
        _, m_cpu = rec.step(*jax.device_put(rec.first_args, cpu))
        ref = float(m_cpu["loss"])
        say("train", "step 1 loss device / cpu", f"{losses[0]!r} / {ref!r} "
            f"(rel diff {abs(losses[0] - ref) / abs(ref):.3e}, "
            f"tolerance {LOSS_RTOL:g})")
        check(abs(losses[0] - ref) <= LOSS_RTOL * abs(ref),
              "step 1 loss differs from the CPU backend")
        say("train", "check step 1 against CPU backend", "passed")

        rng = np.random.default_rng([seed, 7])
        mb = tr.sampler.sample(draw_unique(rng, g.n_vertices, cfg.batch_size))
        ids = mb.all_nodes
        rows = tr.cache.complete_planned(
            tr.cache.submit_planned(ids, n_rows=len(mb.nodes)))
        check(np.array_equal(rows[:len(ids)], store.read_rows(ids))
              and not rows[len(ids):].any(),
              "gathered rows differ from FeatureStore.read_rows")
        say("train", "check gathered rows bit-identical to the store",
            f"passed ({len(ids):,} rows)")
        return tr.state["params"]


def phase_serve(sz, g, store, counter, seed, params):
    from repro.gnn.sampling import draw_unique
    from repro.serving import GNNInferenceServer, ServerConfig
    from repro.serving.scheduler import PriorityClass

    cfg = ServerConfig(model="sage", hidden=HIDDEN,
                       request_batch_size=sz["request_seeds"],
                       fanouts=FANOUTS, mode="helios", max_batch_requests=4,
                       seed=seed)
    # offline scoring class: the budget is wide enough that nothing sheds
    klass = PriorityClass("smoke", 1, budget_v=1.0)
    rng = np.random.default_rng([seed, 11])
    c0 = counter.snapshot()
    with GNNInferenceServer(g, store, cfg, params=params) as srv:
        t0 = time.perf_counter()
        futs = [srv.submit(draw_unique(rng, g.n_vertices, sz["request_seeds"]),
                           klass) for _ in range(sz["requests"])]
        srv.flush()
        wall = time.perf_counter() - t0
        res = [f.result(timeout=600) for f in futs]
        hit = srv.cache.stats().hit_rate
    c1 = counter.snapshot()
    say("serve", "requests", f"{len(futs)} of {sz['request_seeds']} seeds")
    say("serve", "wall s for all requests (host clock)", f"{wall:.3f}")
    say("serve", "compilations", c1[0] - c0[0])
    say("serve", "compile s", f"{c1[1] - c0[1]:.1f}")
    say("serve", "cache hit rate", f"{hit:.4f}")
    check(all(r is not None for r in res), "a request was shed")
    n_classes = g.n_classes
    check(all(r["logits"].shape == (sz["request_seeds"], n_classes)
              and np.all(np.isfinite(r["logits"])) for r in res),
          "logits of the wrong shape or not finite")
    say("serve", "check every future resolved with finite logits", "passed")


def phase_kernel(sz, root, counter, seed, backend):
    from repro.core.hetero_cache import HeteroCache
    from repro.core.iostack import FeatureStore
    from repro.gnn.graph import synth_graph

    n, b = sz["kernel_rows"], sz["kernel_batch"]
    g = synth_graph(n, 42, 1.2, seed)
    store = FeatureStore(os.path.join(root, "kernel_features"), n_rows=n,
                         row_dim=1024, create=True, rng_seed=seed)
    hot = np.bincount(g.col, minlength=n)
    # sampled-neighbour accesses: skewed, with duplicates
    ids = g.col[np.random.default_rng([seed, 13]).integers(0, g.n_edges, b)]
    got = {}
    for be in ("host", backend):
        with HeteroCache(store, hot, n // 20, n // 10,
                         fused_backend=be) as cache:
            c0 = counter.snapshot()
            t0 = time.perf_counter()
            pg = cache.submit_planned(ids)
            t1 = time.perf_counter()
            rows = cache.complete_planned(pg).copy()
            t2 = time.perf_counter()
            pg2 = cache.submit_planned(ids)
            t3 = time.perf_counter()
            cache.complete_planned(pg2)
            got[be] = (rows, pg.plan[2], pg.plan[3], pg.occ)
        c1 = counter.snapshot()
        say("kernel", f"{be} lookup wall s, first / second call",
            f"{t1 - t0:.4f} / {t3 - t2:.4f}")
        if be != "host":
            say("kernel", "compilations", c1[0] - c0[0])
            say("kernel", "compile s", f"{c1[1] - c0[1]:.1f}")
    say("kernel", "shape", f"table {n:,} x 1024, batch {b:,} ids "
        f"({len(np.unique(ids)):,} distinct)")
    (r_h, m_h, x_h, o_h), (r_k, m_k, x_k, o_k) = got["host"], got[backend]
    check(np.array_equal(r_h, r_k), "kernel rows differ from the host backend")
    check(all(np.array_equal(a, c) for a, c in zip(m_h + x_h, m_k + x_k)),
          "kernel miss lists differ from the host backend")
    check(o_h == o_k, f"kernel tier counts {o_k} != host {o_h}")
    check(np.array_equal(r_k, store.read_rows(ids)),
          "kernel rows differ from FeatureStore.read_rows")
    say("kernel", "tier counts device/host/storage/remote", o_k)
    say("kernel", f"check {backend} bit-identical to host backend", "passed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # step 1 is checked against the CPU backend, so keep it available
    # where JAX_PLATFORMS names the platforms
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, JAX found {device}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache

    cache_dir = ("off (rehearsal)" if args.rehearse
                 else compile_cache.enable())
    say("device", "platform / kind / count",
        f"{device['platform']} / {device['kind']} / {device['count']}")
    say("device", "compile cache", cache_dir)
    sz = SIZES["rehearse" if args.rehearse else "full"]
    backend = "pallas" if dev.platform == "tpu" else "pallas-interpret"
    failed = []
    root = tempfile.mkdtemp(prefix="helios_chip_smoke_")
    try:
        with CompileCounter() as counter:
            g, store = make_data(sz, root, args.seed)
            params = None
            for name, run in (
                    ("train", lambda: phase_train(sz, g, store, counter,
                                                  args.seed)),
                    ("serve", lambda: phase_serve(sz, g, store, counter,
                                                  args.seed, params)),
                    ("kernel", lambda: phase_kernel(sz, root, counter,
                                                    args.seed, backend))):
                try:
                    t0 = time.perf_counter()
                    res = run()
                    if name == "train":
                        params = res
                    say(name, "phase", f"passed in {time.perf_counter() - t0:.1f} s")
                except Exception:
                    traceback.print_exc()
                    say(name, "phase", "FAILED")
                    failed.append(name)
            say("device", "persistent compile-cache hits", counter.cache_hits)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
