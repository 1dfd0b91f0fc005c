"""Observability stack: tracer spans, metrics registry, Chrome export,
the serving overlap report, atomic stats snapshots — and the
zero-behavior-change guarantee (bit-identical gathers with tracing on vs
off)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.hetero_cache import HeteroCache
from repro.core.iostack import AsyncIOEngine, FeatureStore, SyncIOEngine
from repro.ft.chaos import ChaosSchedule, RetryPolicy
from repro.gnn.graph import synth_graph
from repro.gnn.train import OutOfCoreGNNTrainer, TrainerConfig
from repro.obs import analyze as obs_analyze
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.export import validate_trace, write_trace

N_ROWS, ROW_DIM, N_SHARDS = 4096, 32, 4


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    p = tmp_path_factory.mktemp("obs_feats")
    return FeatureStore(str(p), n_rows=N_ROWS, row_dim=ROW_DIM,
                        n_shards=N_SHARDS, create=True, rng_seed=0)


@pytest.fixture()
def tracer():
    """A fresh installed tracer, uninstalled (restoring any prior one,
    e.g. a HELIOS_TRACE session tracer) after the test."""
    prev = obs_trace.TRACER
    tr = obs_trace.install()
    yield tr
    obs_trace.TRACER = prev


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_and_parenting(tracer):
    with tracer.span("outer", track="t") as outer:
        assert tracer.current() == outer.sid
        with tracer.span("inner") as inner:
            assert inner.parent == outer.sid
        sid = tracer.record("recorded", tracer.epoch, tracer.epoch + 1,
                            parent=tracer.current())
    assert tracer.current() is None
    by_id = {s.sid: s for s in tracer.spans}
    assert by_id[sid].parent == outer.sid
    # inner closed before outer -> appended first
    assert [s.name for s in tracer.spans] == ["inner", "recorded", "outer"]
    assert all(s.t1 >= s.t0 for s in tracer.spans)


def test_span_virtual_stamps_and_error_flag(tracer):
    with pytest.raises(RuntimeError):
        with tracer.span("boom") as sp:
            sp.set_virtual(1.0, 3.5)
            raise RuntimeError("x")
    sp = tracer.spans[-1]
    assert sp.args["error"] is True
    assert sp.virt_s == pytest.approx(2.5)
    tracer.instant("evt", track="t", args={"k": 1})
    assert tracer.events[-1][0] == "evt"


def test_uninstall_returns_spans_intact():
    prev = obs_trace.TRACER
    try:
        tr = obs_trace.install()
        with tr.span("a"):
            pass
        got = obs_trace.uninstall()
        assert got is tr and len(got.spans) == 1
        assert obs_trace.TRACER is None
    finally:
        obs_trace.TRACER = prev


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_counters_gauges_histograms():
    reg = obs_metrics.Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    h = reg.histogram("h")
    for v in range(1, 101):
        h.observe(float(v))
    assert reg.counter("c").value == 5
    assert reg.gauge("g").value == 2.5
    assert h.count == 100 and h.summary()["min"] == 1.0
    assert h.percentile(50) == pytest.approx(50.0, abs=2.0)
    assert h.percentile(99) == pytest.approx(99.0, abs=2.0)
    snap = reg.snapshot()
    assert snap["c"] == 5 and snap["h.count"] == 100
    with pytest.raises(TypeError):
        reg.gauge("c")
    reg.reset()
    assert reg.snapshot() == {}


def test_histogram_reservoir_bounded_and_deterministic():
    a, b = obs_metrics.Histogram("x"), obs_metrics.Histogram("x")
    for v in range(20000):
        a.observe(float(v))
        b.observe(float(v))
    assert len(a._res) <= a.cap
    assert a.count == 20000 and a.sum == b.sum
    assert a.percentile(50) == b.percentile(50)    # same seed, same stream
    assert 0 <= a.percentile(50) <= 20000


def test_stats_publish_into_registry(store):
    obs_metrics.REGISTRY.reset()
    eng = AsyncIOEngine(store)
    eng.submit(np.arange(512)).wait()
    eng.stats.publish("t.io")
    snap = obs_metrics.REGISTRY.snapshot()
    assert snap["t.io.requests"] == 512 and snap["t.io.bytes"] > 0
    assert snap["t.io.bw"] > 0
    eng.close()
    obs_metrics.REGISTRY.reset()


# ---------------------------------------------------------------------------
# stats snapshots (satellite 1)
# ---------------------------------------------------------------------------

def test_iostats_snapshot_and_delta(store):
    eng = AsyncIOEngine(store)
    eng.submit(np.arange(256)).wait()
    before = eng.stats.snapshot()
    assert before.requests == eng.stats.requests
    eng.submit(np.arange(256, 768)).wait()
    d = eng.stats.delta(before)
    assert d.batches >= 1 and d.requests == 512 and d.bytes > 0
    # a snapshot is frozen; the live stats keep moving
    assert before.requests + d.requests == eng.stats.requests
    eng.close()


def test_cache_stats_callable_snapshot(store):
    ids = np.random.default_rng(0).integers(0, N_ROWS, 2048)
    eng = AsyncIOEngine(store)
    cache = HeteroCache(store, np.arange(N_ROWS)[::-1], 256, 512, eng)
    t = cache.submit_planned(ids[:1024])
    cache.complete_planned(t)
    snap = cache.stats()                 # atomic snapshot via __call__
    assert snap.device_hits == cache.stats.device_hits
    assert snap.hit_rate == pytest.approx(cache.stats.hit_rate)
    t = cache.submit_planned(ids[1024:])
    cache.complete_planned(t)
    d = cache.stats().delta(snap)
    assert (d.device_hits + d.host_hits + d.storage_misses
            + d.remote_hits) == 1024
    assert d.batches == 1
    eng.close()


# ---------------------------------------------------------------------------
# engine + cache span coverage, bit-identical gathers (tier-1 guarantee)
# ---------------------------------------------------------------------------

def test_engine_spans_and_identical_gathers(store, tracer):
    rng = np.random.default_rng(1)
    batches = [rng.integers(0, N_ROWS, 777) for _ in range(4)]
    obs_trace.TRACER = None              # tracing OFF
    eng = AsyncIOEngine(store)
    want = [eng.submit(b).wait()[0] for b in batches]
    eng.close()
    obs_trace.TRACER = tracer            # tracing ON
    eng = AsyncIOEngine(store)
    got = [eng.submit(b).wait()[0] for b in batches]
    eng.close()
    for w, g in zip(want, got):
        assert (w == g).all()            # bit-identical with tracing on
    names = {s.name for s in tracer.spans}
    assert {"io.submit.read", "io.qwait", "io.service.r",
            "io.ticket.read"} <= names
    # worker/ticket spans parent the submit span across threads
    by_id = {s.sid: s for s in tracer.spans}
    submits = {s.sid for s in tracer.spans if s.name == "io.submit.read"}
    for s in tracer.spans:
        if s.name in ("io.qwait", "io.service.r", "io.ticket.read"):
            assert s.parent in submits or s.parent is None
        if s.parent is not None:
            assert s.parent in by_id and s.parent != s.sid


def test_disabled_tracer_records_nothing(store):
    """An installed but disabled tracer costs one flag test per site: it
    records no span and no event, and the bytes are the same."""
    prev = obs_trace.TRACER
    tr = obs_trace.install()
    tr.enabled = False
    try:
        b = np.random.default_rng(6).integers(0, N_ROWS, 2048)
        eng = AsyncIOEngine(store)
        got, _ = eng.submit(b).wait()
        eng.close()
    finally:
        obs_trace.TRACER = prev
    assert tr.spans == [] and tr.events == []
    np.testing.assert_array_equal(got, store.read_rows(b))


def test_enabled_tracer_cost_is_per_shard_batch_not_per_row(store, tracer):
    """Enabled tracing records a fixed set of spans per ticket and shard
    batch, so its cost does not grow with the rows a ticket reads."""
    eng = AsyncIOEngine(store)
    counts = []
    for n in (64, 4096):
        tracer.clear()
        ids = np.random.default_rng(n).permutation(N_ROWS)[:n]
        assert len(np.unique(ids % N_SHARDS)) == N_SHARDS
        eng.submit(ids).wait()
        counts.append(len(tracer.spans))
    eng.close()
    assert counts[0] == counts[1] <= 4 * N_SHARDS + 2


def test_sync_engine_spans(store, tracer):
    eng = SyncIOEngine(store)
    eng.submit(np.arange(128))
    assert any(s.name == "io.sync.read" for s in tracer.spans)


def test_cache_spans_nest_engine_spans(store, tracer):
    ids = np.random.default_rng(2).integers(0, N_ROWS, 1024)
    eng = AsyncIOEngine(store)
    cache = HeteroCache(store, np.arange(N_ROWS)[::-1], 128, 256, eng)
    t = cache.submit_planned(ids)
    cache.complete_planned(t)
    eng.close()
    by_id = {s.sid: s for s in tracer.spans}
    sub = [s for s in tracer.spans if s.name == "cache.gather.submit"]
    assert sub and any(s.name == "cache.gather.complete"
                       for s in tracer.spans)
    # engine submit spans opened inside the cache phase parent to it
    io_subs = [s for s in tracer.spans if s.name == "io.submit.read"]
    assert io_subs and all(
        by_id[s.parent].name.startswith("cache.") for s in io_subs
        if s.parent is not None)


# ---------------------------------------------------------------------------
# retry / hedge spans under chaos (satellite 3)
# ---------------------------------------------------------------------------

def test_retry_instants_under_chaos(store, tracer):
    eng = AsyncIOEngine(store,
                        chaos=ChaosSchedule(seed=7, read_error_rate=0.05),
                        retry=RetryPolicy(deadline_s=5e-4,
                                          backoff_base_s=2e-5))
    rng = np.random.default_rng(3)
    clean = None
    for _ in range(6):
        b = rng.integers(0, N_ROWS, 2048)
        d, _ = eng.submit(b).wait()
    assert eng.stats.retries > 0
    eng.close()
    retries = [e for e in tracer.events if e[0] == "ft.retry.r"]
    assert retries, "chaos retries must surface as ft.retry instants"
    name, t, track, cat, tname, args = retries[0]
    assert cat == "ft" and args["retries"] >= 1 and track.startswith("s")
    del clean


def test_chaos_env_gathers_identical_when_traced(store, tracer):
    """Same chaos seed, tracing on vs off: recovery path is span-invariant."""
    b = np.random.default_rng(4).integers(0, N_ROWS, 4096)
    ch = ChaosSchedule(seed=11, read_error_rate=0.03)
    obs_trace.TRACER = None
    eng = AsyncIOEngine(store, chaos=ch,
                        retry=RetryPolicy(backoff_base_s=2e-5))
    want, _ = eng.submit(b).wait()
    eng.close()
    obs_trace.TRACER = tracer
    eng = AsyncIOEngine(store, chaos=ChaosSchedule(seed=11,
                                                   read_error_rate=0.03),
                        retry=RetryPolicy(backoff_base_s=2e-5))
    got, _ = eng.submit(b).wait()
    eng.close()
    assert (want == got).all()
    assert any(e[0] == "ft.retry.r" for e in tracer.events)


# ---------------------------------------------------------------------------
# traced training epoch: export schema, parenting, per-batch attribution
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_epoch(tmp_path_factory):
    prev = obs_trace.TRACER
    tr = obs_trace.install()
    g = synth_graph(5000, 8, skew=1.0, seed=0)
    p = tmp_path_factory.mktemp("obs_epoch")
    st = FeatureStore(str(p / "f"), n_rows=5000, row_dim=32, n_shards=4,
                      create=True, rng_seed=3)
    with OutOfCoreGNNTrainer(g, st, TrainerConfig(
            mode="helios", batch_size=64, fanouts=(4, 3), hidden=32,
            presample_batches=2)) as trn:
        out = trn.train(6)
    obs_trace.TRACER = prev
    return tr, out


STAGES = ("sample", "io_submit", "cache_lookup", "io_complete",
          "cache_refresh", "batch_build", "train")


def test_traced_epoch_report_and_obs(traced_epoch):
    """The report is the host's wall clock: the run's wall time and one
    stage timing per operator, each called once a batch."""
    tr, out = traced_epoch
    assert set(out) == {"n_batches", "wall_s", "stages", "cache", "io",
                        "loss_first", "loss_last"}
    assert out["n_batches"] == 6 and out["wall_s"] > 0.0
    assert set(out["stages"]) == set(STAGES)
    for name, st in out["stages"].items():
        assert st["calls"] == 6
        assert 0.0 < st["wall_s"] <= out["wall_s"]
    assert "wait_s" in out["stages"]["batch_build"]
    assert not any("overlap" in k or "bubble" in k for k in out["io"])


def test_concurrent_batch_spans_well_formed(traced_epoch):
    tr, out = traced_epoch
    pipe = [s for s in tr.spans if s.cat == "pipe"]
    assert pipe
    by_id = {s.sid: s for s in tr.spans}
    for s in pipe:
        assert s.args["batch"] >= 0
        assert s.v0 is None and s.v1 is None        # wall-stamped only
        assert 0.0 <= s.t0 <= s.t1 <= tr.now()
        assert s.track in ("host", "io", "device")
        if s.parent is not None:
            assert s.parent in by_id
    assert len({s.args["batch"] for s in pipe}) == 6
    # batches overlap on the wall clock: at depth 2 some batch starts
    # before the previous one has finished
    first = {b: min(s.t0 for s in pipe if s.args["batch"] == b)
             for b in range(6)}
    last = {b: max(s.t1 for s in pipe if s.args["batch"] == b)
            for b in range(6)}
    assert any(first[b + 1] < last[b] for b in range(5))


def test_traced_epoch_spans_cover_every_operator(traced_epoch):
    """Every operator of every batch leaves exactly one ``pipe.<op>`` span,
    and each span's wall matches the stage report's sum."""
    tr, out = traced_epoch
    pipe = [s for s in tr.spans if s.cat == "pipe"]
    seen = {}
    for s in pipe:
        key = (s.name, s.args["batch"])
        seen[key] = seen.get(key, 0) + 1
    assert seen == {(f"pipe.{op}", b): 1 for op in STAGES for b in range(6)}
    for op in STAGES:
        walls = sum(s.wall_s for s in pipe if s.name == f"pipe.{op}")
        assert walls == pytest.approx(out["stages"][op]["wall_s"], rel=1e-6)


def test_chrome_export_schema(traced_epoch, tmp_path):
    tr, _ = traced_epoch
    doc = write_trace(tr, str(tmp_path / "trace.json"))
    validate_trace(doc)                  # raises on malformed events
    with open(tmp_path / "trace.json") as fh:
        ondisk = json.load(fh)
    assert ondisk["traceEvents"]
    evs = ondisk["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert "X" in phases and "M" in phases
    pids = {e["pid"] for e in evs if e["ph"] == "X"}
    assert pids == {2}                   # training spans: the wall timeline
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["name"] for e in meta}
    assert {"process_name", "thread_name"} <= names
    # one named track per shard worker and per pipeline resource
    tracks = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert {"ssd0", "device", "io"} <= tracks
    x = [e for e in evs if e["ph"] == "X"]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in x)


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace({"nope": []})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "X", "pid": 1, "tid": 1,
                                         "ts": -5, "dur": 1, "name": "x"}]})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "?", "pid": 1, "tid": 1,
                                         "name": "x"}]})


# ---------------------------------------------------------------------------
# HELIOS_TRACE env plumbing (satellite 2)
# ---------------------------------------------------------------------------

def test_env_var_installs_tracer_and_exports(store, tmp_path):
    out = tmp_path / "envtrace.json"
    code = ("import numpy as np\n"
            "from repro.core.iostack import AsyncIOEngine, FeatureStore\n"
            f"s = FeatureStore({store.path!r}, n_rows={N_ROWS}, "
            f"row_dim={ROW_DIM}, n_shards={N_SHARDS})\n"
            "e = AsyncIOEngine(s)\n"
            "e.submit(np.arange(512)).wait()\n"
            "e.close()\n")
    env = dict(os.environ, HELIOS_TRACE=str(out),
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd="/root/repo",
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    with open(out) as fh:
        doc = json.load(fh)
    validate_trace(doc)
    assert any(e.get("name") == "io.ticket.read"
               for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# analyzer unit + property tests (satellite 3)
# ---------------------------------------------------------------------------

def test_overlap_report_bounds_and_serial_zero():
    r = obs_analyze.overlap_report({"serial": 10.0}, 10.0)
    assert r["overlap_efficiency"] == 0.0
    r = obs_analyze.overlap_report({"io": 8.0, "device": 8.0}, 8.0)
    assert r["overlap_efficiency"] == 1.0
    assert r["bubble_frac"] == 0.0


try:
    import hypothesis.strategies as hst
    from hypothesis import given, settings
    _HAS_HYPOTHESIS = True
except ImportError:                      # optional dep: drop ONLY the
    _HAS_HYPOTHESIS = False              # property tests, keep the module

if _HAS_HYPOTHESIS:
    @given(hst.lists(hst.tuples(hst.floats(0.001, 5), hst.integers(0, 2)),
                     min_size=1, max_size=40),
           hst.floats(0.0, 1.0))
    @settings(max_examples=50, deadline=None)
    def test_overlap_report_bounded(items, slack):
        """Any busy split and any makespan from the busiest resource's
        time (full overlap) to the summed time (serial) reads in [0, 1]."""
        res_names = ("host", "io", "device")
        busy = {}
        for d, r in items:
            busy[res_names[r]] = busy.get(res_names[r], 0.0) + d
        total, busiest = sum(busy.values()), max(busy.values())
        makespan = busiest + slack * (total - busiest)
        r = obs_analyze.overlap_report(busy, makespan)
        assert 0.0 <= r["overlap_efficiency"] <= 1.0
        assert 0.0 <= r["bubble_frac"] <= 1.0
        assert r["sum_busy_s"] == pytest.approx(total)
        if len(busy) > 1 and total - busiest > 1e-3:
            assert r["overlap_efficiency"] == pytest.approx(1.0 - slack,
                                                            abs=1e-6)
