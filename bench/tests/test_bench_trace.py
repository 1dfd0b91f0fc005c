"""The trace-to-metrics reduction on a small synthetic trace."""
from __future__ import annotations

import pytest

import tracing


def trace(device_events, window=(100, 1100), modules=None):
    dev_lines = [{"name": tracing.OPS_LINE, "events": device_events}]
    if modules is not None:
        dev_lines.append({"name": tracing.MODULES_LINE, "events": modules})
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            (tracing.WINDOW, window[0], window[1] - window[0])]}]},
        {"name": "/device:TPU:0", "lines": dev_lines},
        {"name": "/device:TPU_NON_CORE:0", "lines": [
            {"name": tracing.OPS_LINE, "events": [("x", 0, 10_000)]}]},
    ]


def test_busy_is_union_clipped_to_window():
    ev = [("a", 50, 100),      # 100..150 inside the window
          ("b", 120, 80),      # overlaps a: union 100..200
          ("c", 500, 100),     # 500..600
          ("d", 1050, 100)]    # clipped to 1050..1100
    r = tracing.reduce(trace(ev))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((100 + 100 + 50) * 1e-9)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(750e-9)


def test_programs_ranked_by_module_and_gaps_labelled():
    ev = [("fusion.1", 100, 300), ("fusion.2", 700, 100)]
    mods = [("jit_step(17)", 100, 300), ("jit_take(3)", 700, 100)]
    spans = [("pipe.sample", 350, 650), ("pipe.train", 800, 1200)]
    r = tracing.reduce(trace(ev, modules=mods), spans)
    assert r["device_ops"][0][0] == "jit_step"
    assert r["device_ops"][0][1] == pytest.approx(300e-9)
    assert [g[0] for g in r["idle_gaps"]] == ["pipe.sample", "pipe.train"]
    assert r["idle_gaps"][0][1] == pytest.approx(300e-9)


def test_nothing_to_read():
    assert tracing.reduce([]) is None
    host_only = trace([])[:1]
    assert tracing.reduce(host_only) is None


def test_tracer_spans_on_trace_clock():
    class Span:
        def __init__(self, name, t0, t1):
            self.name, self.t0, self.t1 = name, t0, t1

    class Tracer:
        epoch = 10.0
        spans = [Span("pipe.train", 1.0, 2.0), Span("io.service.r", 1, 2)]

    class Win:
        pc0 = 11.0          # the window opened 1 s after the tracer's epoch

    planes = trace([], window=(5_000, 9_000))
    got = tracing.tracer_spans(Tracer, Win, planes)
    assert got == [("pipe.train", 5_000, 5_000 + 1e9)]
