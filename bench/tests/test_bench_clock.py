"""The program's tracer spans and the profiler's trace share one clock:
annotations opened inside tracer spans at both ends of a real
``jax.profiler`` window land where ``tracing.tracer_spans`` puts those
spans, within 1 ms."""
from __future__ import annotations

import glob
import os
import time

import jax

import tracing
from repro.obs.trace import Tracer


def _marked(tracer, name):
    with tracer.span(f"pipe.{name}"):
        with jax.profiler.TraceAnnotation(name):
            time.sleep(0.005)


def test_tracer_spans_on_profiler_clock(tmp_path):
    tracer = Tracer()
    win = tracing.Window(str(tmp_path))
    with win:
        _marked(tracer, "clock.start")
        t_end = time.perf_counter() + 2.0
        while time.perf_counter() < t_end:
            jax.numpy.ones(64).sum().block_until_ready()
        _marked(tracer, "clock.end")
    mapped = {n: (a, b) for n, a, b in
              tracing.tracer_spans(tracer, win, win.planes())}
    # the raw trace: Window.planes() keeps only the window's annotation
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    marks = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
             for pl in jax.profiler.ProfileData.from_file(path).planes
             for ln in pl.lines for e in ln.events
             if e.name in ("clock.start", "clock.end")}
    assert set(marks) == {"clock.start", "clock.end"}
    for name, (a, b) in marks.items():
        sa, sb = mapped[f"pipe.{name}"]
        assert abs(sa - a) < 1e6 and abs(sb - b) < 1e6, (name, sa - a, sb - b)
