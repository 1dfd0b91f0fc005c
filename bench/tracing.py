"""Device trace of the measured window and its reduction to metrics.

``Window`` wraps the window in ``jax.profiler`` tracing with a host
annotation ``bench.window`` that marks its ends on the trace's clock.
``reduce`` turns the trace into: the seconds in which an operation ran on
each device (the union of the device's op intervals inside the window,
averaged over the chips), the window's length, the device programs that
took most time, and the idle time labelled by the program's own spans
(``repro.obs`` tracer) that covered it.
"""
from __future__ import annotations

import glob
import os
import re
import time

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("pipe.", "serve.")
DEVICE_PLANE = re.compile(r"/device:(TPU|GPU):\d+")


class Window:
    """Profile the block under a ``bench.window`` annotation; records the
    host's ``perf_counter`` at its start, to place host spans."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.pc0 = None

    def __enter__(self):
        import jax

        jax.profiler.start_trace(self.log_dir)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()
        self.pc0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self._ann.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def planes(self) -> list:
        """The trace as plain data: ``[{"name", "lines": [{"name",
        "events": [(name, start_ns, duration_ns)]}]}]``, device planes
        whole and of the host only the window's annotation."""
        import jax

        paths = sorted(glob.glob(os.path.join(
            self.log_dir, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            return []
        pd = jax.profiler.ProfileData.from_file(paths[-1])
        out = []
        for pl in pd.planes:
            dev = bool(DEVICE_PLANE.fullmatch(pl.name))
            lines = []
            for ln in pl.lines:
                ev = [(e.name, e.start_ns, e.duration_ns) for e in ln.events
                      if dev or e.name == WINDOW]
                if ev:
                    lines.append({"name": ln.name, "events": ev})
            out.append({"name": pl.name, "lines": lines})
        return out


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _window(planes: list):
    for pl in planes:
        for ln in pl["lines"]:
            for name, t0, dur in ln["events"]:
                if name == WINDOW:
                    return t0, t0 + dur
    return None


def device_planes(planes: list) -> list:
    """Planes of the accelerator chips (``/device:TPU:<n>``)."""
    return [pl for pl in planes if DEVICE_PLANE.fullmatch(pl["name"])]


def _line(pl: dict, name: str):
    for ln in pl["lines"]:
        if ln["name"] == name:
            return ln
    return None


def _clip(events: list, w0: float, w1: float) -> list:
    out = []
    for name, t0, dur in events:
        a, b = max(t0, w0), min(t0 + dur, w1)
        if b > a:
            out.append((name, a, b))
    return out


def _label(spans: list, t: float) -> str:
    """What the host was doing at ``t``: the names of the pipeline or
    server spans that covered it."""
    names = sorted({s[0] for s in spans if s[1] <= t <= s[2]})
    return "+".join(names) if names else "no span"


def reduce(planes: list, spans: list = (), top: int = 10):
    """Device busy and idle time of the window.

    ``spans``: ``(name, start_ns, end_ns)`` on the trace's clock.  Returns
    ``None`` when the trace has no window or no device, else ``busy_s``,
    ``window_s``, ``device_ops`` and ``idle_gaps`` (each a list of
    ``[name, seconds]``, longest first, at most ``top`` entries)."""
    win = _window(planes)
    devs = device_planes(planes)
    if win is None or not devs:
        return None
    w0, w1 = win
    busy, by_prog, idle = [], {}, {}
    for pl in devs:
        ops = _line(pl, OPS_LINE) or _line(pl, MODULES_LINE)
        if ops is None:
            continue
        clipped = _clip(ops["events"], w0, w1)
        merged = _union([[a, b] for _, a, b in clipped])
        busy.append(sum(b - a for a, b in merged))
        progs = _line(pl, MODULES_LINE) or ops
        for name, a, b in _clip(progs["events"], w0, w1):
            key = re.sub(r"\(\d+\)$", "", name)
            by_prog[key] = by_prog.get(key, 0.0) + (b - a) / 1e9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = _label(spans, (a + b) / 2)
                idle[lab] = idle.get(lab, 0.0) + (b - a) / 1e9
    if not busy:
        return None

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (w1 - w0) / 1e9,
            "device_ops": ranked(by_prog),
            "idle_gaps": ranked({k: v / len(busy) for k, v in idle.items()})}


def tracer_spans(tracer, window: Window, planes: list) -> list:
    """The ``repro.obs`` tracer's spans placed on the trace's clock."""
    win = _window(planes)
    if tracer is None or win is None or window.pc0 is None:
        return []
    off = win[0] - (window.pc0 - tracer.epoch) * 1e9
    return [(s.name, off + s.t0 * 1e9, off + s.t1 * 1e9)
            for s in tracer.spans if s.name.startswith(SPAN_PREFIXES)]
