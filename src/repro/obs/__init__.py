"""Observability: tracing, metrics, and the serving overlap report.

The subsystem is zero-overhead when off: every instrumented call site
reads one module global (``trace.TRACER``) and checks ``enabled`` before
doing any work, and the default state is ``TRACER is None``.  Installing
a tracer (``trace.install`` / ``HELIOS_TRACE``) lights up nested spans
stamped with wall time across the IO stack, the cache, the pipeline, the
remote/fleet layers, and the serving path (whose spans also carry its
virtual-clock interval); the Chrome-trace exporter (``export``) writes
them for Perfetto, and ``analyze.overlap_report`` gives the serving
loop's overlap efficiency and bubble fraction.
"""
from repro.obs import analyze, export, metrics, trace
from repro.obs.analyze import overlap_report
from repro.obs.export import to_chrome_trace, validate_trace, write_trace
from repro.obs.metrics import REGISTRY, Registry
from repro.obs.trace import Span, Tracer, get_tracer, install, uninstall

__all__ = [
    "analyze", "export", "metrics", "trace",
    "overlap_report",
    "to_chrome_trace", "validate_trace", "write_trace",
    "REGISTRY", "Registry",
    "Span", "Tracer", "get_tracer", "install", "uninstall",
]
