"""Overlap report for the serving loop's virtual-time accounting.

The serving loop accumulates a ``{resource: busy_virtual_seconds}`` dict
as it schedules work on its ``VirtualClock``; :func:`overlap_report`
turns that plus the makespan into overlap efficiency and compute-bubble
fraction for ``ServingStats.summary()``.

With S = sum of per-resource virtual busy time, M = makespan, and L = the
busiest single resource's total virtual time::

    overlap_efficiency = clamp((S - M) / (S - L), 0, 1)

i.e. 0 when nothing overlaps (serial: M = S) and 1 at the physical
limit (M = L: the schedule is as short as the busiest resource
allows).  ``bubble_frac = 1 - device_busy / M`` is the fraction of the
makespan the compute resource sat idle.
"""
from __future__ import annotations

__all__ = ["overlap_report"]

_EPS = 1e-9


def overlap_report(busy, makespan, device_keys=("device",)):
    """Overlap metrics from a ``{resource: busy_virtual_s}`` dict.

    ``busy`` must be keyed by *logical* resource (host/io/device/...),
    even when the executor serialized everything onto one physical
    resource — that way serial mode reports efficiency 0 rather than a
    degenerate division.
    """
    busy = {k: float(v) for k, v in busy.items() if v > 0}
    makespan = float(makespan)
    s = sum(busy.values())
    busiest = max(busy.values(), default=0.0)
    denom = s - busiest
    if denom <= _EPS or makespan <= _EPS:
        eff = 0.0
    else:
        eff = (s - makespan) / denom
        eff = 0.0 if eff < 0.0 else (1.0 if eff > 1.0 else eff)
    device_busy = sum(busy.get(k, 0.0) for k in device_keys)
    bubble = 1.0 - device_busy / makespan if makespan > _EPS else 0.0
    bubble = 0.0 if bubble < 0.0 else (1.0 if bubble > 1.0 else bubble)
    return {
        "overlap_efficiency": eff,
        "bubble_frac": bubble,
        "makespan_s": makespan,
        "busy_s": dict(sorted(busy.items())),
        "sum_busy_s": s,
    }
