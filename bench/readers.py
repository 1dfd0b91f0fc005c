"""Helpers the per-layer metric readers (``bench/metrics/``) share.

Each reader takes the run's context and returns a number, or ``None``
where it finds nothing to read.
"""
from __future__ import annotations


def per_batch_ms(ctx: dict, stage: str):
    st = (ctx.get("stages") or {}).get(stage)
    if not st or not st["calls"]:
        return None
    return 1000.0 * st["wall_s"] / st["calls"]


def hit_rate(ctx: dict):
    c = ctx.get("cache")
    if c is None:
        return None
    total = (c["device_hits"] + c["host_hits"] + c["storage_misses"]
             + c["remote_hits"])
    if not total:
        return None
    return 100.0 * (c["device_hits"] + c["host_hits"]) / total


def idle_share(ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s") or tr.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def span_ms(ctx: dict, name: str):
    walls = (ctx.get("spans") or {}).get(name)
    if not walls:
        return None
    return 1000.0 * sum(walls) / len(walls)
