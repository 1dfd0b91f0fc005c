"""Rows found in the device or host tier over rows looked up in the
window (``CacheStats``), %."""
from readers import hit_rate

LAYER = "cache"


def read(ctx: dict):
    return hit_rate(ctx)
