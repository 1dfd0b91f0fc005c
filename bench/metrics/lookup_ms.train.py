"""Host wall of the ``io_submit`` and ``cache_lookup`` operators per batch, ms:
the tier plan, the storage submission and the host and device tier
gathers."""
from readers import per_batch_ms

LAYER = "cache"


def read(ctx: dict):
    a, b = per_batch_ms(ctx, "io_submit"), per_batch_ms(ctx, "cache_lookup")
    return None if a is None or b is None else a + b
