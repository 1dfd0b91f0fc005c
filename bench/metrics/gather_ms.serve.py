"""Host wall of the server's ``serve.gather`` span per micro-batch, ms:
one deduplicated gather through the cache and the IO stack."""
from readers import span_ms

LAYER = "cache"


def read(ctx: dict):
    return span_ms(ctx, "serve.gather")
