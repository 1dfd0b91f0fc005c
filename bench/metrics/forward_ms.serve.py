"""Host wall of the server's ``serve.forward`` span per micro-batch, ms:
the uploads and jitted forward steps of its requests, logits on the host."""
from readers import span_ms

LAYER = "device step"


def read(ctx: dict):
    return span_ms(ctx, "serve.forward")
