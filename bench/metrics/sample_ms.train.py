"""Host wall of the pipeline's ``sample`` operator per batch, ms."""
from readers import per_batch_ms

LAYER = "sample"


def read(ctx: dict):
    return per_batch_ms(ctx, "sample")
