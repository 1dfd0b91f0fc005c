"""Host wall of the ``train`` operator per batch, ms: the jitted step up to
its loss on the host, including the wait for the batch's feature upload."""
from readers import per_batch_ms

LAYER = "device step"


def read(ctx: dict):
    return per_batch_ms(ctx, "train")
