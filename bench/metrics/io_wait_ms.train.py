"""Host wall of the ``io_complete`` operator per batch, ms: waiting for the
storage reads and landing the rows."""
from readers import per_batch_ms

LAYER = "IO"


def read(ctx: dict):
    return per_batch_ms(ctx, "io_complete")
