"""Share of the traced window in which no operation ran on the device, %."""
from readers import idle_share

LAYER = "device"


def read(ctx: dict):
    return idle_share(ctx)
