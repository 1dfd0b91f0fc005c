"""Bytes read from storage over the steps of the window (``IOStats``),
MB per step."""
LAYER = "IO"


def read(ctx: dict):
    steps = ctx.get("steps")
    io = ctx.get("io")
    if not steps or io is None:
        return None
    return io["bytes"] / steps / 1e6
