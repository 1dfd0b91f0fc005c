"""XLA compilations inside the measured window (``jax.monitoring``)."""
LAYER = "device step"


def read(ctx: dict):
    return ctx.get("compiles")
