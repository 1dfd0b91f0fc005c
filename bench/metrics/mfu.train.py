"""The whole step's share of the chip's bf16 peak, %: seeds per second
times the operations one seed requires (``bench/flops.py``, from the
configuration's sizes) over the peak of ``device_kind``."""
LAYER = "device step"


def read(ctx: dict):
    rate, fps, peak = (ctx.get("seeds_per_s"), ctx.get("flops_per_seed"),
                       ctx.get("peak_flops"))
    if not rate or not fps or not peak:
        return None
    return 100.0 * rate * fps / peak
