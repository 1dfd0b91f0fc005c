"""Bytes moved host to device per step, MB: the padded feature batch and
the index, mask and label tensors that ``batch_build`` uploads (the
stage's ``h2d_bytes`` counter over its calls)."""
LAYER = "host-to-device"


def read(ctx: dict):
    st = (ctx.get("stages") or {}).get("batch_build")
    if not st or not st["calls"] or "h2d_bytes" not in st:
        return None
    return st["h2d_bytes"] / st["calls"] / 1e6
