"""Host wall per step, ms, of the padded feature batch's host-to-device
upload: the ``pipe.train.upload`` span, in which a traced step waits for
the transfer before its dispatch (the ``train`` stage's ``upload_s``)."""
LAYER = "host-to-device"


def read(ctx: dict):
    st = (ctx.get("stages") or {}).get("train")
    if not st or not st["calls"] or "upload_s" not in st:
        return None
    return 1000.0 * st["upload_s"] / st["calls"]
