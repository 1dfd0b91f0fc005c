"""Host wall per batch, ms, in which the device stream was free but its
``batch_build`` operator still waited for the batch's cache lookup and
storage reads: the sum of the ``pipe.wait.batch_build`` spans, which the
pipeline executor also keeps as the stage's ``wait_s``."""
LAYER = "pipeline"


def read(ctx: dict):
    st = (ctx.get("stages") or {}).get("batch_build")
    if not st or not st["calls"] or "wait_s" not in st:
        return None
    return 1000.0 * st["wait_s"] / st["calls"]
