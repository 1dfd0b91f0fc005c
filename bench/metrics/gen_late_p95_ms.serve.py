"""95th percentile of how late the generator handed each request to the
server loop, ms after its due time."""
from harness import percentile

LAYER = "load generator"


def read(ctx: dict):
    late = ctx.get("gen_late_s")
    if not late:
        return None
    return 1000.0 * percentile(late, 95)
