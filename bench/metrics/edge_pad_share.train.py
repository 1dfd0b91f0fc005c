"""Share of the edge rows the step's aggregation runs over that are
padding, %: 100 x (1 - real edges / edge slots), from the ``batch_build``
stage's counters (the blocks' static slots, plus GAT's self edge per row
and layer)."""
LAYER = "aggregation"


def read(ctx: dict):
    st = (ctx.get("stages") or {}).get("batch_build")
    if not st or not st.get("edge_slots"):
        return None
    return 100.0 * (1.0 - st["real_edges"] / st["edge_slots"])
