"""Share of the uploaded feature rows that are padding, %: 100 x (1 -
real rows / rows uploaded), from the ``batch_build`` stage's counters."""
LAYER = "host-to-device"


def read(ctx: dict):
    st = (ctx.get("stages") or {}).get("batch_build")
    if not st or not st.get("feature_rows"):
        return None
    return 100.0 * (1.0 - st["real_rows"] / st["feature_rows"])
