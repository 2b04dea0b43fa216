"""Kernels, the superstep's fusions as one: least time one superstep
could take at the chip's HBM bandwidth (target weights once per valid
round, draft step weights gamma times, the KV of each busy lane's
shortest possible prompt), over its device time, in percent.  Rounds,
busy lanes and dispatches are counted over the traced window."""
from bench import shapes


def read(ctx):
    m = (ctx["trace"] or {}).get("modules", {}).get("jit__superstep")
    tr = ctx["traced"]
    if not m or not m["calls"] or not tr:
        return None
    d = {k: tr[1][k] - tr[0][k] for k in
         ("steps", "busy_lane_rounds", "dispatches")}
    if d["dispatches"] <= 0 or d["steps"] <= 0:
        return None
    doc = ctx["doc"]
    width = min(ctx["mix"]["prompt"]["widths"])
    total = (d["steps"] * shapes.round_bytes(doc, ctx["gamma"])
             + d["busy_lane_rounds"] * width
             * shapes.kv_bytes_per_token(doc))
    least_s = total / d["dispatches"] / ctx["peaks"]["hbm_bytes_s"]
    return 100.0 * least_s / (m["s"] / m["calls"])
