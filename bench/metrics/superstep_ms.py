"""Model step: device milliseconds per call of the fused decode
superstep program (``jit__superstep``) in the traced window."""


def read(ctx):
    m = (ctx["trace"] or {}).get("modules", {}).get("jit__superstep")
    if not m or not m["calls"]:
        return None
    return 1000.0 * m["s"] / m["calls"]
