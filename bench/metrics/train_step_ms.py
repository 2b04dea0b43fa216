"""Training: device milliseconds per call of the draft trainer's
optimizer step program (``jit_step``) in the traced window."""


def read(ctx):
    m = (ctx["trace"] or {}).get("modules", {}).get("jit_step")
    if not m or not m["calls"]:
        return None
    return 1000.0 * m["s"] / m["calls"]
