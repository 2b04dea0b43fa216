"""Scheduler: 90th percentile over the window's requests of slot
admission (``Request.admit_t``) minus scheduled send time."""
import numpy as np


def read(ctx):
    waits = [r["admit"] - r["send"] for r in ctx["requests"]
             if r["admit"] is not None]
    return float(np.percentile(waits, 90)) if waits else None
