"""Model step: 90th percentile over the window's requests of first token
(``Request.first_token_t``) minus slot admission (``Request.admit_t``):
the wait in chunked prefill, after the queue, until the first token
reaches the host."""
import numpy as np


def read(ctx):
    waits = [r["first"] - r["admit"] for r in ctx["requests"]
             if r["admit"] is not None and r["first"] is not None]
    return float(np.percentile(waits, 90)) if waits else None
