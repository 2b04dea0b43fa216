"""Model step, prefill: device milliseconds of the refill and chunked
prefill programs per 1000 prompt row-tokens they were given (rows x
width, pad rows included) in the traced window."""

PREFIXES = ("jit__chunk_", "jit__refill", "jit__prefill", "jit__prologue")


def read(ctx):
    trace, tr = ctx["trace"], ctx["traced"]
    if not trace or not tr:
        return None
    s = sum(m["s"] for name, m in trace["modules"].items()
            if name.startswith(PREFIXES))
    toks = tr[1]["prefill_row_tokens"] - tr[0]["prefill_row_tokens"]
    if s <= 0 or toks <= 0:
        return None
    return 1e6 * s / toks
