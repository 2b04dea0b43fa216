"""Whole decode step against the chip's peak: target model FLOPs of the
tokens the supersteps committed, over the superstep's device time, over
the bf16 peak, in percent (counted over the traced window)."""
from bench import shapes


def read(ctx):
    m = (ctx["trace"] or {}).get("modules", {}).get("jit__superstep")
    tr = ctx["traced"]
    if not m or not m["calls"] or not tr:
        return None
    d = {k: tr[1][k] - tr[0][k] for k in
         ("tokens_out", "refills", "dispatches")}
    if d["dispatches"] <= 0:
        return None
    # a refill's first token comes from its prefill, not a superstep
    committed = d["tokens_out"] - d["refills"]
    flops = committed / d["dispatches"] * shapes.token_flops(ctx["doc"])
    return 100.0 * flops / (m["s"] / m["calls"]) \
        / ctx["peaks"]["bf16_flops"]
