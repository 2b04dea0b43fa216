"""Engine host loop: 90th percentile, over the requests served in the
traced window, of the milliseconds between consecutive token deliveries
to one request (``serve.deliver`` instants, each naming the requests
that received tokens: one per superstep unpack and one per commit of
first tokens, so the gap after the first token counts).  Gaps between
two deliveries in the window, pooled over the requests.

Read from the program's process-wide span tracer
(``repro.obs.trace.DEFAULT_TRACER``), which records while the profiler
runs; ``events`` stands in for it in tests."""
import numpy as np


def _events():
    try:
        from repro.obs.trace import DEFAULT_TRACER
    except ImportError:       # a program without the tracer
        return []
    return DEFAULT_TRACER.events()


def read(ctx, events=None):
    tr = ctx["traced"]
    if not tr:
        return None
    evs = _events() if events is None else events
    t0, t1 = tr[0]["t"] * 1e9, tr[1]["t"] * 1e9
    last, gaps = {}, []
    for ph, name, s, _, _, args in sorted(evs, key=lambda e: e[2]):
        if name != "serve.deliver" or not t0 <= s <= t1:
            continue
        for rid in args["rids"]:
            if rid in last:
                gaps.append(s - last[rid])
            last[rid] = s
    if not gaps:
        return None
    return float(np.percentile(gaps, 90)) / 1e6
