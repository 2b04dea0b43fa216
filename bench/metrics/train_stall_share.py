"""Training: share of the traced window in which the serving thread (the
one that records ``superstep.dispatch`` spans) was inside a
``train.cycle`` span, clipped to the window, in percent: the stall of
admission and decode that synchronous draft training causes.

Read from the program's process-wide span tracer
(``repro.obs.trace.DEFAULT_TRACER``), which records while the profiler
runs; ``events`` stands in for it in tests."""
from bench.devtrace import union


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
    serving = {tid for ph, name, s, d, tid, _ in evs
               if name == "superstep.dispatch" and t0 <= s <= t1}
    if not serving:
        return None
    cycles = [(max(s, t0), min(s + d, t1))
              for ph, name, s, d, tid, _ in evs
              if name == "train.cycle" and tid in serving
              and s + d > t0 and s < t1]
    return 100.0 * sum(e - s for s, e in union(cycles)) / (t1 - t0)
