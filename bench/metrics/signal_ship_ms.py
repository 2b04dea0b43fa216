"""Transport: the serving thread's milliseconds inside ``signals.ship``
spans (captured windows stacked and handed to the signal channel, their
copies toward the trainer's chip enqueued) per ``superstep.dispatch``
span that starts in the traced window.  Ship spans are clipped to the
window; the serving thread is the one that records the dispatches.

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
    dispatches = [tid for ph, name, s, d, tid, _ in evs
                  if name == "superstep.dispatch" and t0 <= s <= t1]
    if not dispatches:
        return None
    serving = set(dispatches)
    ships = union([(max(s, t0), min(s + d, t1))
                   for ph, name, s, d, tid, _ in evs
                   if name == "signals.ship" and tid in serving
                   and s + d > t0 and s < t1])
    return sum(e - s for s, e in ships) / len(dispatches) / 1e6
