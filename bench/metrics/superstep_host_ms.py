"""Engine host loop: the serving thread's own time per superstep, in
milliseconds.  From the first ``superstep.dispatch`` span that starts in
the traced window to the window's end, the serving thread's time outside
its waits on the device (``superstep.wait``, ``signals.fetch``,
``refill.first_wait``), outside sync training (``train.cycle``) and
outside idle ticks (``idle``), over the ``superstep.dispatch`` spans
that start there.  Starting at a dispatch, which no span of the thread
encloses, leaves no wait cut off at the window's start.

Read from the program's process-wide span tracer
(``repro.obs.trace.DEFAULT_TRACER``), which records while the profiler
runs; ``events`` stands in for it in tests."""
from bench.devtrace import union

WAITS = ("superstep.wait", "signals.fetch", "refill.first_wait",
         "train.cycle", "idle")


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
    dispatches = [(s, tid) for ph, name, s, d, tid, _ in evs
                  if name == "superstep.dispatch" and t0 <= s <= t1]
    if not dispatches:
        return None
    a = min(s for s, _ in dispatches)
    serving = {tid for _, tid in dispatches}
    waits = union([(max(s, a), min(s + d, t1))
                   for ph, name, s, d, tid, _ in evs
                   if name in WAITS and tid in serving
                   and s + d > a and s < t1])
    host = (t1 - a) - sum(e - s for s, e in waits)
    return host / len(dispatches) / 1e6
