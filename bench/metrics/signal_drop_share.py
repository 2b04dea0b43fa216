"""Transport: share of the signal windows offered to the channel in the
traced window that its drop-oldest bound dropped, in percent.  Read
from the channel's cumulative counters ``signals.captured`` and
``signals.dropped``, which the program samples at every window it
offers: each counter's rise from its first to its last sample in the
traced window.  Zero while the trainer keeps up with serving.

Read from the program's process-wide span tracer
(``repro.obs.trace.DEFAULT_TRACER``), which records while the profiler
runs; ``events`` stands in for it in tests."""


def _events():
    try:
        from repro.obs.trace import DEFAULT_TRACER
    except ImportError:       # a program without the tracer
        return []
    return DEFAULT_TRACER.events()


def _rise(evs, name, t0, t1):
    vals = [args["value"] for ph, n, s, _, _, args in sorted(
        (e for e in evs if e[0] == "C" and e[1] == name),
        key=lambda e: e[2]) if t0 <= s <= t1]
    return vals[-1] - vals[0] if len(vals) > 1 else None


def read(ctx, events=None):
    tr = ctx["traced"]
    if not tr:
        return None
    evs = _events() if events is None else events
    t0, t1 = tr[0]["t"] * 1e9, tr[1]["t"] * 1e9
    captured = _rise(evs, "signals.captured", t0, t1)
    dropped = _rise(evs, "signals.dropped", t0, t1)
    if not captured or dropped is None:
        return None
    return 100.0 * dropped / captured
