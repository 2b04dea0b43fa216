"""Training: mean host milliseconds of the ``train.feed`` spans that end
in the traced window: per optimizer step, the batch built on the host
and copied to the device before the step's dispatch, while the device
waits on the previous step's read.

Read from the program's process-wide span tracer
(``repro.obs.trace.DEFAULT_TRACER``), which records while the profiler
runs; ``events`` stands in for it in tests."""


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
    feeds = [d for ph, name, s, d, _, _ in evs
             if name == "train.feed" and t0 <= s + d <= t1]
    if not feeds:
        return None
    return sum(feeds) / len(feeds) / 1e6
