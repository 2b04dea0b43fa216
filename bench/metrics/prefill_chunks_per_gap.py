"""Model step: mean number of prefill chunks dispatched in one
inter-superstep gap, over the ``prefill.advance`` spans that end in the
traced window (their ``chunks`` argument).  A program whose spans carry
no ``chunks`` argument gives nothing to read.

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
    chunks = [args["chunks"] for ph, name, s, d, _, args in evs
              if name == "prefill.advance" and t0 <= s + d <= t1
              and args and "chunks" in args]
    if not chunks:
        return None
    return sum(chunks) / len(chunks)
