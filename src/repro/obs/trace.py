"""Ring-buffered span/event tracer on the JAX profiler's clock.

The tracer records host-side *spans* (named intervals: superstep
dispatch, the host's waits on device results, refill, prefill chunk,
train cycle and its steps) and *instants* (token deliveries, deploy
pickup, admissions, park/probe/resume transitions) into a bounded deque
of tuples ``(ph, name, ts_ns, dur_ns, tid, args)``.  Recording is one
tuple append under a lock, safe on the serving hot loop and in the
background ``TrainingService`` thread; timestamps come from
``time.perf_counter_ns``, never the device.

While a JAX profiler session runs (``jax.profiler.trace`` /
``start_trace``), every span is also opened as a
``jax.profiler.TraceAnnotation`` of the same name, and every instant as
an empty one.  They then sit in the profile's host plane, on the
profiler's clock, over the device's ops; the ring keeps the same spans
with ``perf_counter`` stamps for readers in the process.

``DEFAULT_TRACER`` is the collaborator every component takes when it is
given none.  It records only while a profiler session runs: without
one, ``enabled`` is False and ``span()`` returns a shared no-op context
manager, so a hook costs one profiler-state check and allocates
nothing.  A ``Tracer(always=True)`` (what ``ObsConfig(trace=True)``
builds) records always.  A span is kept if the tracer records when it
starts or when it ends, with its true start and end: a span under way
when a session stops is kept whole.  ``span()`` times nothing when the
tracer does not record; ``long_span`` always times its span, so a train
cycle that began before a session is kept whole too.

``export()`` converts the ring into Chrome trace-event JSON (the format
read by ``chrome://tracing`` and https://ui.perfetto.dev): spans become
``"ph": "X"`` complete events with microsecond ``ts`` / ``dur``,
instants ``"ph": "i"``, and thread names ``"ph": "M"`` metadata so the
serving loop and the training thread render as separate tracks.  Spans
recorded on one thread nest by construction.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

# True only between a profiler session's start and stop
_profiling = TraceAnnotation.is_enabled


class _NullSpan:
    """Shared no-op context manager for a tracer that is not recording."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """Context manager recording one complete ("X") event on exit, and
    one profiler annotation while a session runs.  The event's interval
    includes opening and closing the annotation, so consecutive spans
    of one thread leave no gap for the tracer's own work."""
    __slots__ = ("_tr", "name", "args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, args: dict):
        self._tr = tr
        self.name = name
        self.args = args

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        self._ann = None
        if _profiling():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        return self

    def set(self, **args):
        """Add arguments known only once the span's work is done."""
        self.args.update(args)

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        t1 = time.perf_counter_ns()
        if self._ann is not None or self._tr.enabled:
            self._tr._append("X", self.name, self._t0, t1 - self._t0,
                             self.args)
        return False


class Tracer:
    """Bounded, thread-safe span/instant recorder.

    The deque drops the oldest events beyond ``capacity`` so an endless
    serving run keeps the trailing window.  ``always=False`` records
    only while a JAX profiler session runs (``DEFAULT_TRACER``).
    """

    def __init__(self, capacity: int = 65536, always: bool = True):
        self.capacity = int(capacity)
        self.always = always
        self._buf: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._t0 = time.perf_counter_ns()
        self._tid_names: dict = {}

    @property
    def enabled(self) -> bool:
        """Whether hooks record now; guard argument building with it."""
        return self.always or _profiling()

    # -- recording -----------------------------------------------------
    def span(self, name: str, **args):
        """Open a named span; use as ``with tracer.span("unpack"): ...``."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def long_span(self, name: str, **args) -> _Span:
        """A span that may begin before the tracer records (a train
        cycle under way when a profiler session starts): always timed,
        and kept if the tracer records when it starts or ends."""
        return _Span(self, name, args)

    def _append(self, ph, name, ts, dur, args):
        tid = threading.get_ident()
        if tid not in self._tid_names:
            self._tid_names[tid] = threading.current_thread().name
        with self._lock:
            self._buf.append((ph, name, ts, dur, tid, args))

    def instant(self, name: str, **args):
        """Record a zero-duration event (delivery, deploy pickup, ...)."""
        if not self.enabled:
            return
        if _profiling():
            with TraceAnnotation(name):
                pass
        self._append("i", name, time.perf_counter_ns(), 0, args)

    def counter(self, name: str, **values):
        """Record a counter sample (renders as a track in Perfetto)."""
        if self.enabled:
            self._append("C", name, time.perf_counter_ns(), 0, values)

    # -- export --------------------------------------------------------
    def events(self):
        """Snapshot of the raw event tuples ``(ph, name, ts_ns, dur_ns,
        tid, args)``, oldest first, on the ``perf_counter_ns`` clock."""
        with self._lock:
            return list(self._buf)

    def export(self, path: Optional[str] = None) -> dict:
        """Render the ring as a Chrome trace-event JSON document.

        Returns the document (``{"traceEvents": [...]}``); when
        ``path`` is given it is also written there.
        """
        pid = os.getpid()
        out = []
        for tid, tname in sorted(self._tid_names.items()):
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": tname}})
        for ph, name, ts_ns, dur_ns, tid, args in self.events():
            ev = {"ph": ph, "name": name, "pid": pid, "tid": tid,
                  "ts": (ts_ns - self._t0) / 1e3, "cat": "tide"}
            if ph == "X":
                ev["dur"] = dur_ns / 1e3
            elif ph == "i":
                ev["s"] = "t"  # thread-scoped instant
            if args:
                ev["args"] = dict(args)
            out.append(ev)
        doc = {"traceEvents": out, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


# the process-wide tracer of every component given none: it records
# while a profiler session runs, and readers in the process find the
# session's spans here after the system is closed
DEFAULT_TRACER = Tracer(always=False)
