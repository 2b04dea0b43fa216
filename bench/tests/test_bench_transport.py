"""The readers of the decoupled cell's metrics on hand-made events and
traces: ship time per dispatch on the serving thread, the channel's drop
share from its cumulative counters, and the trainer's device share; and
None where there is nothing to read."""
import importlib

import pytest

from repro.obs import trace as obs_trace

T0 = 10.0                                   # window [10 s, 11 s]
SERVE, TRAIN = 1, 2                         # thread ids


def _reader(name):
    return importlib.import_module("bench.metrics." + name)


def _ns(ms):
    return T0 * 1e9 + ms * 1e6


def _ctx(t0=T0, t1=T0 + 1.0, trace=None):
    return {"traced": ({"t": t0}, {"t": t1}), "trace": trace}


def _span(name, a, b, tid=SERVE):
    return ("X", name, _ns(a), _ns(b) - _ns(a), tid, {})


def _count(name, at, value):
    return ("C", name, _ns(at), 0, SERVE, {"value": value})


def _events():
    return [
        _span("superstep.dispatch", -40, -38),      # before the window
        _span("superstep.dispatch", 100, 102),
        _span("superstep.dispatch", 500, 502),
        _span("signals.ship", -5, 4),               # clipped to [0, 4]
        _span("signals.ship", 300, 302),
        _span("signals.ship", 700, 703),
        _span("signals.ship", 400, 450, TRAIN),     # another thread
        _span("signals.ship", 999, 1010),           # clipped to [999, 1000]
        _count("signals.captured", -10, 40),        # before the window
        _count("signals.dropped", -10, 1),
        _count("signals.captured", 200, 41),
        _count("signals.dropped", 200, 1),
        _count("signals.captured", 600, 45),
        _count("signals.dropped", 600, 2),
        _count("signals.captured", 900, 51),
        _count("signals.dropped", 900, 4),
        _count("signals.captured", 1200, 60),       # after the window
        _count("signals.dropped", 1200, 9),
    ]


def test_signal_ship_ms_per_dispatch_on_serving_thread():
    # 4 + 2 + 3 + 1 ms over 2 dispatches
    assert _reader("signal_ship_ms").read(_ctx(), _events()) \
        == pytest.approx(5.0)


def test_signal_drop_share_from_counter_rises():
    # captured 41 -> 51, dropped 1 -> 4 within the window
    assert _reader("signal_drop_share").read(_ctx(), _events()) \
        == pytest.approx(30.0)


def test_signal_drop_share_zero_while_nothing_drops():
    evs = [e for e in _events() if e[1] != "signals.dropped"] + [
        _count("signals.dropped", t, 3) for t in (200, 600, 900)]
    assert _reader("signal_drop_share").read(_ctx(), evs) == 0.0


def test_trainer_busy_share_sums_step_and_eval():
    trace = {"window_s": 2.0, "modules": {
        "jit_step": {"s": 0.6, "calls": 15},
        "jit_eval_acc": {"s": 0.1, "calls": 1},
        "jit__superstep": {"s": 1.5, "calls": 6}}}
    assert _reader("trainer_busy_share").read(_ctx(trace=trace)) \
        == pytest.approx(35.0)
    idle = {"window_s": 2.0, "modules": {"jit__superstep": {"s": 1.5,
                                                           "calls": 6}}}
    assert _reader("trainer_busy_share").read(_ctx(trace=idle)) == 0.0


@pytest.mark.parametrize("name", ["signal_ship_ms", "signal_drop_share"])
def test_span_readers_none_without_events(name, monkeypatch):
    mod = _reader(name)
    assert mod.read(_ctx(T0 + 5.0, T0 + 6.0), _events()) is None
    assert mod.read(_ctx(), []) is None
    assert mod.read({"traced": None}, _events()) is None
    monkeypatch.delattr(obs_trace, "DEFAULT_TRACER")
    assert mod.read(_ctx()) is None


def test_trainer_busy_share_none_without_trace():
    assert _reader("trainer_busy_share").read(_ctx()) is None


def test_channel_counts_into_tracer():
    """The channel samples both cumulative counters at every window it
    is offered, and the drop-oldest bound moves ``signals.dropped``."""
    import numpy as np

    from repro.core.signals import SignalBatch
    from repro.core.transport import SignalChannel
    tr = obs_trace.Tracer()
    ch = SignalChannel(capacity=2, tracer=tr)
    for i in range(3):
        ch.add(SignalBatch(np.zeros((4, 2), np.float32),
                           np.full(4, i, np.int32)))
    got = [(e[1], e[5]["value"]) for e in tr.events() if e[0] == "C"]
    assert got == [("signals.captured", 1), ("signals.dropped", 0),
                   ("signals.captured", 2), ("signals.dropped", 0),
                   ("signals.captured", 3), ("signals.dropped", 1)]
