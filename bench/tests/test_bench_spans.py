"""The four readers of the program's spans on a hand-made event list and
window: values against hand counts, clipping at the window's edges,
other threads left out, and None where there is nothing to read."""
import importlib
import time

import jax
import pytest

from repro.obs import trace as obs_trace

NAMES = ("train_stall_share", "train_feed_ms", "superstep_host_ms",
         "delivery_gap_p90_ms")
T0 = 10.0                                   # window [10 s, 11 s]
SERVE, OTHER = 1, 2                         # thread ids


def _reader(name):
    return importlib.import_module("bench.metrics." + name)


def _ns(ms):
    """Host ns of ``ms`` milliseconds after the window's start."""
    return T0 * 1e9 + ms * 1e6


def _ctx(t0=T0, t1=T0 + 1.0):
    return {"traced": ({"t": t0}, {"t": t1})}


def _span(name, a, b, tid=SERVE):
    return ("X", name, _ns(a), _ns(b) - _ns(a), tid, {})


def _deliver(at, rids):
    return ("i", "serve.deliver", _ns(at), 0, SERVE, {"rids": rids})


def _events():
    return [
        _span("superstep.dispatch", -50, -48),      # before the window
        _span("superstep.dispatch", 100, 103),
        _span("superstep.dispatch", 400, 403),
        _span("superstep.dispatch", 800, 803),
        _span("superstep.wait", -5, 20),
        _span("superstep.wait", 110, 140),
        _span("signals.fetch", 140, 145),
        _span("superstep.wait", 410, 430),
        _span("refill.first_wait", 430, 432),
        _span("superstep.wait", 810, 830),
        _span("superstep.wait", 300, 350, OTHER),   # another thread
        _span("idle", 870, 880),
        _span("train.cycle", -300, 50),             # clipped to [0, 50]
        _span("train.cycle", 500, 700),
        _span("train.read", 510, 520),              # inside a cycle
        _span("train.cycle", 950, 1200),            # clipped to [950, 1000]
        _span("train.cycle", 200, 300, OTHER),      # another thread
        _span("train.feed", -10, -4),               # ends before
        _span("train.feed", 505, 507),
        _span("train.feed", 520, 524),
        _span("train.feed", 998, 1003),             # ends after
        _deliver(-20, [1, 2]),                      # before the window
        _deliver(100, [1, 2]),
        _deliver(250, [1]),
        _deliver(400, [1, 2, 3]),
        _deliver(1100, [1]),                        # after the window
    ]


def test_train_stall_share_clips_cycles_of_the_serving_thread():
    # [0, 50] + [500, 700] + [950, 1000] of 1000 ms
    assert _reader("train_stall_share").read(_ctx(), _events()) \
        == pytest.approx(30.0)


def test_train_stall_share_zero_without_cycles():
    evs = [e for e in _events() if e[1] != "train.cycle"]
    assert _reader("train_stall_share").read(_ctx(), evs) == 0.0


def test_train_feed_ms_means_spans_ending_in_window():
    assert _reader("train_feed_ms").read(_ctx(), _events()) \
        == pytest.approx(3.0)


def test_superstep_host_ms_counts_from_first_dispatch():
    # [100, 1000] = 900 ms less the serving thread's waits: [110, 145],
    # [410, 432], cycle [500, 700], [810, 830], idle [870, 880], cycle
    # [950, 1000] = 337 ms; over 3 dispatches
    assert _reader("superstep_host_ms").read(_ctx(), _events()) \
        == pytest.approx(563.0 / 3)


def test_delivery_gap_p90_ms_pools_gaps_in_window():
    # request 1: 150, 150; request 2: 300; p90 of [150, 150, 300]
    assert _reader("delivery_gap_p90_ms").read(_ctx(), _events()) \
        == pytest.approx(270.0)


@pytest.mark.parametrize("name", NAMES)
def test_reader_none_on_empty_window(name):
    mod = _reader(name)
    assert mod.read(_ctx(T0 + 5.0, T0 + 6.0), _events()) is None
    assert mod.read(_ctx(), []) is None
    assert mod.read({"traced": None}, _events()) is None


@pytest.mark.parametrize("name", NAMES)
def test_reader_none_on_program_without_tracer(name, monkeypatch):
    """A program that has no process-wide tracer gives nothing to read,
    and the reader does not raise."""
    monkeypatch.delattr(obs_trace, "DEFAULT_TRACER")
    assert _reader(name).read(_ctx()) is None


def test_readers_read_default_tracer_under_profiler(tmp_path):
    """With no ``events`` given, the readers read what the program's
    default tracer recorded while a profiler session ran."""
    tr = obs_trace.DEFAULT_TRACER
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.perf_counter()
        with tr.span("superstep.dispatch"):
            pass
        with tr.long_span("train.cycle"):
            with tr.span("train.feed"):
                time.sleep(0.01)
        tr.instant("serve.deliver", rids=[7])
        time.sleep(0.01)
        tr.instant("serve.deliver", rids=[7])
        t1 = time.perf_counter()
    ctx = _ctx(t0, t1)
    assert 0.0 < _reader("train_stall_share").read(ctx) < 100.0
    assert _reader("train_feed_ms").read(ctx) >= 10.0
    assert _reader("superstep_host_ms").read(ctx) > 0.0
    assert _reader("delivery_gap_p90_ms").read(ctx) >= 10.0
