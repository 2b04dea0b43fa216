"""The two prefill-wait readers on hand-made request rows and span
events: values against hand counts, clipping to the traced window, and
None where there is nothing to read."""
import importlib
import time

import jax
import pytest

from repro.obs import trace as obs_trace

T0 = 10.0                                   # traced window [10 s, 11 s]


def _reader(name):
    return importlib.import_module("bench.metrics." + name)


def _ns(ms):
    """Host ns of ``ms`` milliseconds after the window's start."""
    return T0 * 1e9 + ms * 1e6


def _ctx(t0=T0, t1=T0 + 1.0, requests=()):
    return {"traced": ({"t": t0}, {"t": t1}), "requests": list(requests)}


def _advance(a, b, **args):
    return ("X", "prefill.advance", _ns(a), _ns(b) - _ns(a), 1, args)


def _events():
    return [
        _advance(-30, -20, chunks=9, row_tokens=2304),   # ends before
        _advance(-5, 5, chunks=2, row_tokens=512),       # ends inside
        _advance(100, 110, chunks=4, row_tokens=1024),
        _advance(300, 302),                              # no arguments
        ("X", "prefill.chunk", _ns(400), _ns(401) - _ns(400), 1,
         {"rows": 1, "width": 256}),
        _advance(500, 520, chunks=6, row_tokens=1536),
        _advance(995, 1010, chunks=11, row_tokens=2816),  # ends after
    ]


def _row(send, admit, first):
    return {"send": send, "admit": admit, "first": first, "finish": None,
            "n": 1, "budget": 1, "width": 512}


def test_prefill_chunks_per_gap_means_spans_ending_in_window():
    assert _reader("prefill_chunks_per_gap").read(_ctx(), _events()) \
        == pytest.approx((2 + 4 + 6) / 3)


def test_prefill_chunks_per_gap_none_without_chunks_argument():
    """The spans of a program that does not count its chunks (no
    ``chunks`` argument) give nothing to read."""
    evs = [_advance(100, 110), _advance(200, 205)]
    assert _reader("prefill_chunks_per_gap").read(_ctx(), evs) is None


def test_prefill_chunks_per_gap_none_on_empty_window():
    mod = _reader("prefill_chunks_per_gap")
    assert mod.read(_ctx(T0 + 5.0, T0 + 6.0), _events()) is None
    assert mod.read(_ctx(), []) is None
    assert mod.read({"traced": None}, _events()) is None


def test_prefill_chunks_per_gap_none_without_tracer(monkeypatch):
    monkeypatch.delattr(obs_trace, "DEFAULT_TRACER")
    assert _reader("prefill_chunks_per_gap").read(_ctx()) is None


def test_prefill_chunks_per_gap_reads_default_tracer_under_profiler(
        tmp_path):
    tr = obs_trace.DEFAULT_TRACER
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.perf_counter()
        for n in (3, 5):
            with tr.span("prefill.advance") as sp:
                sp.set(chunks=n, row_tokens=256 * n)
        t1 = time.perf_counter()
    assert _reader("prefill_chunks_per_gap").read(_ctx(t0, t1)) \
        == pytest.approx(4.0)


def test_prefill_wait_p90_s_first_token_minus_admission():
    # waits 0.1 .. 1.0 s; one request still queued, one admitted but
    # not yet given its first token: both left out
    rows = [_row(0.0, 1.0, 1.0 + 0.1 * i) for i in range(1, 11)]
    rows += [_row(0.0, None, None), _row(0.0, 2.0, None)]
    got = _reader("prefill_wait_p90_s").read(_ctx(requests=rows))
    assert got == pytest.approx(0.91)


def test_prefill_wait_p90_s_none_without_first_tokens():
    mod = _reader("prefill_wait_p90_s")
    assert mod.read(_ctx()) is None
    assert mod.read(_ctx(requests=[_row(0.0, None, None),
                                   _row(0.0, 1.0, None)])) is None
