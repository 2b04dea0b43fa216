"""The trace reduction on a hand-made trace: busy union, idle share,
per-module time and calls, and naming of idle gaps by host events."""
import pytest

from bench import devtrace


def _trace():
    ms = 1e6
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit__superstep(17)", 0 * ms, 10 * ms),
            ("jit__superstep(17)", 12 * ms, 10 * ms),
            ("jit_step", 30 * ms, 5 * ms)]},
        {"name": "XLA Ops", "events": [
            ("fusion.1", 0 * ms, 6 * ms), ("fusion.2", 4 * ms, 6 * ms),
            ("fusion.1", 12 * ms, 10 * ms), ("convolution.3", 30 * ms,
                                             5 * ms)]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ("serve_stream", 0, 40 * ms),
            ("_unpack_superstep", 22 * ms, 7 * ms),
            ("sleep", 36 * ms, 4 * ms)]}]}
    return [host, dev], (0.0, 40 * ms)


def test_busy_is_union_of_op_intervals():
    planes, win = _trace()
    red = devtrace.reduce(planes, win)
    # [0,10] overlapping ops, [12,22], [30,35] -> 25 ms busy of 40
    assert red["busy_s"] == pytest.approx(0.025)
    assert red["window_s"] == pytest.approx(0.040)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.375)


def test_modules_by_name_with_calls():
    planes, win = _trace()
    mods = devtrace.reduce(planes, win)["modules"]
    assert mods["jit__superstep"] == {"s": pytest.approx(0.020), "calls": 2}
    assert mods["jit_step"] == {"s": pytest.approx(0.005), "calls": 1}


def test_window_clips_events():
    planes, _ = _trace()
    red = devtrace.reduce(planes, (5e6, 15e6))
    assert red["busy_s"] == pytest.approx(0.008)    # [5,10] + [12,15]
    assert red["modules"]["jit__superstep"]["calls"] == 2


def test_gaps_longest_first_named_by_innermost_host_event():
    planes, win = _trace()
    gaps = devtrace.reduce(planes, win)["gaps_top"]
    assert [g[0] for g in gaps] == ["_unpack_superstep", "sleep",
                                    "serve_stream"]
    assert [round(g[1], 6) for g in gaps] == [0.008, 0.005, 0.002]


def test_union_merges_and_sorts():
    assert devtrace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4),
                                                                (5, 6)]


def test_module_name_strips_numeric_suffix():
    assert devtrace.module_name("jit__superstep(123)") == "jit__superstep"
    assert devtrace.module_name("fusion.12") == "fusion"
