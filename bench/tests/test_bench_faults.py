"""The whole run, past the look for a chip, on a tiny configuration on
the CPU: a sound run is correct; the control, put in the program's
place, is not; nor is a run with a fault planted in the timed path: a
token altered where the superstep produces it, a draft train step that
returns its draft unchanged, or one that trains on half of its batch."""
import os
import time

import pytest

from bench import arrivals, cell, check, faults

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _bench():
    bm = dict(cell.load_benchmark())
    bm["configs"] = [{"name": "tiny", "file": "bench/tests/data/tiny.json"}]
    bm["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                        "traffic": "tiny-chat", "chips": 1}]
    bm["per_layer"] = [dict(m, workloads=["tiny.chat"])
                       for m in bm["per_layer"]]
    return bm


def _run(**kw):
    return cell.run("tiny.chat", 2 ** 31 + 5, 2.0, False,
                    t_proc=time.perf_counter(), require_tpu=False,
                    bench=_bench(),
                    mix=arrivals.load_mix("tiny-chat", DATA), **kw)


def test_train_numbers_required_unless_the_mix_trains_not():
    served = {"gap_max": 0.01, "ctrl_gap_max": 0.5}
    got = check.merge(served, None, "program")
    assert set(got) == {"served_gap_max", *check.TRAIN_NUMBERS}
    assert all(got[k] == float("inf") for k in check.TRAIN_NUMBERS)
    assert check.merge(served, None, "program", trains=False) == {
        "served_gap_max": 0.01}


@pytest.mark.parametrize("fault", [None, "control", "altered_token",
                                   "unchanged_state", "half_batch"])
def test_correct_only_when_sound(fault):
    res = _run(control=fault == "control", fault=faults.FAULTS.get(fault))
    line = res.line
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(line)[-1] == "checks"
    checks = line["checks"]
    over = {k for k, c in checks.items() if c["value"] > c["limit"]}
    if fault != "altered_token":
        # an altered token also leaves the trained windows unfound
        assert res.notes["train_windows_unlocated"] == 0
    if fault is None:
        assert line["correct"] is True, checks
        assert set(line["metrics"]) == {
            m["name"] for m in cell.load_benchmark()["end_to_end"]}
        assert res.notes["compiles_in_window"] == 0
        return
    assert line["correct"] is False
    want = {"control": {"served_gap_max", "signal_feat_err",
                        "train_loss_err"},
            "altered_token": {"served_gap_max"},
            "unchanged_state": {"train_change_err"},
            "half_batch": {"train_loss_err", "train_grad_err"}}[fault]
    assert want <= over, checks
